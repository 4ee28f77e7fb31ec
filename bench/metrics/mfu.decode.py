"""Model FLOP utilisation of the fused decode calls in the traced slice,
in %: the model operations of every live token they decoded (work.py
token_flops, at each token's real context) over the device's busy time
inside those calls times the bf16 peak."""
import reading
import tracing as tr
import work


def read(run):
    if run.trace is None:
        return None
    spans = reading.traced_calls(run, "fused_decode")
    if not spans:
        return None
    ctx = reading.contexts(run.events, run.mix["slots"])
    flops = sum(work.token_flops(run.z, int(c)) for _, _, call in spans
                for step in ctx[call] for c in step)
    busy = tr.busy_within_ns(run.trace, spans) / 1e9
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])
