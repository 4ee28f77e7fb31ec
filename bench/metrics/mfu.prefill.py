"""Model FLOP utilisation of the prefill calls in the traced slice, in
%: work.py prefill_flops of every prompt they took over the device's
busy time inside those calls times the bf16 peak."""
import reading
import tracing as tr
import work


def read(run):
    if run.trace is None:
        return None
    spans = reading.traced_calls(run, "prefill")
    if not spans:
        return None
    flops = sum(run.events[call][1].shape[0]
                * work.prefill_flops(run.z, run.events[call][1].shape[1])
                for _, _, call in spans)
    busy = tr.busy_within_ns(run.trace, spans) / 1e9
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])
