"""Device idle ms per decode round of the scheduler: the time with no
operation on the device between the starts of the first and the last
program ``serve/decode`` span in the traced slice, over the rounds that
started in between (tracing.idle_ms_per_round)."""
import tracing as tr


def read(run):
    return None if run.trace is None else tr.idle_ms_per_round(run.trace)
