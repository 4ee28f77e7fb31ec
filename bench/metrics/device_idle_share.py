"""Share of the traced slice of the window in which no operation ran on
the device, in %: 1 - (union of device-operation intervals) / slice."""


def read(run):
    if run.trace_stats is None:
        return None
    s = run.trace_stats
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
