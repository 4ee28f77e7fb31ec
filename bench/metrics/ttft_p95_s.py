"""95th percentile, over every request due inside the window, of the
time from when it was due to when its client saw the first token. A
request that failed or never saw one counts as missing (+inf)."""
import math

from runner import percentile


def read(run):
    vals = []
    for c in run.clients:
        if run.t_open <= c.due < run.t_close:
            ok = c.reason in ("completed", "cancelled") and \
                not math.isnan(c.first)
            vals.append(c.first - c.due if ok else math.inf)
    return percentile(vals, 95) if vals else None
