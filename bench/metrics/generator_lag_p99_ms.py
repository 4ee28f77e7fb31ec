"""99th percentile of how late the open-loop sender sent each request
due inside the window, in ms (host clock)."""
from runner import percentile


def read(run):
    if run.mix["loop"] != "open":
        return None
    vals = [(c.sent - c.due) * 1e3 for c in run.clients
            if run.t_open <= c.due < run.t_close]
    return percentile(vals, 99) if vals else None
