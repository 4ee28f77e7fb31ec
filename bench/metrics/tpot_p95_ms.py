"""95th percentile, over requests completed inside the window, of each
request's time per output token after the first:
(t_done - t_first) / (tokens - 1), in ms. Tokens reach the client per
fused decode call, so a single gap between two tokens is not seen; a
request's mean rate is."""
from runner import percentile


def read(run):
    vals = [(c.done - c.first) / (c.seen - 1) * 1e3 for c in run.clients
            if c.reason == "completed" and c.seen > 1
            and run.t_open <= c.done < run.t_close]
    return percentile(vals, 95) if vals else None
