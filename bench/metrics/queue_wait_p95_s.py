"""95th percentile, over requests due inside the window, of the wait
from when each was due to the start of the prefill that admitted it
(the scheduler's queue, host clock)."""
from runner import percentile


def read(run):
    start = {}
    for ev in run.events:
        if ev[0] == "prefill":
            for row in ev[1]:
                start.setdefault(row.tobytes(), ev[2])
    vals = [start[c.req.prompt.tobytes()] - c.due for c in run.clients
            if run.t_open <= c.due < run.t_close
            and c.req.prompt.tobytes() in start]
    return percentile(vals, 95) if vals else None
