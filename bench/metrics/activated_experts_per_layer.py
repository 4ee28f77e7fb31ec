"""Mean number of experts that received any token, per layer per
decode step (the program's own counter), over the steps of the fused
calls that started inside the window and had a live slot."""
import numpy as np


def read(run):
    vals = []
    for e in run.fused_in_window():
        ok, act = e[2][1], e[2][3]
        if act is None:
            return None
        live = ok.any(axis=1)
        vals.append(np.asarray(act, float)[live])
    vals = [v for v in vals if v.size]
    return float(np.concatenate(vals).mean()) if vals else None
