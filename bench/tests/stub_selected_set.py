"""A new per-layer metric, as test_layout.py adds one: copied into
``bench/metrics/`` it reads a counter of the program that no other
reader reads, by its key, from the fused calls inside the window: the
mean size of XShare's selected expert set per layer and step."""
import numpy as np


def read(run):
    vals = [np.asarray(e[5]["selected_set"], float)
            for e in run.fused_in_window()]
    return float(np.concatenate(vals).mean()) if vals else None
