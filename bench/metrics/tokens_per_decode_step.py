"""Tokens the fused decode scan produced per decode step, over the
calls that started inside the window: the batch's occupancy."""


def read(run):
    calls = run.fused_in_window()
    steps = sum(e[2][1].shape[0] for e in calls)
    toks = sum(int(e[2][1].sum()) for e in calls)
    return toks / steps if steps else None
