"""Seconds from the start of the process to the opening of the window:
drawing the weights and the traffic, warming (and, in a cell's first
run, compiling) every program, and the traffic before the window."""


def read(run):
    return run.t_open - run.t_process
