"""Compiles and persistent-cache loads that happened inside the window;
every program should have been warmed before it opens."""


def read(run):
    return len(run.compile_events)
