"""The device's peak_bytes_in_use after the window, in GB (1e9 B)."""


def read(run):
    return run.peak_bytes / 1e9
