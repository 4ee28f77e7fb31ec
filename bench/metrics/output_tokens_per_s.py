"""Output tokens delivered to clients inside the window, over the
window's seconds (host clock, client side)."""


def read(run):
    return run.tokens_in_window / run.seconds
