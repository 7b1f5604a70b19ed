"""Every output token emitted in the window, over the window's length
(host clock)."""
from measures import tokens_in_window


def read(run):
    n = tokens_in_window(run)
    return n / run.seconds if n else None
