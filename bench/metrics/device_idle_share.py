"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-operation intervals) / window."""
from devtrace import busy_s


def read(run):
    tr = run.trace
    if tr is None or tr.window is None:
        return None
    busy = busy_s(tr)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / tr.window_s)
