"""One minus the union of device-operation intervals over the traced
window."""
from bench.trace import busy_s


def read(w):
    if w.trace is None or w.trace.window_s <= 0 or not w.trace.ops:
        return None
    return 100.0 * (1.0 - busy_s(w.trace) / w.trace.window_s)
