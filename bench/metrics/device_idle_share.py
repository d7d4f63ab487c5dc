"""Device: share of the window in which the chip ran no operation, in %
(1 - union of device-operation intervals / window)."""
from yardstick.trace import busy_seconds, window_seconds


def read(m):
    if m.trace is None or not m.trace.ops:
        return None
    return 100.0 * (1.0 - busy_seconds(m.trace) / window_seconds(m.trace))
