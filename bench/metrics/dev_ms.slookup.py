"""Device programs: device milliseconds per run of ``slookup`` in the
window, from the profiler trace."""
from yardstick.trace import ms_per_run


def read(m):
    return ms_per_run(m.trace, "slookup") if m.trace else None
