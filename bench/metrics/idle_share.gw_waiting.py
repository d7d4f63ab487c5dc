"""Device: share of the window, in %, in which the chip ran no operation
and the gateway's flusher thread had no program span open, so it waited
for requests. The flusher's spans come from the program's ring of raw
spans (``repro.obs.RECORDER``), mapped onto the profiler clock by the
window's anchor: ``t0`` on the host clock is the start of the trace's
``bench.window`` span. None when the ring dropped spans of the window."""
from yardstick.trace import busy, clip, merge


def read(m):
    if m.trace is None or not m.trace.ops:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    t0_ns = round(m.t0 * 1e9)
    spans = obs.RECORDER.spans(t0_ns, round(m.t1 * 1e9))
    if spans is None:
        return None
    flusher = {s.thread for s in spans if s.name == "gateway.wave"}
    if not flusher:
        return None
    lo, hi = m.trace.window
    shift = lo - t0_ns
    host = [(s.start_ns + shift, s.end_ns + shift) for s in spans
            if s.thread in flusher]
    covered = merge(busy(m.trace) + clip(host, lo, hi))
    return 100.0 * (1.0 - sum(e - s for s, e in covered) / (hi - lo))
