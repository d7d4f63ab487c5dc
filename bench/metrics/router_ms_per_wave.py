"""Router: host milliseconds per ``index.apply_wave`` call begun in the
window (routing, padding, the device programs it waits for, host syncs)."""


def read(m):
    spans = m.spans_in_window("router")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
