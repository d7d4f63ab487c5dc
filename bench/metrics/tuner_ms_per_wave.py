"""Tuner: host milliseconds per wave spent in ``tuner.after_wave`` and
``tuner.observe_inserts`` (between waves, on the gateway's thread)."""


def read(m):
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    spans = m.spans_in_window("tuner")
    if waves <= 0 or not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / waves
