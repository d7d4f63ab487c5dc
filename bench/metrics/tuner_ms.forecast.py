"""Tuner: host milliseconds per wave in the forecaster's streaming EM step
(E-step and M-step over the wave's inserts): the program's
``tuner.forecast`` span, ``gw.stats()["obs"]`` deltas over the window's
waves."""


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    if b is None or a is None or waves <= 0:
        return None
    name = "tuner.forecast"
    if name not in a["spans"]:
        return None
    t = a["spans"][name]["total_s"] - b["spans"].get(name, {}).get("total_s", 0.0)
    return 1e3 * t / waves
