"""Gateway: requests per wave in the window (``gw.stats()`` deltas).
Fuller waves spread the fixed cost of a wave over more requests."""


def read(m):
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    if waves <= 0:
        return None
    return (m.gw_after["ops"] - m.gw_before["ops"]) / waves
