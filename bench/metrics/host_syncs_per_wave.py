"""Router: blocking device-to-host reads per wave, at every site of the
served path (router results, the BMAT size, telemetry, the forecaster):
the program's ``host_syncs`` counter, ``gw.stats()["obs"]`` deltas over the
window's waves."""


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    if b is None or a is None or waves <= 0:
        return None
    syncs = a["counters"].get("host_syncs", 0) - b["counters"].get("host_syncs", 0)
    return syncs / waves
