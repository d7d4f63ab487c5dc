"""Device programs: accept rounds ``sinsert`` ran per call (the program's
``sinsert.rounds`` over ``sinsert.calls``, ``gw.stats()["obs"]`` deltas).
The rounds stop once no key is pending or a round placed none, so a wave
whose keys the probe resolved runs none."""


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    if b is None or a is None or "sinsert.calls" not in a["counters"]:
        return None

    def delta(name):
        return a["counters"].get(name, 0) - b["counters"].get(name, 0)

    calls = delta("sinsert.calls")
    if calls <= 0 or "sinsert.rounds" not in a["counters"]:
        return None
    return delta("sinsert.rounds") / calls
