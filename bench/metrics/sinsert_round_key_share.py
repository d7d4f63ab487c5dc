"""Device programs: share, in %, of a write wave's keys still pending when
``sinsert``'s accept rounds start, after the probe resolved the keys
already stored (the program's ``sinsert.round_keys`` over
``sinsert.keys``, ``gw.stats()["obs"]`` deltas). The rounds run at full
width whatever the share."""


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    if b is None or a is None:
        return None

    def delta(name):
        return a["counters"].get(name, 0) - b["counters"].get(name, 0)

    keys = delta("sinsert.keys")
    if keys <= 0:
        return None
    return 100.0 * delta("sinsert.round_keys") / keys
