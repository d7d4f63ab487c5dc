"""jit: programs compiled, or loaded from the persistent cache, in the
window while a ``tuner.*`` span was the innermost open span of the
compiling thread (the program's ``compiles.tuner.*`` counters,
``gw.stats()["obs"]`` deltas)."""
PREFIX = "compiles.tuner."


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    if b is None or a is None:
        return None
    return float(sum(v - b["counters"].get(k, 0)
                     for k, v in a["counters"].items() if k.startswith(PREFIX)))
