"""Router: host milliseconds per wave inside ``router.apply_wave`` that are
not spent waiting for a device result (``router.wait``): routing, padding,
op logs, launches and the compiles they trigger. Program spans,
``gw.stats()["obs"]`` deltas over the window's waves."""


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    if b is None or a is None or waves <= 0:
        return None

    def total(name):
        return (a["spans"].get(name, {}).get("total_s", 0.0)
                - b["spans"].get(name, {}).get("total_s", 0.0))

    if total("router.apply_wave") <= 0:
        return None
    return 1e3 * (total("router.apply_wave") - total("router.wait")) / waves
