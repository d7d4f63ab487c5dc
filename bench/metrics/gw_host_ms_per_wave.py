"""Gateway: host milliseconds per wave in the gateway's own code on the
flusher thread: the self time of the program's ``gateway.wave``,
``gateway.drain`` and ``gateway.complete`` spans (``gw.stats()["obs"]``
deltas over the window's waves)."""
SPANS = ("gateway.wave", "gateway.drain", "gateway.complete")


def read(m):
    b, a = m.gw_before.get("obs"), m.gw_after.get("obs")
    waves = m.gw_after["waves"] - m.gw_before["waves"]
    if b is None or a is None or waves <= 0:
        return None

    def self_s(obs, name):
        return obs["spans"].get(name, {}).get("self_s", 0.0)

    return 1e3 * sum(self_s(a, n) - self_s(b, n) for n in SPANS) / waves
