"""The readers of the program's own spans and counters, on a fixed
``Measured``, a fixed recorder and a fixed trace."""
import sys

import pytest

from bench_tiny import BENCH
from yardstick import trace as tr
from yardstick.cell import Measured
from yardstick.spec import load_reader

from repro import obs

MS = 1_000_000  # ns
T0 = 5.0        # the window on the host clock, seconds
T1 = 5.1
ANCHOR = 1000 * MS  # start of ``bench.window`` on the profiler clock
FLUSHER, WORKER = 11, 22

NEW = ("gw_host_ms_per_wave", "router_host_ms_per_wave", "host_syncs_per_wave",
       "tuner_ms.forecast", "tuner_ms.telemetry", "compiles_in_window.tuner",
       "compiles_in_window.router", "sinsert_round_key_share",
       "idle_share.gw_waiting")


def _obs(spans, counters):
    return {"spans": {n: {"count": c, "total_s": t, "self_s": s}
                      for n, (c, t, s) in spans.items()},
            "counters": counters, "dropped": 0}


BEFORE = _obs(
    {"gateway.wave": (10, 1.0, 0.010), "gateway.drain": (10, 0.002, 0.002),
     "router.apply_wave": (10, 0.5, 0.001), "router.wait": (12, 0.3, 0.3),
     "tuner.telemetry": (10, 0.2, 0.2)},
    {"host_syncs": 40, "compiles.tuner.forecast": 3, "compiles.router.launch": 1,
     "compiles.none": 50, "sinsert.keys": 1000, "sinsert.round_keys": 10})
AFTER = _obs(
    {"gateway.wave": (14, 1.4, 0.014), "gateway.drain": (14, 0.003, 0.003),
     "gateway.complete": (4, 0.004, 0.004),
     "router.apply_wave": (14, 0.7, 0.0014), "router.wait": (17, 0.38, 0.38),
     "tuner.forecast": (4, 0.06, 0.06), "tuner.telemetry": (14, 0.28, 0.28)},
    {"host_syncs": 68, "compiles.tuner.forecast": 6, "compiles.tuner.decide": 1,
     "compiles.router.launch": 3, "compiles.none": 60, "sinsert.keys": 5000,
     "sinsert.round_keys": 50})


def _recorder(monkeypatch, ring=64):
    """Spans on the host clock, recorded through ``span`` with a scripted
    clock: the flusher busy 10-40 ms into the window (a child 15-35) and
    60-70 ms, a worker thread busy 80-100 ms."""
    rec = obs.Recorder(ring=ring)
    t0 = round(T0 * 1e9)
    clock = iter(t0 + ms * MS for ms in (10, 15, 35, 40, 60, 70, 80, 100))
    thread = [FLUSHER]
    monkeypatch.setattr(obs, "_now", lambda: next(clock))
    monkeypatch.setattr(obs, "_thread", lambda: thread[0])
    with rec.span("gateway.wave", wave=0):
        with rec.span("router.wait"):
            pass
    with rec.span("gateway.wave", wave=1):
        pass
    thread[0] = WORKER
    with rec.span("executor.build", arg=3):
        pass
    monkeypatch.undo()
    return rec


def _measured(trace=True, before=BEFORE, after=AFTER):
    # device busy 30-50 and 65-80 ms into the window, on the profiler clock
    t = tr.Trace(ops=[("fusion.1", ANCHOR + 30 * MS, ANCHOR + 50 * MS),
                      ("copy.2", ANCHOR + 65 * MS, ANCHOR + 80 * MS)],
                 programs=[],
                 spans={"window": [(ANCHOR, ANCHOR + 100 * MS)]})
    def stats(waves, o):
        return {"waves": waves} if o is None else {"waves": waves, "obs": o}

    return Measured(t0=T0, t1=T1, rows={}, gw_before=stats(100, before),
                    gw_after=stats(104, after), spans={}, compiles=[],
                    trace=t if trace else None)


@pytest.fixture
def fixed_recorder(monkeypatch):
    rec = _recorder(monkeypatch)
    monkeypatch.setattr(obs, "RECORDER", rec)
    return rec


def read(name, m):
    return load_reader(BENCH, name)(m)


def test_per_wave_readers_divide_deltas_by_waves(fixed_recorder):
    m = _measured()
    # gateway self time: (0.004 + 0.001 + 0.004) s over 4 waves
    assert read("gw_host_ms_per_wave", m) == pytest.approx(2.25)
    # apply_wave 0.2 s less 0.08 s of waits, over 4 waves
    assert read("router_host_ms_per_wave", m) == pytest.approx(30.0)
    assert read("host_syncs_per_wave", m) == pytest.approx(7.0)
    assert read("tuner_ms.forecast", m) == pytest.approx(15.0)
    assert read("tuner_ms.telemetry", m) == pytest.approx(20.0)


def test_compile_and_round_key_counters(fixed_recorder):
    m = _measured()
    assert read("compiles_in_window.tuner", m) == 4.0   # 3 forecast + 1 decide
    assert read("compiles_in_window.router", m) == 2.0
    assert read("sinsert_round_key_share", m) == pytest.approx(1.0)  # 40 of 4000


def test_idle_share_maps_ring_spans_by_the_window_anchor(fixed_recorder):
    # covered: device 30-50, 65-80; flusher 10-40, 60-70 -> 10-50, 60-80;
    # the worker's 80-100 is not the flusher's. Idle 0-10, 50-60, 80-100.
    assert read("idle_share.gw_waiting", _measured()) == pytest.approx(40.0)
    # against the device alone the window is 65 % idle: the spans matter
    assert 100 - 100 * tr.busy_seconds(_measured().trace) / 0.1 == pytest.approx(65.0)


def test_idle_share_is_none_when_the_ring_dropped_window_spans(monkeypatch):
    monkeypatch.setattr(obs, "RECORDER", _recorder(monkeypatch, ring=2))
    assert obs.RECORDER.dropped == 2
    assert read("idle_share.gw_waiting", _measured()) is None


def test_readers_find_nothing_without_the_programs_recorder(monkeypatch):
    """The parent's program has no ``obs`` stats and no ``repro.obs``: every
    new reader returns None and raises nothing."""
    import repro

    m = _measured(before=None, after=None)
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    for name in NEW:
        assert read(name, m) is None, name
    # no trace (CPU), or no inserts: the readers that need them say nothing
    m = _measured(trace=False, before=_obs({}, {}), after=_obs({}, {}))
    assert read("idle_share.gw_waiting", m) is None
    assert read("sinsert_round_key_share", m) is None
    assert read("tuner_ms.forecast", m) is None
    assert read("compiles_in_window.tuner", m) == 0.0
