"""The reader of ``sinsert_rounds_per_call``: accept rounds over ``sinsert``
calls, from the program's counter deltas; silent where the program has no
such counters (the parent's) or made no call."""
import time

import pytest

from bench_tiny import BENCH, SEED, quick, tiny_cell
from yardstick.cell import Measured
from yardstick.spec import load_reader

NAME = "sinsert_rounds_per_call"


def _measured(before, after):
    def stats(waves, counters):
        if counters is None:
            return {"waves": waves}
        return {"waves": waves,
                "obs": {"spans": {}, "counters": counters, "dropped": 0}}

    return Measured(t0=0.0, t1=1.0, rows={}, gw_before=stats(10, before),
                    gw_after=stats(14, after), spans={}, compiles=[])


def read(m):
    return load_reader(BENCH, NAME)(m)


@pytest.mark.parametrize("before,after,want", [
    ({"sinsert.calls": 10, "sinsert.rounds": 12},
     {"sinsert.calls": 30, "sinsert.rounds": 47}, 1.75),      # 35 / 20
    ({"sinsert.calls": 10, "sinsert.rounds": 0},
     {"sinsert.calls": 14, "sinsert.rounds": 0}, 0.0),        # probe placed all
    ({}, {"sinsert.calls": 4, "sinsert.rounds": 12}, 3.0),    # first call in window
])
def test_rounds_per_call_from_counter_deltas(before, after, want):
    assert read(_measured(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    (None, None),                                             # no recorder
    ({"sinsert.keys": 10}, {"sinsert.keys": 90}),             # the parent's
    ({"sinsert.calls": 5, "sinsert.rounds": 3},
     {"sinsert.calls": 5, "sinsert.rounds": 3}),              # no call
])
def test_rounds_per_call_is_none_without_calls(before, after):
    assert read(_measured(before, after)) is None


def test_traced_ycsb_a_runs_no_round(monkeypatch, tmp_path):
    """YCSB A updates only stored keys: the probe places every one, so a
    traced tiny run reads no accept round per call."""
    cellmod = quick(monkeypatch)
    cell = tiny_cell("wikits24m_s8.ycsb_a")
    assert NAME in {m["name"] for m in cell.per_layer}
    res = cellmod.run_cell(cell, SEED + 2, 1.0, True, time.perf_counter(),
                           trace_dir=str(tmp_path / "trace"))
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # a CPU compile can push every wave out of the window; a wave in it
    # makes a call
    if "gw_ops_per_wave" in m:
        assert m[NAME]["value"] == 0.0
