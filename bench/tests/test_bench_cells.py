"""Each cell runs end to end on the CPU at a tiny size: the whole harness
but the look for a chip, both with and without tracing."""
import time

import pytest

from bench_tiny import ROOT, SEED, quick, tiny_cell
from yardstick.spec import load_cell

CELLS = ["wikits24m_s8.ycsb_a", "fb24m_s8.ycsb_c"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_end_to_end_metrics(name, monkeypatch):
    cellmod = quick(monkeypatch)
    cell = tiny_cell(name)
    res = cellmod.run_cell(cell, SEED, 1.0, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"ops_per_s", "setup_s"} <= set(res["metrics"])
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    for c in res["checks"].values():
        assert c["value"] <= c["limit"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_per_layer_metrics(name, monkeypatch, tmp_path):
    cellmod = quick(monkeypatch)
    cell = tiny_cell(name)
    res = cellmod.run_cell(cell, SEED + 1, 1.0, True, time.perf_counter(),
                           trace_dir=str(tmp_path / "trace"))
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # the device readers find no chip plane on the CPU and leave their
    # metrics out; the host readers need a wave that ends in the window,
    # which a CPU compile of the tuner's commit can push out of it
    assert "compiles_in_window" in m
    assert not {"dev_ms.slookup", "device_idle_share"} & set(m)
    if "gw_ops_per_wave" in m:
        assert m["gw_ops_per_wave"]["value"] > 0
        assert "router_ms_per_wave" in m
    assert set(m) <= {p["name"] for p in cell.per_layer}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not (tmp_path / "trace").exists()


@pytest.mark.parametrize("name", CELLS)
def test_tuner_keeps_the_layouts_shard_count(name):
    """The configuration caps the tuner's shards at its layout's, so the
    controller may not split a shard; without the cap it may. The tuner
    explores as the configuration says, and builds overlap serving."""
    import numpy as np
    from repro.tuning import A_SPLIT_SHARD
    from yardstick.cell import build_program

    cell = tiny_cell(name)
    assert cell.config["tuner"]["controller"]["max_shards"] == load_cell(
        ROOT, name).config["n_shards"]
    keys = np.arange(1, 24_001, dtype=np.int64) * 7  # 12k keys a shard
    for cap, may_split in ((cell.config["n_shards"], False), (None, True)):
        tuner = dict(cell.config["tuner"], controller={"max_shards": cap} if cap else {})
        dep = build_program(dict(cell.config, tuner=tuner), keys, keys)
        try:
            t = dep.gw.tuner
            mask = t.controller.action_mask(t.telemetry.snapshot(dep.index), 0)
            assert t.scheduler.cfg.explore == tuner.get("scheduler", {}).get(
                "explore", True)
            assert t.scheduler.cfg.async_build and t.scheduler.cfg.max_concurrent_builds == 2
        finally:
            dep.close()
        assert bool(mask[A_SPLIT_SHARD]) == may_split
