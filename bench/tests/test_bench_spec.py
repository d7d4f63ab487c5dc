"""Cells, configurations, mixes and per-layer metrics are found by name,
so that one is added by adding files and entries, and none is edited."""
import json
import os
import shutil
import sys
import time
import types

import pytest

from bench_tiny import BENCH, ROOT, SEED, quick
from yardstick import spec

# an op kind, a key set and a metric that exist only in a test's directory
PROBE_OP = '''"""Op kind ``probe``: a lookup of a key drawn uniformly from the key
range, so that about half of them miss."""
import numpy as np

QUEUE = "lookup"


def draw(keys, rng, size):
    return rng.integers(keys[0], keys[-1] + 1, size), np.zeros(size, np.int64)


def send(gw, key, arg):
    return gw.submit_lookup(key)


def encode(answer):
    return bool(answer[0]), int(answer[1])


def replay(ref, key, arg, found, got):
    f, v = ref.lookup(key)
    return (found != f) | (got != v)


def prime(key, val, width):
    return {"lookup_keys": np.asarray([key], np.int64), "pad_lookup": width}
'''

STEPS_KEYSET = '''import numpy as np


def make(n, seed):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.integers(1, 4, n)).astype(np.int64)
'''


def write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    write(tmp_path / "BENCHMARK.json", json.dumps({
        "paths": ["bx"],
        "workloads": [{"name": "c9.m9", "config": "c9", "traffic": "m9",
                       "chips": 1, "why": "t"}],
        "end_to_end": [{"name": "ops_per_s", "unit": "ops/s"}],
        "per_layer": [
            {"name": "x.p50", "unit": "ms"},
            {"name": "silent", "unit": "%"},
            {"name": "elsewhere", "unit": "ms", "workloads": ["other.cell"]},
        ]}))
    write(tmp_path / "bx" / "configs" / "c9.json", '{"name": "c9", "n_keys": 7}')
    write(tmp_path / "bx" / "traffic" / "m9.json",
          '{"name": "m9", "clients": 3, "ops": {"probe": 1.0}}')
    write(tmp_path / "bx" / "ops" / "probe.py", PROBE_OP)
    write(tmp_path / "bx" / "metrics" / "x.p50.py",
          "def read(m):\n    return 2 * m.n\n")
    write(tmp_path / "bx" / "metrics" / "silent.py", "def read(m):\n    return None\n")
    cell = spec.load_cell(tmp_path, "c9.m9")
    assert cell.config["n_keys"] == 7 and cell.traffic["clients"] == 3
    assert [op.NAME for op in cell.ops] == ["probe"]
    assert [m["name"] for m in cell.per_layer] == ["x.p50", "silent"]
    assert spec.read_metrics(cell, types.SimpleNamespace(n=21)) == {
        "x.p50": {"value": 42.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        spec.load_cell(tmp_path, "nope")


def test_cell_of_new_files_only_runs_correct(tmp_path, monkeypatch):
    """A configuration with its own key set and a mix with its own op kind,
    added as files and entries beside the benchmark's, run end to end."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "yardstick"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "steps6k_s2.mix9", "config": "steps6k_s2",
                               "traffic": "mix9", "chips": 1, "why": "t"})
    write(tmp_path / "BENCHMARK.json", json.dumps(bench))
    write(tmp_path / "bench" / "keysets" / "steps.py", STEPS_KEYSET)
    write(tmp_path / "bench" / "ops" / "probe.py", PROBE_OP)
    write(tmp_path / "bench" / "configs" / "steps6k_s2.json", json.dumps({
        "name": "steps6k_s2", "keyset": "steps", "n_keys": 6000, "data_seed": 3,
        "n_shards": 2, "gateway": {"max_batch": 256, "max_delay_s": 0.002},
        "tuner": {"max_concurrent_builds": 2, "commit_replay_cap": 4096}}))
    write(tmp_path / "bench" / "traffic" / "mix9.json", json.dumps({
        "name": "mix9", "clients": 32,
        "ops": {"read": 0.3, "update": 0.3, "probe": 0.4}}))

    cell = spec.load_cell(tmp_path, "steps6k_s2.mix9")
    assert [op.NAME for op in cell.ops] == ["read", "update", "probe"]
    probe = cell.ops[2]
    seen = []
    encode = probe.encode
    monkeypatch.setattr(probe, "encode", lambda a: seen.append(encode(a)) or seen[-1])
    cellmod = quick(monkeypatch)
    res = cellmod.run_cell(cell, SEED, 1.0, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    found = [f for f, _ in seen]
    assert any(found) and not all(found)


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["bench"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert set(c["reduced"]) <= set(body["reduced"])
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert w["config"] in configs
        assert cell.traffic["name"] == w["traffic"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        assert spec.load_module(BENCH, "keysets", cell.config["keyset"])
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(BENCH, m["name"]))


def test_run_refuses_without_a_tpu(monkeypatch, capsys):
    import runpy

    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", ""))
    run = runpy.run_path(str(BENCH / "run.py"), run_name="bench_run")
    rc = run["main"](["--workload", "fb24m_s8.ycsb_c", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "needs 1 TPU chip" in out.err


def test_two_write_ops_on_one_queue_are_refused(tmp_path):
    """Their replays would each see only their own requests of a wave, so
    the last write of a key across both could not be told."""
    for name in ("put_a", "put_b"):
        write(tmp_path / "ops" / f"{name}.py", 'QUEUE = "insert"\nWRITES = True\n')
    with pytest.raises(ValueError):
        spec.load_ops(tmp_path, {"name": "m", "ops": {"put_a": 0.5, "put_b": 0.5}})
