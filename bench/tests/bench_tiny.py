"""Shared by the benchmark's tests: import paths and a tiny copy of a cell
(a test-only override of its configuration and mix) for the CPU."""
import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from yardstick.spec import load_cell  # noqa: E402

SEED = 2**31 + 12345          # seeds past 32 signed bits are valid


def tiny_cell(name: str):
    """The cell with 12k keys over 2 shards, 48 clients and one pad width."""
    cell = load_cell(ROOT, name)
    config = dict(cell.config, n_keys=12_000, n_shards=2,
                  gateway={"max_batch": 256, "max_delay_s": 0.002})
    return dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, clients=48))


def quick(monkeypatch):
    """Shorter warm-up and stream for CPU runs of a tiny cell."""
    from yardstick import cell as cellmod

    monkeypatch.setattr(cellmod, "WARM_MIN_S", 0.3)
    monkeypatch.setattr(cellmod, "WARM_CAP_S", 4.0)
    monkeypatch.setattr(cellmod, "WARM_WAVES", 4)
    monkeypatch.setattr(cellmod, "STREAM_LEN", 1 << 14)
    return cellmod
