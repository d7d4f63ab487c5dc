"""Finds a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell, its
configuration and its traffic mix. Under the benchmark's directory:

  configs/<config>.json     the deployment: key set, scale, layout
  keysets/<keyset>.py       a configuration's key set: ``make(n, seed)``
  traffic/<traffic>.json    the mix the one generator reads: its clients
                            and a table of op kind -> proportion
  ops/<op>.py               one op kind: how it draws, sends, answers and
                            replays against the reference
  metrics/<metric>.py       one per-layer metric: ``read(measured)``

A later cell, mix or metric is added as files and entries; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import types
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    ops: tuple                   # the mix's op kinds (modules), in its order
    end_to_end: List[dict]       # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    bench_dir: pathlib.Path


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: pathlib.Path, name: str) -> Cell:
    root = pathlib.Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    config = json.loads((bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        ops=load_ops(bench_dir, traffic),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir,
    )


_MODULES: Dict[pathlib.Path, types.ModuleType] = {}


def load_module(bench_dir: pathlib.Path, kind: str, name: str) -> types.ModuleType:
    """``<kind>/<name>.py`` under the benchmark's directory, loaded once;
    the module's ``NAME`` is ``name``."""
    path = (pathlib.Path(bench_dir) / kind / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(path)
        mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", str(path))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.NAME = name
        _MODULES[path] = mod
    return _MODULES[path]


def load_ops(bench_dir: pathlib.Path, mix: dict) -> tuple:
    """The op kinds of a mix's ``ops`` table, in its order. Two op kinds
    that write through one gateway queue would need one replay in
    submission order across both, so a mix may not have them."""
    ops = tuple(load_module(bench_dir, "ops", name) for name in mix["ops"])
    queues = [op.QUEUE for op in ops if getattr(op, "WRITES", False)]
    if len(queues) != len(set(queues)):
        raise ValueError(f"mix {mix.get('name')!r}: two op kinds write "
                         f"through one gateway queue ({queues})")
    return ops


def load_reader(bench_dir: pathlib.Path, metric: str) -> Callable:
    """``read`` of ``metrics/<metric>.py``: takes the traced run's
    ``Measured`` and returns a number, or None where it finds nothing."""
    return load_module(bench_dir, "metrics", metric).read


def read_metrics(cell: Cell, measured) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        v: Optional[float] = load_reader(cell.bench_dir, m["name"])(measured)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
