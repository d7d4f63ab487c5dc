"""Key sets of the configurations, one file each under ``bench/keysets/``.

A configuration names its key set (``"keyset": "<name>"``); the file
``keysets/<name>.py`` has ``make(n, seed)``, which returns ``n`` unique
sorted int64 keys below ``MAX_KEY``. The generators live with the
benchmark, so that the data a cell serves cannot change with the program.
"""
from __future__ import annotations

import pathlib

import numpy as np

MAX_KEY = 1 << 52


def unique_pad(keys: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """The first ``n`` distinct ``keys``, topped up with uniform draws
    where duplicates left fewer than ``n``."""
    keys = np.unique(keys)
    while len(keys) < n:
        extra = rng.integers(0, MAX_KEY, size=2 * (n - len(keys)))
        keys = np.unique(np.concatenate([keys, extra]))
    return np.sort(keys[:n]).astype(np.int64)


def make_keys(bench_dir: pathlib.Path, name: str, n: int, seed: int) -> np.ndarray:
    from yardstick.spec import load_module

    keys = load_module(bench_dir, "keysets", name).make(n, seed)
    if len(keys) != n or keys.dtype != np.int64 or (np.diff(keys) <= 0).any():
        raise ValueError(f"key set {name!r} did not give {n} sorted distinct "
                         "int64 keys")
    if keys[0] < 0 or keys[-1] >= MAX_KEY:
        raise ValueError(f"key set {name!r} left [0, 2**52)")
    return keys
