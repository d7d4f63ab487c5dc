"""Request streams of YCSB's CoreWorkload, drawn from a seed.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``);
this one generator reads every mix:

  clients   closed-loop virtual clients, no think time
  ops       a table of op kind -> proportion; each op kind is a file of
            its own, ``bench/ops/<op>.py``, that draws its requests' keys
            and arguments (YCSB's read and update draw the scrambled
            zipfian below over the bootstrap keys)

The stream is fixed by the seed: request i has the same op kind, key and
argument in every run of that seed, whichever client sends it and when.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# YCSB ScrambledZipfianGenerator: a zipfian (constant 0.99) over a fixed
# 10**10 items with its precomputed zeta, hashed (FNV-1a 64) onto the real
# item count.
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZIPFIAN_CONSTANT = 0.99
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

# written values stay non-negative and far from the index's tombstone value
VALUE_LIMIT = 1 << 62


@dataclasses.dataclass(frozen=True)
class Stream:
    op: np.ndarray     # int8 [L]: index into the mix's op kinds
    key: np.ndarray    # int64 [L]: the key sent
    arg: np.ndarray    # int64 [L]: the op's argument (an update's value)

    def __len__(self) -> int:
        return len(self.op)


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of a long, then
    the absolute value of the signed result."""
    v = np.asarray(x, dtype=np.int64).view(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray, items: int, theta: float, zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` (Gray et al., SIGMOD 1994) for
    uniform draws ``u`` in [0, 1): rank 0 is the most popular item."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def scrambled_zipfian(rng: np.random.Generator, size: int, n: int) -> np.ndarray:
    """Item indices in [0, n) with YCSB's scrambled zipfian popularity."""
    z = zipfian(rng.random(size), YCSB_ITEM_COUNT, YCSB_ZIPFIAN_CONSTANT,
                YCSB_ZETAN)
    return fnvhash64(z) % n


def make_stream(mix: dict, ops: tuple, keys: np.ndarray,
                rng: np.random.Generator, length: int) -> Stream:
    """``length`` requests of ``mix`` over the sorted bootstrap ``keys``;
    ``ops`` are the mix's op kinds, in the order of its ``ops`` table."""
    shares = np.array([float(p) for p in mix["ops"].values()])
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"op proportions of {mix['name']} do not sum to 1")
    op = np.searchsorted(np.cumsum(shares), rng.random(length), side="right")
    op = np.minimum(op, len(ops) - 1).astype(np.int8)
    key = np.zeros(length, np.int64)
    arg = np.zeros(length, np.int64)
    for i, kind in enumerate(ops):
        sel = np.nonzero(op == i)[0]
        key[sel], arg[sel] = kind.draw(keys, rng, len(sel))
    return Stream(op=op, key=key, arg=arg)
