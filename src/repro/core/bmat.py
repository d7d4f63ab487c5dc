"""BMAT — Balanced Model Adjustment Tree (Section 3.3).

The delta buffer for updates that cannot be accommodated in-place. It answers
two batched queries in O(log |U|):

  * ``rank(k)``  — number of buffered entries with key < k. This is the bias
    term r(k) of Definition 1 / Phase 1.
  * ``lookup(k)``— value of a buffered key.

Two physical types, mirroring the paper's RBMAT (Red-Black) and B+MAT (B+Tree):

  * RBMAT  — binary traversal with a BFS/Eytzinger index schedule over the
    packed sorted array: log2(cap) dependent gathers, no auxiliary arrays.
    This is the TPU-native analogue of a balanced binary tree (DESIGN.md §2).
  * B+MAT  — two-level fence tree: the fence array (every ``fanout``-th key)
    is searched first (VMEM-resident tile on TPU), then one bounded in-node
    search. Fused Pallas kernel in repro/kernels/bmat_rank.py.

Inserts are vectorized sorted merges of a batch (LSM-style amortization) —
the tensor analogue of O(log n) pointer insertion; "height" is the number of
dependent gathers a rank query performs, which is what drives the paper's
performance measure S1.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.shapes import pow2_at_least as _ceil_pow2  # §7.5 shared quant
from repro.core.types import BMATState, KEY_MAX, TOMBSTONE

RBMAT = "rbmat"
BPMAT = "b+mat"
_MIN_CAP = 4096  # generous floor: halves the compile-on-growth events


def bmat_height(size: int, tree_type: str, fanout: int) -> int:
    """Dependent-gather count of one rank query (performance measure S1).
    Shared by the BMAT wrapper and the shard router's aggregate measures."""
    n = max(size, 2)
    if tree_type == RBMAT:
        return int(np.ceil(np.log2(n)))
    return int(np.ceil(np.log2(max(n // fanout, 2)))) + int(
        np.ceil(np.log2(fanout))
    )


def _make_fences(keys: jnp.ndarray, fanout: int) -> jnp.ndarray:
    f = keys[::fanout]
    return jnp.concatenate([f, jnp.asarray([KEY_MAX], dtype=keys.dtype)])


# --------------------------------------------------------------------------
# batched rank (searchsorted-left semantics over the live prefix)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("levels",))
def _rank_rbmat(keys: jnp.ndarray, queries: jnp.ndarray, levels: int) -> jnp.ndarray:
    """Binary-tree descent over the sorted array using the complete-tree BFS
    schedule: at level l, node t inspects sorted index (2t+1)*2^(h-1-l) - 1.
    After h levels, t == searchsorted_left(keys, q). KEY_MAX padding keeps
    every probe in bounds."""
    cap = keys.shape[0]

    def body(l, t):
        stride = jnp.int64(1) << (levels - 1 - l)
        s = jnp.minimum((2 * t + 1) * stride - 1, cap - 1)
        go_right = keys[s] < queries
        return 2 * t + go_right.astype(t.dtype)

    t = jnp.zeros(queries.shape, dtype=jnp.int64)
    t = jax.lax.fori_loop(0, levels, body, t)
    return jnp.minimum(t, cap).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("fanout", "fence_iters", "node_iters"))
def _rank_bpmat(
    keys: jnp.ndarray,
    fences: jnp.ndarray,
    queries: jnp.ndarray,
    fanout: int,
    fence_iters: int,
    node_iters: int,
) -> jnp.ndarray:
    """Fence search (first fence >= q) then bounded in-node search."""
    nf = fences.shape[0]

    def fsearch(_, carry):
        lo, hi = carry  # invariant: fences[lo-1] < q <= fences[hi] (conceptually)
        mid = (lo + hi) >> 1
        go_right = fences[jnp.minimum(mid, nf - 1)] < queries
        return (jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid))

    lo = jnp.zeros(queries.shape, dtype=jnp.int64)
    hi = jnp.full(queries.shape, nf - 1, dtype=jnp.int64)
    lo, hi = jax.lax.fori_loop(0, fence_iters, fsearch, (lo, hi))
    # fence index f: first fence >= q → answer lies in node (f-1, f]
    node_lo = jnp.maximum(lo - 1, 0) * fanout
    cap = keys.shape[0]

    def nsearch(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        go_right = keys[jnp.minimum(mid, cap - 1)] < queries
        return (jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid))

    nlo = node_lo
    nhi = jnp.minimum(node_lo + fanout, cap)
    nlo, nhi = jax.lax.fori_loop(0, node_iters, nsearch, (nlo, nhi))
    return jnp.minimum(nlo, cap).astype(jnp.int32)


@jax.jit
def _scatter_oob(arr, idx, vals):
    """Scatter with out-of-bounds indices dropped (padding rows use OOB)."""
    return arr.at[idx].set(vals, mode="drop")


@jax.jit
def _lookup(keys, vals, ranks, queries):
    cap = keys.shape[0]
    idx = jnp.minimum(ranks.astype(jnp.int64), cap - 1)
    hit = (keys[idx] == queries) & (queries != KEY_MAX)
    val = vals[idx]
    alive = hit & (val != TOMBSTONE)
    return alive, jnp.where(alive, val, 0)


@jax.jit
def prefix_count(mark: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of an int32 0/1 marker, as int64.

    Log-step doubling (Hillis-Steele) instead of ``jnp.cumsum``: the TPU
    compiler's cumsum lowering takes seconds to minutes to compile at
    BMAT sizes (and its int64 form far longer), while log2(n) shifted adds
    compile in under a second and cost a few extra passes over a
    marker array. Exact while the count fits int32."""
    n = mark.shape[0]
    x = mark.astype(jnp.int32)
    s = 1
    while s < n:
        x = x + jnp.concatenate([jnp.zeros((s,), jnp.int32), x[:-s]])
        s *= 2
    return x.astype(jnp.int64)


def _merge(
    keys: jnp.ndarray,
    vals: jnp.ndarray,
    size: jnp.ndarray,
    new_keys: jnp.ndarray,
    new_vals: jnp.ndarray,
    n_new: jnp.ndarray,
):
    """Merge a sorted-unique batch (padded with KEY_MAX) into the packed
    arrays. Duplicate keys must have been routed to value-updates upstream.
    Returns (keys, vals, size) with the same capacity.

    Gather formulation (XLA CPU scatters are serial, so the classic
    two-scatter merge is the hot spot): only the q batch positions are
    scattered — into a marker and a row map — then every output slot pulls
    its element with a cumsum + two gathers.
    """
    cap = keys.shape[0]
    q = new_keys.shape[0]
    # merged position of each new entry (strictly increasing for valid rows)
    new_pos = jnp.arange(q, dtype=jnp.int64) + jnp.searchsorted(
        keys, new_keys, side="right"
    )
    valid_new = jnp.arange(q) < n_new
    tgt = jnp.where(valid_new, new_pos, cap)  # OOB -> dropped
    mark = jnp.zeros((cap,), dtype=jnp.int32).at[tgt].set(1, mode="drop")
    new_at = jnp.full((cap,), -1, dtype=jnp.int32).at[tgt].set(
        jnp.arange(q, dtype=jnp.int32), mode="drop"
    )
    nb = prefix_count(mark)  # new entries at merged positions <= i (inclusive)
    i = jnp.arange(cap, dtype=jnp.int64)
    is_new = new_at >= 0
    old_idx = jnp.clip(i - nb, 0, cap - 1)
    from_old = ~is_new & ((i - nb) < size)
    nk = new_keys[jnp.clip(new_at, 0, q - 1)]
    nv = new_vals[jnp.clip(new_at, 0, q - 1)]
    out_keys = jnp.where(
        is_new, nk, jnp.where(from_old, keys[old_idx], KEY_MAX)
    )
    out_vals = jnp.where(is_new, nv, jnp.where(from_old, vals[old_idx], 0))
    return out_keys, out_vals, size + n_new.astype(size.dtype)


class BMAT:
    """Host wrapper holding the array state + static tuning knobs.

    All batch entry points take jnp arrays of any length; they pad to the
    next power-of-two bucket so jit caches stay small.
    """

    def __init__(self, tree_type: str = BPMAT, fanout: int = 16, capacity: int = _MIN_CAP):
        assert tree_type in (RBMAT, BPMAT)
        assert fanout >= 2 and (fanout & (fanout - 1)) == 0
        self.tree_type = tree_type
        self.fanout = fanout
        capacity = max(_ceil_pow2(capacity), _MIN_CAP)
        self.state = BMATState(
            keys=jnp.full((capacity,), KEY_MAX, dtype=jnp.int64),
            vals=jnp.zeros((capacity,), dtype=jnp.int64),
            fences=_make_fences(jnp.full((capacity,), KEY_MAX, dtype=jnp.int64), fanout),
            size=jnp.asarray(0, dtype=jnp.int32),
        )

    # -- introspection -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.state.keys.shape[0])

    @property
    def size(self) -> int:
        return int(self.state.size)

    @property
    def live_size(self) -> int:
        """Entries excluding tombstones (exact; O(capacity) reduce)."""
        n = int(self.state.size)
        if n == 0:
            return 0
        vals = np.asarray(self.state.vals)[:n]
        return int((vals != TOMBSTONE).sum())

    @property
    def height(self) -> int:
        """Dependent-gather count of one rank query (performance measure S1)."""
        return bmat_height(self.size, self.tree_type, self.fanout)

    def memory_bytes(self, modeled: bool = False) -> int:
        """Live bytes; ``modeled=True`` adds the paper's CPU-side overheads
        (3 pointers/node for RBMAT; node slack + fences for B+MAT) so Fig. 4's
        memory comparison is reproducible."""
        arrays = (self.state.keys, self.state.vals, self.state.fences, self.state.size)
        base = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
        if not modeled:
            return base
        if self.tree_type == RBMAT:
            return self.size * (2 * 8 + 3 * 8 + 1)  # key+val, 3 ptrs, color
        nodes = max(self.size // self.fanout + 1, 1)
        return nodes * (self.fanout * 2 * 8 + 8) + self.capacity // self.fanout * 8

    # -- queries -------------------------------------------------------------
    # Boundary discipline: all public entry points take/return NUMPY arrays
    # and pad to power-of-two buckets on the host before any jnp array is
    # created — arbitrary-length eager jnp ops would recompile per length.
    def _pad_np(self, arr: np.ndarray, fill) -> Tuple[np.ndarray, int]:
        arr = np.asarray(arr)
        n = len(arr)
        b = max(_ceil_pow2(max(n, 1)), 256)
        if n == b:
            return arr, n
        out = np.full(b, fill, dtype=arr.dtype)
        out[:n] = arr
        return out, n

    def _rank_padded(self, q: jnp.ndarray) -> jnp.ndarray:
        cap = self.capacity
        if self.tree_type == RBMAT:
            return _rank_rbmat(self.state.keys, q, int(np.log2(cap)))
        nf = self.state.fences.shape[0]
        return _rank_bpmat(
            self.state.keys,
            self.state.fences,
            q,
            self.fanout,
            int(np.ceil(np.log2(nf + 1))),
            int(np.ceil(np.log2(self.fanout + 1))),
        )

    def rank(self, queries: np.ndarray) -> np.ndarray:
        """r(k): number of buffered entries with key < k (Phase-1 bias)."""
        q, n = self._pad_np(np.asarray(queries, dtype=np.int64), KEY_MAX)
        return np.asarray(self._rank_padded(jnp.asarray(q)))[:n]

    def lookup(self, queries: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        q, n = self._pad_np(np.asarray(queries, dtype=np.int64), KEY_MAX)
        qj = jnp.asarray(q)
        ranks = self._rank_padded(qj)
        found, vals = _lookup(self.state.keys, self.state.vals, ranks, qj)
        return np.asarray(found)[:n], np.asarray(vals)[:n]

    def range_bounds(self, lo: np.ndarray, hi: np.ndarray):
        """(rank(lo), rank(hi+1)) — the buffered slice for a range query."""
        return self.rank(lo), self.rank(np.asarray(hi) + 1)

    # -- updates -------------------------------------------------------------
    def merge(self, new_keys: np.ndarray, new_vals: np.ndarray) -> None:
        """Insert a batch. Keys already present get their value overwritten
        in place; new keys are merged (sorted, vectorized)."""
        new_keys = np.asarray(new_keys, dtype=np.int64)
        new_vals = np.asarray(new_vals, dtype=np.int64)
        if len(new_keys) == 0:
            return
        order = np.argsort(new_keys, kind="stable")
        new_keys, new_vals = new_keys[order], new_vals[order]
        # batch-internal dedup: keep the LAST occurrence (latest write wins)
        is_last = np.concatenate([new_keys[1:] != new_keys[:-1], [True]])
        new_keys, new_vals = new_keys[is_last], new_vals[is_last]
        # existing keys -> value update (host masks, one padded scatter)
        ranks = self.rank(new_keys)
        host_keys = np.asarray(self.state.keys)
        idx = np.minimum(ranks.astype(np.int64), self.capacity - 1)
        present = host_keys[idx] == new_keys
        if present.any():
            pi, _ = self._pad_np(idx[present], self.capacity + 1)
            pv, _ = self._pad_np(new_vals[present], 0)
            self.state = self.state._replace(
                vals=_scatter_oob(self.state.vals, jnp.asarray(pi), jnp.asarray(pv))
            )
        fresh = ~present
        n_new = int(fresh.sum())
        if n_new == 0:
            return
        if self.size + n_new > self.capacity - 1:
            self._grow(self.size + n_new)
        fk, _ = self._pad_np(new_keys[fresh], KEY_MAX)
        fv, _ = self._pad_np(new_vals[fresh], 0)
        keys, vals, size = _merge(
            self.state.keys,
            self.state.vals,
            self.state.size,
            jnp.asarray(fk),
            jnp.asarray(fv),
            jnp.asarray(n_new, dtype=jnp.int32),
        )
        self.state = BMATState(
            keys=keys, vals=vals, fences=_make_fences(keys, self.fanout), size=size
        )

    def delete(self, keys: np.ndarray) -> np.ndarray:
        """Tombstone deletes for buffered keys; returns hit mask."""
        keys = np.asarray(keys, dtype=np.int64)
        found, _ = self.lookup(keys)
        if found.any():
            ranks = self.rank(keys)
            idx = np.minimum(ranks.astype(np.int64), self.capacity - 1)
            pi, _ = self._pad_np(idx[found], self.capacity + 1)
            tomb = np.full(len(pi), TOMBSTONE, dtype=np.int64)
            self.state = self.state._replace(
                vals=_scatter_oob(self.state.vals, jnp.asarray(pi), jnp.asarray(tomb))
            )
        return found

    def compact(self) -> None:
        """Drop tombstones (host-side; used by the tuning actions)."""
        keys = np.asarray(self.state.keys)
        vals = np.asarray(self.state.vals)
        live = (np.arange(self.capacity) < self.size) & (vals != TOMBSTONE)
        self._rebuild(keys[live], vals[live])

    def extract(self, lo: int | None = None, hi: int | None = None):
        """Live (keys, vals) in [lo, hi] as numpy (for flush/retrain)."""
        keys = np.asarray(self.state.keys)[: self.size]
        vals = np.asarray(self.state.vals)[: self.size]
        live = vals != TOMBSTONE
        if lo is not None:
            live &= keys >= lo
        if hi is not None:
            live &= keys <= hi
        return keys[live], vals[live]

    def remove_range(self, lo: int, hi: int) -> None:
        """Remove all live entries in [lo, hi] (after they were absorbed
        in-place by a subset-retrain tuning action)."""
        keys = np.asarray(self.state.keys)[: self.size]
        vals = np.asarray(self.state.vals)[: self.size]
        keep = ~((keys >= lo) & (keys <= hi)) & (vals != TOMBSTONE)
        self._rebuild(keys[keep], vals[keep])

    def switch_type(self) -> None:
        """Tuning action A3: RBMAT <-> B+MAT (state is layout-agnostic)."""
        self.tree_type = BPMAT if self.tree_type == RBMAT else RBMAT

    # -- internals -----------------------------------------------------------
    def _grow(self, need: int) -> None:
        new_cap = max(_ceil_pow2(4 * need + 2), _MIN_CAP)
        keys = np.full(new_cap, KEY_MAX, dtype=np.int64)
        vals = np.zeros(new_cap, dtype=np.int64)
        keys[: self.size] = np.asarray(self.state.keys)[: self.size]
        vals[: self.size] = np.asarray(self.state.vals)[: self.size]
        k = jnp.asarray(keys)
        self.state = BMATState(
            keys=k,
            vals=jnp.asarray(vals),
            fences=_make_fences(k, self.fanout),
            size=self.state.size,
        )

    def _rebuild(self, keys: np.ndarray, vals: np.ndarray) -> None:
        cap = max(_ceil_pow2(len(keys) + 1), _MIN_CAP)
        k = np.full(cap, KEY_MAX, dtype=np.int64)
        v = np.zeros(cap, dtype=np.int64)
        k[: len(keys)] = keys
        v[: len(keys)] = vals
        kj = jnp.asarray(k)
        self.state = BMATState(
            keys=kj,
            vals=jnp.asarray(v),
            fences=_make_fences(kj, self.fanout),
            size=jnp.asarray(len(keys), dtype=jnp.int32),
        )
