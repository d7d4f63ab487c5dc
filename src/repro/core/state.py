"""Device-resident functional state for the UpLIF index (DESIGN.md §3).

``UpLIFState`` is a pure JAX pytree bundling everything an index operation
needs: the gapped slot array, the spline model, the BMAT delta-buffer arrays
and the structural counters. Every operation in ``repro/core/fops.py`` is a
pure function ``(UpLIFState, batch) -> (UpLIFState, result)`` — jittable,
vmappable (states with equal shapes stack into a leading shard axis) and
free of host round-trips on the hot path.

``UpLIFStatic`` carries the jit-stable scalars (window size, search depths,
BMAT layout, locate strategy). It is hashable and passed as a static
argument, so each (static, shapes) pair compiles exactly once.

The stateful ``repro.core.uplif.UpLIF`` class is a thin host shell that owns
one ``UpLIFState`` and forwards to ``fops``; ``repro.core.sharded`` routes a
keyspace over many such states.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.types import BMATState, RadixSplineModel, SlotsState

_I64_MAX = np.iinfo(np.int64).max

LOCATE_SPLINE = "spline"      # radix-spline predict + bounded window bisect
LOCATE_BINSEARCH = "binsearch"  # model-free full bisect (B+Tree baseline)
LOCATE_FUSED = "fused"        # fused Pallas predict+search kernel (hot path)
LOCATE_AUTO = "auto"          # the default strategy (spline)

LOCATE_STRATEGIES = (LOCATE_SPLINE, LOCATE_BINSEARCH, LOCATE_FUSED)


def resolve_locate(requested: str, fits: bool = True) -> str:
    """Map a configured locate strategy to the one that will run.

    ``LOCATE_AUTO`` picks the jnp spline path on every platform: the
    fused kernels walk one query at a time on the TPU's scalar unit and
    ran warm lookup waves about 3.6x slower than spline on a v5e (S=1,
    200k keys; PERF.md §5), so fused is an explicit choice. Explicit strategies pass through validated, so
    tests/benches can pin interpret-mode fused on CPU. ``fits`` is the
    fused kernels' static shape guard for the state at hand
    (``fops.fused_fits``): where it refuses, fused resolves to spline, so
    the resolved name is always the code that executes."""
    if requested == LOCATE_AUTO:
        requested = LOCATE_SPLINE
    if requested not in LOCATE_STRATEGIES:
        raise ValueError(
            f"unknown locate strategy {requested!r}; "
            f"expected one of {LOCATE_STRATEGIES + (LOCATE_AUTO,)}"
        )
    if requested == LOCATE_FUSED and not fits:
        return LOCATE_SPLINE
    return requested


class Counters(NamedTuple):
    """Structural counters maintained on-device by the pure ops.

    These are the Section 4.1 performance-measure inputs that the RL tuning
    agent reads; keeping them in the pytree means an op never needs a host
    sync just to stay accountable.
    """

    n_keys: jnp.ndarray           # int64 — live keys in the slot array
    n_bmat_live: jnp.ndarray      # int64 — live (non-tombstone) BMAT entries
    n_inplace: jnp.ndarray        # int64 — accepted in-place inserts
    n_overflow: jnp.ndarray       # int64 — inserts routed to the BMAT
    min_granularity: jnp.ndarray  # int64 — smallest failed-window key span


class KeyHalves(NamedTuple):
    """Persistent (hi:int32, lo:uint32) decomposition of every int64 key
    array the fused Pallas kernels read, plus the float32 spline positions.

    The fused adapters in ``repro.kernels.ops`` consume pre-split halves;
    without this pytree member they re-split the O(S·cap) slot/BMAT arrays
    inside every jitted call. Carrying the halves in ``UpLIFState`` amortizes
    that conversion per *state version*: built once at construction/retrain
    (``make_halves``), maintained incrementally by the write paths in
    ``fops`` alongside the int64 source arrays. Invariant (pinned by the
    property suite): every field is byte-identical to a fresh
    ``kernels.ops.split_key`` of its int64 source.
    """

    slot_hi: jnp.ndarray      # int32  [cap] / [S, cap] — slots.keys >> 32
    slot_lo: jnp.ndarray      # uint32 — slots.keys & 0xFFFFFFFF
    spline_hi: jnp.ndarray    # int32  — model.spline_keys >> 32
    spline_lo: jnp.ndarray    # uint32
    spline_pos32: jnp.ndarray  # float32 — model.spline_pos.astype(f32)
    bmat_hi: jnp.ndarray      # int32  — bmat.keys >> 32
    bmat_lo: jnp.ndarray      # uint32
    fence_hi: jnp.ndarray     # int32  — bmat.fences >> 32
    fence_lo: jnp.ndarray     # uint32


class UpLIFState(NamedTuple):
    """The whole index as one pytree (slots + model + BMAT + counters).

    ``halves`` is the optional persistent (hi, lo) decomposition: ``None``
    (the per-call re-split baseline) vs present is a treedef difference, so
    the two modes trace separately and never mix inside one jit cache entry.
    """

    slots: SlotsState
    model: RadixSplineModel
    bmat: BMATState
    counters: Counters
    halves: Optional[KeyHalves] = None


def make_halves(
    slots: SlotsState, model: RadixSplineModel, bmat: BMATState
) -> KeyHalves:
    """Build the full decomposition fresh (construction / retrain / pad)."""
    from repro.kernels.ops import split_key  # no cycle: kernels never import core

    slot_hi, slot_lo = split_key(slots.keys)
    spline_hi, spline_lo = split_key(model.spline_keys)
    bmat_hi, bmat_lo = split_key(bmat.keys)
    fence_hi, fence_lo = split_key(bmat.fences)
    return KeyHalves(
        slot_hi=slot_hi,
        slot_lo=slot_lo,
        spline_hi=spline_hi,
        spline_lo=spline_lo,
        spline_pos32=model.spline_pos.astype(jnp.float32),
        bmat_hi=bmat_hi,
        bmat_lo=bmat_lo,
        fence_hi=fence_hi,
        fence_lo=fence_lo,
    )


class UpLIFStatic(NamedTuple):
    """Jit-stable scalars for the op suite (hashable; static argument)."""

    window: int         # W — insert/last-mile window (power of two)
    movement_k: int     # K — max elements shifted per in-place insert
    rs_iters: int       # bounded knot-search depth of the spline model
    insert_rounds: int  # in-place retry rounds before BMAT overflow
    fanout: int         # B+MAT fence fanout
    bmat_kind: str      # 'rbmat' | 'b+mat'
    # one concrete strategy (str), or — for mixed per-shard dispatch in the
    # stacked ops — a sorted tuple of the DISTINCT strategies in play; the
    # traced per-shard ``codes`` array indexes into that tuple. Keeping the
    # tuple sorted/deduplicated bounds the static universe at 7 values, so
    # controller flips never grow the jit cache past the warmed family.
    locate: str         # LOCATE_SPLINE | LOCATE_BINSEARCH | LOCATE_FUSED


def init_counters(
    n_keys: int = 0,
    n_bmat_live: int = 0,
    n_inplace: int = 0,
    n_overflow: int = 0,
    min_granularity: int = _I64_MAX,
) -> Counters:
    return Counters(
        n_keys=jnp.asarray(n_keys, dtype=jnp.int64),
        n_bmat_live=jnp.asarray(n_bmat_live, dtype=jnp.int64),
        n_inplace=jnp.asarray(n_inplace, dtype=jnp.int64),
        n_overflow=jnp.asarray(n_overflow, dtype=jnp.int64),
        min_granularity=jnp.asarray(min_granularity, dtype=jnp.int64),
    )


def state_memory_bytes(state: UpLIFState) -> int:
    """Total live bytes of the device-resident state (counters excluded)."""
    total = 0
    for arrs in (state.slots, state.model, state.bmat):
        total += sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrs)
    return total
