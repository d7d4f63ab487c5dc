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

from typing import NamedTuple

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


class UpLIFState(NamedTuple):
    """The whole index as one pytree (slots + model + BMAT + counters).

    Keys are stored once, as int64. The fused Pallas kernels take
    (int32 hi, uint32 lo) halves; their adapters in ``repro.kernels.ops``
    split the arrays they read inside each call, so no write path keeps a
    second copy of the keys up to date.
    """

    slots: SlotsState
    model: RadixSplineModel
    bmat: BMATState
    counters: Counters


class UpLIFStatic(NamedTuple):
    """Jit-stable scalars for the op suite (hashable; static argument).

    ``locate`` is one strategy for the whole index: on a stacked state
    every shard runs it, so a wave is one locate and one rank."""

    window: int         # W — insert/last-mile window (power of two)
    movement_k: int     # K — max elements shifted per in-place insert
    rs_iters: int       # bounded knot-search depth of the spline model
    insert_rounds: int  # in-place retry rounds before BMAT overflow
    fanout: int         # B+MAT fence fanout
    bmat_kind: str      # 'rbmat' | 'b+mat'
    # the one strategy every shard of the index runs, already resolved
    # (``resolve_locate``) against the fused kernels' shape guard
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
