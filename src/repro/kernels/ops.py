"""jit'd dispatch wrappers around the Pallas kernels.

Handles: int64 -> (hi:int32, lo:uint32) decomposition, padding to kernel
block sizes, platform selection (``interpret_mode``: the one place that
decides whether kernels compile for the TPU or run in Pallas interpret
mode), and the big-buffer fallback composition for bmat_rank. Each wrapper
is numerically validated against repro.kernels.ref in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.bmat_rank import bmat_rank_offset_pallas
from repro.kernels.gmm_estep import N_BLK as GMM_N_BLK, gmm_estep_pallas
from repro.kernels.rows import pad_queries
from repro.kernels.spline_lookup import (
    Q_BLK as SPL_Q_BLK,
    fused_locate_pallas,
    spline_lookup_pallas,
)
from repro.kernels.tile_search import Q_BLK as TS_Q_BLK, TILE, tile_search_pallas
from repro.shapes import pow2_at_least

MAX_VMEM_KEYS = 131072  # ~1MB hi/lo in VMEM; larger buffers use tile fallback
MAX_VMEM_SLOTS = 1 << 20   # fused-locate slot residency guard (8MB hi/lo)
MAX_F32_POSITIONS = 1 << 24  # f32 slot positions are exact below this


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def interpret_mode() -> bool:
    """Kernels compile for the TPU on a TPU and run in Pallas interpret
    mode everywhere else (CPU tests); no kernel entry has a default."""
    return not on_tpu()


def split_key(k: jnp.ndarray):
    """int64 key -> (hi:int32, lo:uint32); exact for the 52-bit domain and
    for the KEY_MAX sentinel ordering (hi compares first)."""
    hi = (k >> 32).astype(jnp.int32)
    lo = (k & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return hi, lo


def _pad_to(x: jnp.ndarray, mult: int, fill):
    n = x.shape[0]
    return _pad_len(x, -(-n // mult) * mult, fill), n


def _pad_len(x: jnp.ndarray, m: int, fill):
    """Pad [n] to length ``m`` with ``fill``."""
    n = x.shape[0]
    if m == n:
        return x
    return jnp.concatenate([x, jnp.full((m - n,), fill, x.dtype)])


# -- spline lookup ----------------------------------------------------------


def spline_lookup(table, spline_keys, spline_pos, shift, queries, n_iters):
    """Batched learned-index predict (float32 positions)."""
    sk_hi, sk_lo = split_key(spline_keys)
    q_hi, q_lo = split_key(queries)
    sp = spline_pos.astype(jnp.float32)
    q_hi, n = _pad_to(q_hi, SPL_Q_BLK, 0)
    q_lo, _ = _pad_to(q_lo, SPL_Q_BLK, 0)
    if int(shift) < 32:
        # prefix needs low bits — fall back to the jnp oracle (only reachable
        # for tiny key domains; the assigned datasets use shift >= 32)
        out = ref.spline_lookup_ref(
            table, sk_hi, sk_lo, sp, q_hi, q_lo, int(shift), n_iters
        )
    else:
        out = spline_lookup_pallas(
            table, sk_hi, sk_lo, sp, q_hi, q_lo,
            shift=int(shift), n_iters=n_iters, interpret=interpret_mode(),
        )
    return out[:n]


# -- last-mile tile search ----------------------------------------------------


def _tile_buckets(xp, tile_id, block: int):
    """Sort-based per-tile query bucketing shared by every tile_search
    composition (``xp`` is np or jnp — the jnp form stays traceable).
    Returns (order, t_sorted, flat, ok): queries sorted by tile, their flat
    slot in the (n_tiles, block) buffer, and the capacity mask — entries
    beyond ``block`` per tile get ok=False and must be handled by the
    caller (oracle path / a further pass)."""
    order = xp.argsort(tile_id)
    t_sorted = tile_id[order]
    within = xp.arange(t_sorted.shape[0]) - xp.searchsorted(
        t_sorted, t_sorted, side="left"
    )
    ok = within < block
    flat = t_sorted * block + xp.minimum(within, block - 1)
    return order, t_sorted, flat, ok


def route_and_search(slot_keys, queries, pred_pos):
    """Sort-based routing: map each query to the TILE containing its
    predicted position, run the tile kernel, compose global indices.
    Returns j = index of last slot key <= q, assuming the true position is
    inside the predicted tile +- 1 (guaranteed by the model error bound; the
    caller widens to neighbor tiles on miss)."""
    cap = slot_keys.shape[0]
    n_tiles = (cap + TILE - 1) // TILE
    padded_cap = n_tiles * TILE
    sk, _ = _pad_to(slot_keys, TILE, np.iinfo(np.int64).max)
    kh, kl = split_key(sk)
    tiles_hi = kh.reshape(n_tiles, TILE)
    tiles_lo = kl.reshape(n_tiles, TILE)

    tile_id = jnp.clip(pred_pos.astype(jnp.int64) // TILE, 0, n_tiles - 1)
    # bucket queries per tile with capacity TS_Q_BLK (overflow -> oracle path)
    order, t_sorted, flat, ok = _tile_buckets(jnp, tile_id, TS_Q_BLK)
    q_sorted = queries[order]
    qh, ql = split_key(q_sorted)
    buf_hi = jnp.zeros((n_tiles * TS_Q_BLK,), jnp.int32).at[flat].set(
        jnp.where(ok, qh, 0), mode="drop"
    )
    buf_lo = jnp.zeros((n_tiles * TS_Q_BLK,), jnp.uint32).at[flat].set(
        jnp.where(ok, ql, 0), mode="drop"
    )
    out = tile_search_pallas(
        tiles_hi,
        tiles_lo,
        buf_hi.reshape(n_tiles, TS_Q_BLK),
        buf_lo.reshape(n_tiles, TS_Q_BLK),
        interpret=interpret_mode(),
    ).reshape(-1)
    local = out[flat]
    j_sorted = t_sorted * TILE + local.astype(jnp.int64)
    # scatter back to original order
    inv = jnp.argsort(order)
    return j_sorted[inv], ok[inv]


# -- fused locate (predict + bounded window search, one launch) --------------


def locate_fusable(cap: int, n_knots: int, n_table: int, n_shards: int) -> bool:
    """Static-shape guard for the fused locate kernel: every array it keeps
    resident must fit the VMEM budget, the per-shard capacity must stay
    below the f32 position-precision bound, and the model must have at
    least one real spline segment. ``cap``/``n_knots``/``n_table`` are
    per-shard dims; all arguments are trace-time python ints (array
    shapes), so fops can branch on this under jit."""
    return (
        cap <= MAX_F32_POSITIONS
        and n_shards * cap <= MAX_VMEM_SLOTS
        and n_shards * n_knots <= MAX_VMEM_KEYS
        and n_shards * n_table <= MAX_VMEM_KEYS
        and n_knots >= 2
    )


def fused_locate(
    table, spline_keys, spline_pos, shift, slot_keys, queries, sid,
    *, n_table: int, n_knots: int, cap: int, window: int, rs_iters: int,
    spline_hi=None, spline_lo=None, spline_pos32=None,
    slot_hi=None, slot_lo=None,
):
    """Jit-traceable adapter around ``fused_locate_pallas``.

    ``table``/``spline_keys``/``spline_pos``/``slot_keys`` are FLAT over the
    shard axis ([S*T], [S*K], [S*cap]); ``shift`` is the per-shard [S] radix
    shift; ``sid`` maps each query to its shard (all zeros for a single
    shard). Per-query base offsets and block padding are handled here;
    returns (j, icap) as int64 with the ``fops._locate`` contract.

    When the caller carries a persistent decomposition
    (``state.halves``), pass the pre-split ``spline_hi``/``spline_lo``/
    ``spline_pos32``/``slot_hi``/``slot_lo`` and the O(S·cap) int64 ->
    (hi, lo) conversion is skipped entirely (the int64 source arrays are
    then dead inputs that XLA eliminates). Only the O(batch) query split
    stays per-call. Without them the split runs here, per call."""
    L = min(3 * window, cap)
    if spline_hi is None:
        spline_hi, spline_lo = split_key(spline_keys)
    if slot_hi is None:
        slot_hi, slot_lo = split_key(slot_keys)
    if spline_pos32 is None:
        spline_pos32 = spline_pos.astype(jnp.float32)
    q_hi, q_lo = split_key(queries)
    n = queries.shape[0]
    m = pad_queries(n)
    i32 = lambda a, fill: _pad_len(a.astype(jnp.int32), m, fill)
    j, start = fused_locate_pallas(
        table, spline_hi, spline_lo, spline_pos32, slot_hi, slot_lo,
        _pad_len(q_hi, m, np.iinfo(np.int32).max),
        _pad_len(q_lo, m, np.iinfo(np.uint32).max),
        i32(sid * n_table, 0), i32(sid * n_knots, 0), i32(sid * cap, 0),
        i32(shift.astype(jnp.int32)[sid], 32),
        n_table=n_table, n_knots=n_knots, cap=cap, window=window,
        rs_iters=rs_iters, interpret=interpret_mode(),
    )
    j = j[:n].astype(jnp.int64)
    icap = start[:n].astype(jnp.int64) + (L - 1)
    return j, icap


# -- bmat rank ---------------------------------------------------------------


def rank_fusable(n_keys: int, n_fences: int) -> bool:
    """VMEM guard for the offset rank kernel (trace-time shapes)."""
    return n_keys <= MAX_VMEM_KEYS and n_fences <= MAX_VMEM_KEYS


def bmat_rank_fused(keys, fences, queries, sid, *, cap: int, nf: int,
                    fanout: int, keys_hi=None, keys_lo=None,
                    fences_hi=None, fences_lo=None):
    """Jit-traceable shard-offset rank: ``keys``/``fences`` flat over the
    shard axis, ``sid`` per query (zeros for a single shard). Returns the
    shard-local searchsorted-left rank as int32 (callers widen). Pre-split
    halves (``keys_hi``..``fences_lo``, from a persistent ``state.halves``)
    skip the per-call buffer decomposition; only queries split here."""
    if keys_hi is None:
        keys_hi, keys_lo = split_key(keys)
    if fences_hi is None:
        fences_hi, fences_lo = split_key(fences)
    qh, ql = split_key(queries)
    n = queries.shape[0]
    m = pad_queries(n)
    out = bmat_rank_offset_pallas(
        keys_hi, keys_lo, fences_hi, fences_lo,
        _pad_len(qh, m, np.iinfo(np.int32).max),
        _pad_len(ql, m, np.iinfo(np.uint32).max),
        _pad_len((sid * cap).astype(jnp.int32), m, 0),
        _pad_len((sid * nf).astype(jnp.int32), m, 0),
        cap=cap, nf=nf, fanout=fanout, interpret=interpret_mode(),
    )
    return out[:n]


def _bmat_rank_tiled(keys, queries):
    """Two-level tile_search composition for buffers beyond MAX_VMEM_KEYS.

    Level 1 routes each query EXACTLY (no model prediction involved): the
    rank of ``q`` lives in the last TILE whose first key is <= q - 1, found
    by a searchsorted over the tile-first keys (cap/TILE entries — tiny).
    Level 2 runs the tile kernel on ``q - 1`` (searchsorted-left rank =
    1 + index of the last key <= q - 1) with sort-based per-tile bucketing.
    Queries beyond a tile's block capacity re-run in further passes — the
    host loop touches only the unresolved remainder, so heavily duplicated
    query batches terminate in ceil(dup/Q_BLK) passes. Memory stays
    O(tiles * TILE + Q) instead of the O(Q * cap) broadcast compare of the
    jnp oracle, and every pass is on-device."""
    cap = keys.shape[0]
    sk, _ = _pad_to(keys, TILE, np.iinfo(np.int64).max)
    n_tiles = sk.shape[0] // TILE
    kh, kl = split_key(sk)
    tiles_hi = kh.reshape(n_tiles, TILE)
    tiles_lo = kl.reshape(n_tiles, TILE)

    qm1 = queries - 1  # keys are non-negative: q - 1 >= -1 orders below all
    tile_id = np.clip(
        np.searchsorted(np.asarray(sk[::TILE]), np.asarray(qm1), "right") - 1,
        0, n_tiles - 1,
    )
    qh_all, ql_all = split_key(qm1)
    qh_all = np.asarray(qh_all)
    ql_all = np.asarray(ql_all)

    out = np.zeros(queries.shape[0], dtype=np.int32)
    todo = np.arange(queries.shape[0])
    while todo.size:
        order, t_sorted, flat, ok = _tile_buckets(
            np, tile_id[todo], TS_Q_BLK
        )
        buf_hi = np.zeros(n_tiles * TS_Q_BLK, np.int32)
        buf_lo = np.zeros(n_tiles * TS_Q_BLK, np.uint32)
        sel = todo[order]
        buf_hi[flat[ok]] = qh_all[sel[ok]]
        buf_lo[flat[ok]] = ql_all[sel[ok]]
        local = np.asarray(
            tile_search_pallas(
                tiles_hi, tiles_lo,
                jnp.asarray(buf_hi.reshape(n_tiles, TS_Q_BLK)),
                jnp.asarray(buf_lo.reshape(n_tiles, TS_Q_BLK)),
                interpret=interpret_mode(),
            )
        ).reshape(-1)
        res = sel[ok]
        out[res] = np.minimum(
            t_sorted[ok] * TILE + local[flat[ok]] + 1, cap
        ).astype(np.int32)
        todo = sel[~ok]
    return jnp.asarray(out)


def bmat_rank(keys, fences, queries, fanout: int):
    if keys.shape[0] > MAX_VMEM_KEYS:
        # two-level tiled composition: fences are implicit in the tile-first
        # keys, so the fence array is not needed here
        return _bmat_rank_tiled(keys, queries)
    # single BMAT = the offset kernel with all-zero bases (one search
    # implementation to keep in sync with the fused fops path)
    return bmat_rank_fused(
        keys, fences, queries, jnp.zeros(queries.shape, dtype=jnp.int64),
        cap=keys.shape[0], nf=fences.shape[0], fanout=fanout,
    )


# -- gmm e-step ---------------------------------------------------------------


def gmm_estep_width(n: int) -> int:
    """Lanes the E-step runs for ``n`` samples: the next power of two ≥ n,
    at least one block. The width follows n in powers of two, so the
    E-step compiles once per width and never for a batch length."""
    return max(pow2_at_least(n), GMM_N_BLK)


@jax.jit
def _gmm_estep_padded(xp, weights, means, stds, lo, span, std_floor):
    ms = (means - lo) / span
    ss = jnp.maximum(stds / span, std_floor)
    return gmm_estep_pallas(
        xp.astype(jnp.float32), weights.astype(jnp.float32),
        ms.astype(jnp.float32), ss.astype(jnp.float32),
        interpret=interpret_mode(),
    )


def gmm_estep(x, weights, means, stds, *, lo=0.0, span=1.0, std_floor=0.0,
              fetch=np.asarray):
    """(N, K) float32 responsibilities of ``x`` as a host array, under the
    mixture mapped by ``(· − lo) / span`` with stds floored at
    ``std_floor``. ``x`` is padded on the host with 0.0 to
    ``gmm_estep_width(N)`` lanes; one jitted program holds the mapping,
    the float32 casts, the kernel and the transpose, so the mixture may
    stay on the device. ``fetch`` reads the (W, K) result to the host,
    where the padded rows are cut."""
    x = np.asarray(x)
    n = x.shape[0]
    xp = np.zeros(gmm_estep_width(n), x.dtype)
    xp[:n] = x
    out = _gmm_estep_padded(xp, weights, means, stds, lo, span, std_floor)
    return np.asarray(fetch(out))[:n]
