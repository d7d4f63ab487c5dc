"""Jitted pure op suite over ``UpLIFState`` (DESIGN.md §3–§4).

Every public function here is a pure, jitted program of the whole index
pytree — no numpy, no host loops, no Python branching on data:

  * ``lookup(state, q)``                 — batched point lookup
  * ``insert(state, k, v)``              — batched upsert incl. BMAT overflow
  * ``delete(state, q)``                 — batched tombstone delete
  * ``range_scan(state, lo, hi, lim)``   — batched bounded range extraction
  * ``adjusted_rank(state, q)``          — paper Eq. 1 logical position

Two formerly host-side pieces now run on-device:

  * the greedy window-accept of the insert path is replaced by a
    *grid-segment* formulation: windows are aligned to a fixed W-grid over
    the slot array, so the non-overlapping-subset choice collapses to
    "first pending key per grid segment" — one sort + one segment-boundary
    compare instead of a scalar host recurrence (DESIGN.md §4.2);
  * the per-query Python range loop is replaced by a vmapped fixed-width
    ``lax.dynamic_slice`` scan + masked merge with the BMAT slice
    (DESIGN.md §4.3).

Shape/static discipline: batches arrive padded with KEY_MAX to a bucketed
width; ``UpLIFStatic`` (hashable) is the only static argument besides array
shapes. The slot capacity must be a multiple of ``static.window`` (enforced
by the nullifier's ``align``), which keeps every grid window fully in
bounds without clipping.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bmat import (
    RBMAT,
    _make_fences,
    _merge,
    _rank_bpmat,
    _rank_rbmat,
    prefix_count,
)
from repro.core.radix_spline import _rs_predict_impl
from repro.core.state import (
    LOCATE_BINSEARCH,
    LOCATE_FUSED,
    Counters,
    UpLIFState,
    UpLIFStatic,
)
from repro.core.types import BMATState, KEY_MAX, TOMBSTONE, SlotsState
from repro.kernels import ops as kops

_I64_MAX = np.iinfo(np.int64).max


class InsertResult(NamedTuple):
    pending: jnp.ndarray     # bool[n] — keys still unplaced after the rounds
    n_overflow: jnp.ndarray  # int64 — count routed to the BMAT this call
    n_round_keys: jnp.ndarray  # int64 — keys pending when the rounds start
    n_rounds: jnp.ndarray    # int32 — accept rounds the call ran


class RangeResult(NamedTuple):
    keys: jnp.ndarray     # int64[n, max_out] — KEY_MAX beyond ``count``
    vals: jnp.ndarray     # int64[n, max_out]
    count: jnp.ndarray    # int32[n]
    covered: jnp.ndarray  # bool[n] — the answer is whole
    front: jnp.ndarray    # int64[n] — exact below this key; resume here


# ---------------------------------------------------------------------------
# locate — model-guided (spline) or model-free (binsearch baseline)
# ---------------------------------------------------------------------------


def fused_fits(slot_keys, model, bmat: BMATState) -> bool:
    """Static guard of the fused strategy: True when both fused kernels
    (locate and BMAT rank) admit these array shapes — single-shard
    ([cap]) or stacked ([S, cap]). Callers resolve ``locate`` against it
    (``state.resolve_locate``) before building an ``UpLIFStatic``, so a
    static that says fused always runs the kernels."""
    S = slot_keys.shape[0] if slot_keys.ndim == 2 else 1
    return kops.locate_fusable(
        slot_keys.shape[-1], model.spline_keys.shape[-1],
        model.table.shape[-1], S,
    ) and kops.rank_fusable(
        S * bmat.keys.shape[-1], S * bmat.fences.shape[-1]
    )


def _require_fusable(ok: bool):
    if not ok:
        raise ValueError(
            "locate='fused' at shapes the fused kernels refuse; resolve the "
            "strategy with state.resolve_locate(..., fits=fused_fits(...))"
        )


def _locate(static: UpLIFStatic, slot_keys, model, queries, halves=None):
    """(j, ins_cap): j = index of the last slot with key <= q (-1 if below
    all keys); ins_cap = largest slot index an insert derived from this
    locate may target. For the exact binsearch ins_cap is just cap-1; for
    the bounded learned search it is the end of the searched span, so a
    boundary the span could not prove stays UNPLACED (fails the window
    accept, overflows to the BMAT) instead of landing outside the rows
    future lookups will search.

    ``halves`` is the state's persistent (hi, lo) decomposition (or None):
    the fused branch consumes it directly so the kernel adapter skips the
    per-call O(cap) int64 split; the jnp branches ignore it."""
    cap = slot_keys.shape[0]
    if static.locate == LOCATE_BINSEARCH:
        # B+Tree analogue: full bisect, log2(capacity) dependent probes.
        n_iters = max(1, int(np.ceil(np.log2(cap + 1))))

        def body(_, carry):
            lo, hi = carry  # converge to the first index with key > q
            mid = (lo + hi) >> 1
            go = slot_keys[jnp.minimum(mid, cap - 1)] <= queries
            return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

        lo = jnp.zeros(queries.shape, dtype=jnp.int64)
        hi = jnp.full(queries.shape, cap, dtype=jnp.int64)
        lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
        return lo - 1, jnp.full(queries.shape, cap - 1, dtype=jnp.int64)

    if static.locate == LOCATE_FUSED:
        # Fused Pallas hot path: radix predict + knot search + interpolation
        # + the SAME drift-proof 3-row bounded search below, in one kernel
        # launch (interpret mode off-TPU) — same span, same j.
        _require_fusable(kops.locate_fusable(
            cap, model.spline_keys.shape[0], model.table.shape[0], 1
        ))
        return kops.fused_locate(
            model.table, model.spline_keys, model.spline_pos,
            model.shift.reshape(1), slot_keys, queries,
            jnp.zeros(queries.shape, dtype=jnp.int64),
            n_table=model.table.shape[0],
            n_knots=model.spline_keys.shape[0],
            cap=cap, window=static.window, rs_iters=static.rs_iters,
            spline_hi=None if halves is None else halves.spline_hi,
            spline_lo=None if halves is None else halves.spline_lo,
            spline_pos32=None if halves is None else halves.spline_pos32,
            slot_hi=None if halves is None else halves.slot_hi,
            slot_lo=None if halves is None else halves.slot_lo,
        )

    # Learned path: spline predict + bounded probes over the 3-row span
    # around the prediction. Why 3 rows and not one centered window: an
    # insert places a key inside the W-aligned grid row of its (correct)
    # insertion point, and later in-row shifts never move it across a row
    # edge — but they can drift it up to W-1 slots from where the model
    # predicted. Both the placement row and any bulk-loaded key's row lie
    # within rows {row(c)-1, row(c), row(c)+1}, so searching that span
    # finds every live key REGARDLESS of accumulated drift (costs two
    # extra bisect probes vs the old +-W/2 window, which lost keys under
    # heavy localized inserts).
    window = static.window
    L = min(3 * window, cap)
    n_bisect = max(1, int(np.ceil(np.log2(L))))
    p = _rs_predict_impl(model, queries, static.rs_iters)
    c = jnp.clip(jnp.round(p).astype(jnp.int64), 0, cap - 1)
    start = jnp.clip((c // window - 1) * window, 0, max(cap - L, 0))
    lo = start
    hi = jnp.minimum(start + L - 1, cap - 1)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) >> 1
        go = slot_keys[mid] <= queries
        return jnp.where(go, mid, lo), jnp.where(go, hi, mid - 1)

    lo, hi = jax.lax.fori_loop(0, n_bisect, body, (lo, hi))
    j = jnp.where(slot_keys[start] <= queries, lo, start - 1)
    return j, start + (L - 1)


def _probe(slot_keys, slot_vals, slot_occ, j, queries):
    """(hit, alive, value, clipped-index) of the located slot."""
    cap = slot_keys.shape[0]
    jj = jnp.clip(j, 0, cap - 1)
    hit = (j >= 0) & (slot_keys[jj] == queries) & slot_occ[jj] & (queries != KEY_MAX)
    val = slot_vals[jj]
    alive = hit & (val != TOMBSTONE)
    return hit, alive, jnp.where(alive, val, 0), jj


# ---------------------------------------------------------------------------
# BMAT primitives expressed over the state arrays
# ---------------------------------------------------------------------------


def _bmat_rank(static: UpLIFStatic, bmat: BMATState, queries, halves=None):
    """searchsorted-left rank over the packed BMAT (layout per static)."""
    cap = bmat.keys.shape[0]
    if static.locate == LOCATE_FUSED:
        # Definition 1 bias query r(k) through the fused two-level kernel.
        # The rank is an exact integer search, so this is byte-identical to
        # the jnp fence/node bisects for BOTH BMAT kinds (the fence arrays
        # are maintained regardless of the traversal the jnp path uses).
        _require_fusable(kops.rank_fusable(cap, bmat.fences.shape[0]))
        return kops.bmat_rank_fused(
            bmat.keys, bmat.fences, queries,
            jnp.zeros(queries.shape, dtype=jnp.int64),
            cap=cap, nf=bmat.fences.shape[0], fanout=static.fanout,
            keys_hi=None if halves is None else halves.bmat_hi,
            keys_lo=None if halves is None else halves.bmat_lo,
            fences_hi=None if halves is None else halves.fence_hi,
            fences_lo=None if halves is None else halves.fence_lo,
        )
    if static.bmat_kind == RBMAT:
        return _rank_rbmat(bmat.keys, queries, max(1, int(np.log2(cap))))
    nf = bmat.fences.shape[0]
    return _rank_bpmat(
        bmat.keys,
        bmat.fences,
        queries,
        static.fanout,
        max(1, int(np.ceil(np.log2(nf + 1)))),
        max(1, int(np.ceil(np.log2(static.fanout + 1)))),
    )


def _bmat_probe(bmat: BMATState, ranks, queries):
    """(present, alive, value, index) of a query inside the BMAT arrays."""
    cap = bmat.keys.shape[0]
    idx = jnp.minimum(ranks.astype(jnp.int64), cap - 1)
    present = (bmat.keys[idx] == queries) & (queries != KEY_MAX)
    val = bmat.vals[idx]
    alive = present & (val != TOMBSTONE)
    return present, alive, jnp.where(alive, val, 0), idx


# ---------------------------------------------------------------------------
# lookup
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("static",))
def lookup(state: UpLIFState, queries, *, static: UpLIFStatic):
    """Batched point lookup -> (found bool[n], values int64[n]). Pure: the
    state is read-only, so lookups never force a state swap."""
    j, _ = _locate(
        static, state.slots.keys, state.model, queries, halves=state.halves
    )
    _, alive, vals, _ = _probe(
        state.slots.keys, state.slots.vals, state.slots.occ, j, queries
    )
    ranks = _bmat_rank(static, state.bmat, queries, halves=state.halves)
    _, b_alive, b_vals, _ = _bmat_probe(state.bmat, ranks, queries)
    b_alive = b_alive & ~alive
    return alive | b_alive, jnp.where(b_alive, b_vals, vals)


# ---------------------------------------------------------------------------
# insert — grid-segment accept + bounded shift + fill-forward repair
# ---------------------------------------------------------------------------


def _dedup_last_wins(keys):
    """Mask of entries that are NOT the last occurrence of their key."""
    n = keys.shape[0]
    order = jnp.argsort(keys)  # stable
    ks = keys[order]
    dup = jnp.concatenate([ks[1:] == ks[:-1], jnp.zeros(1, dtype=bool)])
    return jnp.zeros(n, dtype=bool).at[order].set(dup)


def _inplace_window_insert(
    slot_keys, slot_vals, slot_occ, q_keys, q_vals, starts, accept, valid,
    window: int, movement_k: int, slot_halves=None,
):
    """One vectorized round of conflict-free in-place window inserts.

    ``starts`` are sorted grid-aligned window starts; ``accept`` marks the
    per-grid-segment representative (disjoint by construction). Returns the
    updated slot arrays, the success mask, the min key-span of failed
    windows (granularity measure S2) and the maintained ``slot_halves``
    ((hi, lo) of ``slot_keys``, or None): the touched rows' halves are
    refreshed by splitting only the Q accepted windows (O(Q·W)) and
    gathering through the same window->row map as the int64 writeback, so
    the persistent decomposition stays byte-identical without an O(cap)
    re-split.
    """
    cap = slot_keys.shape[0]
    W = window
    K = movement_k

    idx = starts[:, None] + jnp.arange(W, dtype=jnp.int64)[None, :]
    w_k = slot_keys[idx]
    w_v = slot_vals[idx]
    w_o = slot_occ[idx]

    t_idx = jnp.arange(W, dtype=jnp.int64)[None, :]
    k_col = q_keys[:, None]
    ip = jnp.sum(w_k < k_col, axis=1, keepdims=True)  # first slot with key >= k

    # nearest empty slot left / right of the insertion point
    left_cand = jnp.where(~w_o & (t_idx < ip), t_idx, -1)
    l = jnp.max(left_cand, axis=1, keepdims=True)
    right_cand = jnp.where(~w_o & (t_idx >= ip), t_idx, 2 * W)
    r = jnp.min(right_cand, axis=1, keepdims=True)

    margin = 2
    in_bounds = (ip[:, 0] >= margin) & (ip[:, 0] <= W - margin)
    # fill-forward safety: the empty run containing the insertion point must
    # START inside the window (i.e. an occupied slot exists to the left of ip
    # in-window, or the window begins at slot 0). Otherwise empties left of
    # the window would keep a stale fill key and break global sortedness.
    has_left_occ = jnp.any(w_o & (t_idx < ip), axis=1) | (starts == 0)
    in_bounds = in_bounds & has_left_occ
    r_ok = (r[:, 0] < W - 1) & (r[:, 0] - ip[:, 0] <= K)
    l_ok = (l[:, 0] >= 1) & (ip[:, 0] - 1 - l[:, 0] <= K)
    use_right = r_ok & (~l_ok | (r[:, 0] - ip[:, 0] <= ip[:, 0] - 1 - l[:, 0]))
    use_left = l_ok & ~use_right
    can = accept & in_bounds & (use_right | use_left)

    ur = use_right[:, None]
    # gather-source schedule for the bounded shift
    src = jnp.where(
        ur & (t_idx > ip) & (t_idx <= r),
        t_idx - 1,
        jnp.where(~ur & (t_idx >= l) & (t_idx < ip - 1), t_idx + 1, t_idx),
    )
    src = jnp.clip(src, 0, W - 1)
    n_k = jnp.take_along_axis(w_k, src, axis=1)
    n_v = jnp.take_along_axis(w_v, src, axis=1)
    n_o = jnp.take_along_axis(w_o, src, axis=1)

    place = jnp.where(use_right, ip[:, 0], ip[:, 0] - 1)
    place_col = place[:, None]
    n_k = jnp.where(t_idx == place_col, k_col, n_k)
    n_v = jnp.where(t_idx == place_col, q_vals[:, None], n_v)
    n_o = jnp.where(t_idx == place_col, True, n_o)

    # keep untouched windows byte-identical
    n_k = jnp.where(can[:, None], n_k, w_k)
    n_v = jnp.where(can[:, None], n_v, w_v)
    n_o = jnp.where(can[:, None], n_o, w_o)

    # ---- fill-forward repair (vectorized suffix-min) ---------------------
    # For a sorted window, an empty slot's fill key = min occupied key at or
    # after it; if none in-window, the (unchanged) boundary fill of the last
    # slot applies. Both collapse to one suffix-min, taken by log-step
    # doubling: the TPU compiler needs over a minute for an int64
    # ``lax.cummin`` and about a second for log2(W) shifted minimums.
    suffix_min = jnp.where(n_o, n_k, jnp.asarray(KEY_MAX, n_k.dtype))
    s = 1
    while s < W:
        suffix_min = jnp.minimum(suffix_min, jnp.concatenate(
            [suffix_min[:, s:], jnp.full((n_k.shape[0], s), KEY_MAX, n_k.dtype)],
            axis=1,
        ))
        s *= 2
    boundary = n_k[:, W - 1 :]
    n_k = jnp.minimum(suffix_min, boundary)

    # ---- writeback -------------------------------------------------------
    # Grid alignment makes windows coincide with rows of the [cap/W, W]
    # view, so instead of three large element scatters (serial on CPU) we
    # scatter only a tiny window->row map and GATHER the updated rows.
    Q = q_keys.shape[0]
    nw = cap // W
    win = starts // W
    row_of_win = jnp.full((nw,), -1, dtype=jnp.int32).at[
        jnp.where(accept, win, nw)
    ].set(jnp.arange(Q, dtype=jnp.int32), mode="drop")
    has = (row_of_win >= 0)[:, None]
    rr = jnp.clip(row_of_win, 0, Q - 1)
    slot_keys = jnp.where(has, n_k[rr], slot_keys.reshape(nw, W)).reshape(cap)
    slot_vals = jnp.where(has, n_v[rr], slot_vals.reshape(nw, W)).reshape(cap)
    slot_occ = jnp.where(has, n_o[rr], slot_occ.reshape(nw, W)).reshape(cap)
    if slot_halves is not None:
        sl_hi, sl_lo = slot_halves
        nk_hi, nk_lo = kops.split_key(n_k)
        sl_hi = jnp.where(has, nk_hi[rr], sl_hi.reshape(nw, W)).reshape(cap)
        sl_lo = jnp.where(has, nk_lo[rr], sl_lo.reshape(nw, W)).reshape(cap)
        slot_halves = (sl_hi, sl_lo)

    span = w_k[:, W - 1] - w_k[:, 0]
    failed_span = jnp.where(
        accept & ~can & valid, span, jnp.asarray(_I64_MAX)
    )
    return slot_keys, slot_vals, slot_occ, can, failed_span, slot_halves


def _merge_pending(static, bmat: BMATState, keys, vals, pending, n_bmat_live,
                   halves=None):
    """Route the still-pending batch into the BMAT arrays (value updates for
    keys already buffered — incl. tombstone revival — sorted merge for fresh
    ones). The caller must guarantee capacity >= size + |pending| + 1.
    Returns the refreshed (bmat_hi, bmat_lo, fence_hi, fence_lo) halves as
    the last element (None when ``halves`` is None): the merge rewrites the
    whole packed array anyway, so re-splitting its output is proportional
    work, unlike the per-lookup re-split this pays off."""
    bcap = bmat.keys.shape[0]
    qk = jnp.where(pending, keys, KEY_MAX)
    ranks = _bmat_rank(static, bmat, qk, halves=halves)
    idx = jnp.minimum(ranks.astype(jnp.int64), bcap - 1)
    present = (bmat.keys[idx] == qk) & pending
    revived = jnp.sum(present & (bmat.vals[idx] == TOMBSTONE))
    new_vals = bmat.vals.at[jnp.where(present, idx, bcap + 1)].set(
        vals, mode="drop"
    )
    fresh = pending & ~present
    mk = jnp.where(fresh, keys, KEY_MAX)
    order = jnp.argsort(mk)
    mk = mk[order]
    mv = jnp.where(fresh, vals, 0)[order]
    n_new = jnp.sum(fresh)
    keys2, vals2, size2 = _merge(
        bmat.keys, new_vals, bmat.size, mk, mv, n_new.astype(jnp.int32)
    )
    fences2 = _make_fences(keys2, static.fanout)
    out = BMATState(
        keys=keys2,
        vals=vals2,
        fences=fences2,
        size=size2,
    )
    bmat_halves = None
    if halves is not None:
        bmat_halves = kops.split_key(keys2) + kops.split_key(fences2)
    return out, n_bmat_live + revived + n_new, jnp.sum(pending), bmat_halves


def _next_locate(i, rounds, locate, sk, slot_halves, pending, j, icap):
    """(j, ins_cap) for insert round ``i + 1``: a fresh locate over the
    slots round ``i`` rewrote, skipped after the last round."""
    if rounds == 1:
        return j, icap
    return jax.lax.cond(
        i + 1 < rounds,
        lambda: locate(sk, slot_halves, pending)[1:],
        lambda: (j, icap),
    )


@functools.partial(
    jax.jit, static_argnames=("static", "check_bmat", "merge_overflow")
)
def insert(
    state: UpLIFState,
    keys,
    vals,
    *,
    static: UpLIFStatic,
    check_bmat: bool = True,
    merge_overflow: bool = True,
):
    """Batched upsert, fully on-device. ``keys`` is KEY_MAX-padded.

    Structure:
      1. locate + probe, once: keys already in place get a value update
         (incl. tombstone revival); keys live in the BMAT get updated there
         (the pending set can't gain such keys mid-call);
      2. static.insert_rounds rounds (a loop, compiled once) of
         grid-segment accept, each on a fresh locate (round 0 on the
         probe's): each pending key maps to the W-aligned window
         holding its insertion slot; the first pending key of each segment
         is accepted — segments are disjoint, so all accepted windows run
         through one vectorized bounded-shift + fill-forward repair.
    Leftovers merge into the BMAT (unless ``merge_overflow=False``, used by
    the subset-retrain migration which re-homes BMAT keys itself).
    """
    W = static.window
    sk, sv, so = state.slots
    bmat = state.bmat
    c = state.counters
    halves = state.halves
    slot_halves = (
        None if halves is None else (halves.slot_hi, halves.slot_lo)
    )
    cap = sk.shape[0]
    assert cap % W == 0, "slot capacity must be W-aligned (nullifier align)"
    n = keys.shape[0]

    pending = (keys != KEY_MAX) & ~_dedup_last_wins(keys)
    n_keys, n_bmat_live = c.n_keys, c.n_bmat_live

    def locate(sk, slot_halves, pending):
        h = halves
        if h is not None:
            h = h._replace(slot_hi=slot_halves[0], slot_lo=slot_halves[1])
        qk = jnp.where(pending, keys, KEY_MAX)
        j, icap = _locate(static, sk, state.model, qk, halves=h)
        return qk, j, icap

    # upsert keys already in the slot array (revives tombstones)
    qk, j, icap = locate(sk, slot_halves, pending)
    hit, alive, _, jj = _probe(sk, sv, so, j, qk)
    n_keys = n_keys + jnp.sum(hit & ~alive)
    sv = sv.at[jnp.where(hit, jj, cap + 1)].set(vals, mode="drop")
    pending = pending & ~hit
    if check_bmat:
        # keys live in the BMAT -> value update there
        ranks = _bmat_rank(static, bmat, qk, halves=halves)
        _, b_alive, _, bidx = _bmat_probe(bmat, ranks, qk)
        upd = b_alive & pending
        bcap = bmat.keys.shape[0]
        bvals = bmat.vals.at[jnp.where(upd, bidx, bcap + 1)].set(
            vals, mode="drop"
        )
        bmat = bmat._replace(vals=bvals)
        pending = pending & ~upd
    n_round_keys = jnp.sum(pending, dtype=jnp.int64)

    rounds = max(1, static.insert_rounds)

    def accept_round(i, carry):
        """One grid-segment accept (the on-device greedy replacement), run
        as a loop body so the TPU compiler compiles it once, not once per
        round. Round 0 reuses the probe's locate (the slot keys have not
        moved since); each round locates for the next one, except the
        last, so a wave runs ``rounds`` locates in all."""
        (sk, sv, so, slot_halves, pending, n_keys, n_inplace, min_gran,
         j, icap) = carry
        qk = jnp.where(pending, keys, KEY_MAX)
        # clamp to the locate span so a boundary the bounded search could
        # not prove lands in the BMAT, never outside the searched rows
        ins_slot = jnp.clip(jnp.minimum(j + 1, icap), 0, cap - 1)
        bucket = jnp.where(pending, ins_slot // W, jnp.int64(cap // W + 1))
        order = jnp.argsort(bucket)  # stable: ties keep key order
        qs = qk[order]
        vs = vals[order]
        bs = bucket[order]
        pend_s = pending[order]
        first = jnp.concatenate(
            [jnp.ones(1, dtype=bool), bs[1:] != bs[:-1]]
        )
        accept = pend_s & first
        starts = jnp.clip(bs * W, 0, cap - W)
        sk, sv, so, can, failed_span, slot_halves = _inplace_window_insert(
            sk, sv, so, qs, vs, starts, accept, pend_s,
            W, static.movement_k, slot_halves=slot_halves,
        )
        ok = can & pend_s
        n_ok = jnp.sum(ok)
        pending = pending & ~jnp.zeros(n, dtype=bool).at[order].set(ok)
        j, icap = _next_locate(i, rounds, locate, sk, slot_halves, pending,
                               j, icap)
        return (sk, sv, so, slot_halves, pending, n_keys + n_ok,
                n_inplace + n_ok, jnp.minimum(min_gran, jnp.min(failed_span)),
                j, icap)

    sk, sv, so, slot_halves, pending, n_keys, n_inplace, min_gran, _, _ = (
        jax.lax.fori_loop(
            0, rounds, accept_round,
            (sk, sv, so, slot_halves, pending, n_keys, c.n_inplace,
             c.min_granularity, j, icap),
        )
    )

    if halves is not None:
        halves = halves._replace(
            slot_hi=slot_halves[0], slot_lo=slot_halves[1]
        )
    n_over = jnp.asarray(0, dtype=jnp.int64)
    if merge_overflow:
        bmat, n_bmat_live, n_over, bh = _merge_pending(
            static, bmat, keys, vals, pending, n_bmat_live, halves=halves
        )
        if halves is not None:
            halves = halves._replace(
                bmat_hi=bh[0], bmat_lo=bh[1], fence_hi=bh[2], fence_lo=bh[3]
            )

    counters = Counters(
        n_keys=n_keys,
        n_bmat_live=n_bmat_live,
        n_inplace=n_inplace,
        n_overflow=c.n_overflow + n_over,
        min_granularity=min_gran,
    )
    new_state = UpLIFState(
        slots=SlotsState(keys=sk, vals=sv, occ=so),
        model=state.model,
        bmat=bmat,
        counters=counters,
        halves=halves,
    )
    return new_state, InsertResult(
        pending=pending, n_overflow=n_over, n_round_keys=n_round_keys,
        n_rounds=jnp.asarray(rounds, dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# delete
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("static",))
def delete(state: UpLIFState, keys, *, static: UpLIFStatic):
    """Batched tombstone delete -> (state, hit bool[n]). Every occurrence of
    a deleted key reports a hit, but tombstones/counters apply once per
    distinct key (duplicates are masked out of the canonical set)."""
    sk, sv, so = state.slots
    bmat = state.bmat
    cap = sk.shape[0]
    canonical = ~_dedup_last_wins(keys)

    j, _ = _locate(static, sk, state.model, keys, halves=state.halves)
    _, alive, _, jj = _probe(sk, sv, so, j, keys)
    once = alive & canonical
    sv = sv.at[jnp.where(once, jj, cap + 1)].set(TOMBSTONE, mode="drop")

    ranks = _bmat_rank(static, bmat, keys, halves=state.halves)
    _, b_alive, _, bidx = _bmat_probe(bmat, ranks, keys)
    b_alive = b_alive & ~alive
    b_once = b_alive & canonical
    bcap = bmat.keys.shape[0]
    bvals = bmat.vals.at[jnp.where(b_once, bidx, bcap + 1)].set(
        TOMBSTONE, mode="drop"
    )

    c = state.counters
    counters = c._replace(
        n_keys=c.n_keys - jnp.sum(once),
        n_bmat_live=c.n_bmat_live - jnp.sum(b_once),
    )
    new_state = UpLIFState(
        slots=SlotsState(keys=sk, vals=sv, occ=so),
        model=state.model,
        bmat=bmat._replace(vals=bvals),
        counters=counters,
        halves=state.halves,  # tombstones touch vals only: halves unchanged
    )
    return new_state, alive | b_alive


# ---------------------------------------------------------------------------
# range scan — vmapped fixed-width slice + masked merge with the BMAT
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("static", "max_out"))
def range_scan(
    state: UpLIFState, lo, hi, lim=None, *, static: UpLIFStatic, max_out: int
):
    """Batched range extraction: the first ``lim`` live (key, value) pairs
    with lo <= key <= hi, in key order (``lim`` defaults to, and is capped
    at, ``max_out``). Returns fixed-shape KEY_MAX-padded arrays plus
    per-query counts — no host loop anywhere.

    Each query reads a fixed slice of the slots (``4 * max_out`` from
    ``lo``) and of the BMAT (``max_out`` from ``rank(lo)``). Every pair
    below the first key past either slice — the query's ``front`` — is
    seen, so the answer is exact up to ``front``: ``covered`` says that is
    all of it (``lim`` pairs found, or ``front > hi``). A query that is not
    covered (slices thinned by tombstones or gaps) is answered up to
    ``front``; the caller continues it from ``front`` with the limit left."""
    sk, sv, so = state.slots
    bmat = state.bmat
    cap = sk.shape[0]
    L = min(4 * max_out, cap)
    lim = max_out if lim is None else jnp.minimum(lim, max_out)

    j, _ = _locate(static, sk, state.model, lo, halves=state.halves)
    jj = jnp.clip(j, 0, cap - 1)
    s = jnp.where((j >= 0) & (sk[jj] == lo), jj, j + 1)
    s = jnp.clip(s, 0, cap - L)
    # slots at and past s + L hold keys >= sk[s + L] (sorted, fill-forward)
    s_end = s + L
    s_front = jnp.where(
        s_end < cap, sk[jnp.minimum(s_end, cap - 1)], KEY_MAX
    )

    def slice_one(si):
        return (
            jax.lax.dynamic_slice(sk, (si,), (L,)),
            jax.lax.dynamic_slice(sv, (si,), (L,)),
            jax.lax.dynamic_slice(so, (si,), (L,)),
        )

    seg_k, seg_v, seg_o = jax.vmap(slice_one)(s)
    ok = (
        seg_o
        & (seg_k >= lo[:, None])
        & (seg_k <= hi[:, None])
        & (seg_v != TOMBSTONE)
    )
    a_k = jnp.where(ok, seg_k, KEY_MAX)
    # in-slice keys are already sorted; pushing invalids to KEY_MAX keeps the
    # valid prefix sorted under a stable argsort
    a_ord = jnp.argsort(a_k, axis=1)[:, :max_out]
    a_k = jnp.take_along_axis(a_k, a_ord, axis=1)
    a_v = jnp.take_along_axis(jnp.where(ok, seg_v, 0), a_ord, axis=1)

    # ---- buffered slice: [rank(lo), rank(hi+1)) ------------------------
    bcap = bmat.keys.shape[0]
    M = min(max_out, bcap)
    hi_safe = jnp.minimum(hi, KEY_MAX - 1)
    r0 = _bmat_rank(static, bmat, lo, halves=state.halves).astype(jnp.int64)
    r1 = _bmat_rank(
        static, bmat, hi_safe + 1, halves=state.halves
    ).astype(jnp.int64)
    b_start = jnp.clip(r0, 0, bcap - M)
    b_end = b_start + M
    b_front = jnp.where(
        b_end < bcap, bmat.keys[jnp.minimum(b_end, bcap - 1)], KEY_MAX
    )

    def bslice(si):
        return (
            jax.lax.dynamic_slice(bmat.keys, (si,), (M,)),
            jax.lax.dynamic_slice(bmat.vals, (si,), (M,)),
        )

    b_k, b_v = jax.vmap(bslice)(b_start)
    b_abs = b_start[:, None] + jnp.arange(M, dtype=jnp.int64)[None, :]
    b_ok = (
        (b_abs >= r0[:, None])
        & (b_abs < r1[:, None])
        & (b_k >= lo[:, None])
        & (b_k <= hi[:, None])
        & (b_v != TOMBSTONE)
    )
    b_k = jnp.where(b_ok, b_k, KEY_MAX)
    b_v = jnp.where(b_ok, b_v, 0)

    # ---- merge the two sorted streams below the front, keep the first
    # ``lim`` ---------------------------------------------------------------
    front = jnp.minimum(s_front, b_front)
    m_k = jnp.concatenate([a_k, b_k], axis=1)
    m_v = jnp.concatenate([a_v, b_v], axis=1)
    seen = m_k < front[:, None]
    m_k = jnp.where(seen, m_k, KEY_MAX)
    m_ord = jnp.argsort(m_k, axis=1)[:, :max_out]
    out_k = jnp.take_along_axis(m_k, m_ord, axis=1)
    out_v = jnp.take_along_axis(jnp.where(seen, m_v, 0), m_ord, axis=1)
    n_seen = jnp.sum(out_k != KEY_MAX, axis=1)
    count = jnp.minimum(n_seen, lim).astype(jnp.int32)
    keep = jnp.arange(max_out)[None, :] < count[:, None]
    return RangeResult(
        keys=jnp.where(keep, out_k, KEY_MAX),
        vals=jnp.where(keep, out_v, 0),
        count=count,
        covered=(n_seen >= lim) | (front > hi),
        front=front,
    )


# ---------------------------------------------------------------------------
# stacked (sharded) op suite — S shards, ONE flat program
#
# The router (repro/core/sharded.py) stores S shards as one stacked pytree
# ([S, ...] leaves, equal per-shard shapes). Rather than vmapping (XLA:CPU
# lowers vmap-batched gathers ~2x slower) or unrolling S per-shard programs
# (op-count — and with it the CPU per-op fixed cost — scales with S), these
# variants FLATTEN the shard axis: queries arrive as ONE padded batch with
# a per-query shard id, and every gather/scatter goes through the [S*cap]
# view with a ``sid``-derived offset. Op count, per-op batch sizes and even
# the routing cost (no grouping, no result re-scatter) match the
# single-shard program exactly — S is amortized to zero on the hot path.
#
# Keys are range-partitioned across shards, so sorting a batch by key also
# groups it by shard — the grid-segment accept and the segmented BMAT merge
# both lean on that.
# ---------------------------------------------------------------------------


def _locate_stacked(static: UpLIFStatic, slot_keys, model, q, sid,
                    halves=None, codes=None):
    """Shard-local (j, ins_cap) of the last slot of shard ``sid`` with
    key <= q (same contract as ``_locate``).

    ``slot_keys`` is [S, cap]; ``q``/``sid`` are flat [N].

    Per-shard dispatch: when ``static.locate`` is a TUPLE of distinct
    strategies, ``codes`` (traced int32[S], indices into the tuple) assigns
    each shard its strategy. The wave runs once per distinct strategy —
    at most 3 launches, each a full-batch program identical to a uniform
    wave — and every query keeps the (j, ins_cap) pair of its own shard's
    branch, so the locate span (and with it the insert clamp) matches what
    a uniform run of that strategy would produce. The tuple is sorted and
    deduplicated by the router, so at most 7 static values exist
    (3 singles are plain strings; 3 pairs + 1 triple) and the jit cache
    stays flat no matter how the controller flips shards.
    """
    if isinstance(static.locate, tuple):
        sel = codes[sid]
        j = icap = None
        for i, strat in enumerate(static.locate):
            ji, ici = _locate_stacked(
                static._replace(locate=strat), slot_keys, model, q, sid,
                halves=halves,
            )
            if j is None:
                j, icap = ji, ici
            else:
                m = sel == i
                j = jnp.where(m, ji, j)
                icap = jnp.where(m, ici, icap)
        return j, icap

    S, cap = slot_keys.shape
    flat = slot_keys.reshape(-1)
    base = sid * cap

    if static.locate == LOCATE_BINSEARCH:
        n_iters = max(1, int(np.ceil(np.log2(cap + 1))))

        def body(_, carry):
            lo, hi = carry
            mid = (lo + hi) >> 1
            go = flat[base + jnp.minimum(mid, cap - 1)] <= q
            return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

        lo = jnp.zeros(q.shape, dtype=jnp.int64)
        hi = jnp.full(q.shape, cap, dtype=jnp.int64)
        lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
        return lo - 1, jnp.full(q.shape, cap - 1, dtype=jnp.int64)

    if static.locate == LOCATE_FUSED:
        # ONE kernel launch for all S shards: arrays flatten over the shard
        # axis and every query carries its base offsets (sid * dim), so S
        # stays amortized to zero exactly like the flat jnp variants.
        _require_fusable(kops.locate_fusable(
            cap, model.spline_keys.shape[1], model.table.shape[1], S
        ))
        return kops.fused_locate(
            model.table.reshape(-1), model.spline_keys.reshape(-1),
            model.spline_pos.reshape(-1), model.shift,
            flat, q, sid,
            n_table=model.table.shape[1],
            n_knots=model.spline_keys.shape[1],
            cap=cap, window=static.window, rs_iters=static.rs_iters,
            spline_hi=None if halves is None
            else halves.spline_hi.reshape(-1),
            spline_lo=None if halves is None
            else halves.spline_lo.reshape(-1),
            spline_pos32=None if halves is None
            else halves.spline_pos32.reshape(-1),
            slot_hi=None if halves is None else halves.slot_hi.reshape(-1),
            slot_lo=None if halves is None else halves.slot_lo.reshape(-1),
        )

    W = static.window
    L = min(3 * W, cap)  # 3-row drift-proof span (see _locate)
    n_bisect = max(1, int(np.ceil(np.log2(L))))
    T = model.table.shape[1]
    K = model.spline_keys.shape[1]
    tflat = model.table.reshape(-1)
    skflat = model.spline_keys.reshape(-1)
    spflat = model.spline_pos.reshape(-1)
    tbase = sid * T
    sbase = sid * K

    # every bounded search below runs in GLOBAL (flat) coordinates so the
    # loop bodies contain no shard-offset adds — the per-iteration op count
    # matches the single-shard program exactly
    n_buckets = T - 2
    b = jnp.clip(q >> model.shift[sid].astype(q.dtype), 0, n_buckets - 1)
    lo = sbase + jnp.maximum(tflat[tbase + b].astype(jnp.int64), 1) - 1
    hi = sbase + jnp.clip(tflat[tbase + b + 1].astype(jnp.int64), 0, K - 2)

    def sbody(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) >> 1
        go = skflat[mid] <= q
        return jnp.where(go, mid, lo), jnp.where(go, hi, mid - 1)

    lo, hi = jax.lax.fori_loop(0, static.rs_iters, sbody, (lo, hi))
    seg = jnp.clip(lo - sbase, 0, K - 2) + sbase
    k0 = skflat[seg]
    k1 = skflat[seg + 1]
    p0 = spflat[seg]
    p1 = spflat[seg + 1]
    dk = (q - k0).astype(jnp.float64)
    span = jnp.maximum((k1 - k0).astype(jnp.float64), 1.0)
    t = jnp.clip(dk / span, 0.0, 1.0)
    p = p0 + t * (p1 - p0)

    c = jnp.clip(jnp.round(p).astype(jnp.int64), 0, cap - 1)
    start = jnp.clip((c // W - 1) * W, 0, max(cap - L, 0))
    lo = base + start
    hi = base + jnp.minimum(start + L - 1, cap - 1)

    def wbody(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) >> 1
        go = flat[mid] <= q
        return jnp.where(go, mid, lo), jnp.where(go, hi, mid - 1)

    lo, hi = jax.lax.fori_loop(0, n_bisect, wbody, (lo, hi))
    j = jnp.where(flat[base + start] <= q, lo - base, start - 1)
    return j, start + (L - 1)


def _probe_stacked(slots: SlotsState, j, q, sid):
    S, cap = slots.keys.shape
    g = sid * cap + jnp.clip(j, 0, cap - 1)
    kk = slots.keys.reshape(-1)[g]
    vv = slots.vals.reshape(-1)[g]
    oo = slots.occ.reshape(-1)[g]
    hit = (j >= 0) & (kk == q) & oo & (q != KEY_MAX)
    alive = hit & (vv != TOMBSTONE)
    return hit, alive, jnp.where(alive, vv, 0), jnp.clip(j, 0, cap - 1)


def _bmat_rank_stacked(static: UpLIFStatic, bmat: BMATState, q, sid,
                       halves=None, codes=None):
    """Shard-local searchsorted-left rank; q/sid are flat [N].

    Mixed per-shard strategies collapse to AT MOST two launches here: the
    rank is an exact integer search whose jnp program depends only on
    ``bmat_kind`` (spline and binsearch shards share it bit-for-bit), so
    only a fused-vs-jnp partition of the batch remains.
    """
    if isinstance(static.locate, tuple):
        rj = _bmat_rank_stacked(
            static._replace(locate=LOCATE_BINSEARCH), bmat, q, sid,
            halves=halves,
        )
        if LOCATE_FUSED not in static.locate:
            return rj
        rf = _bmat_rank_stacked(
            static._replace(locate=LOCATE_FUSED), bmat, q, sid,
            halves=halves,
        )
        sel = codes[sid]
        return jnp.where(sel == static.locate.index(LOCATE_FUSED), rf, rj)

    S, cap = bmat.keys.shape
    kflat = bmat.keys.reshape(-1)
    base = sid * cap
    if static.locate == LOCATE_FUSED:
        _require_fusable(kops.rank_fusable(S * cap, S * bmat.fences.shape[1]))
        return kops.bmat_rank_fused(
            kflat, bmat.fences.reshape(-1), q, sid,
            cap=cap, nf=bmat.fences.shape[1], fanout=static.fanout,
            keys_hi=None if halves is None else halves.bmat_hi.reshape(-1),
            keys_lo=None if halves is None else halves.bmat_lo.reshape(-1),
            fences_hi=None if halves is None
            else halves.fence_hi.reshape(-1),
            fences_lo=None if halves is None
            else halves.fence_lo.reshape(-1),
        ).astype(jnp.int64)
    if static.bmat_kind == RBMAT:
        levels = max(1, int(np.log2(cap)))

        def body(l, t):
            stride = jnp.int64(1) << (levels - 1 - l)
            s = jnp.minimum((2 * t + 1) * stride - 1, cap - 1)
            go = kflat[base + s] < q
            return 2 * t + go.astype(t.dtype)

        t = jnp.zeros(q.shape, dtype=jnp.int64)
        t = jax.lax.fori_loop(0, levels, body, t)
        return jnp.minimum(t, cap)

    # global-coordinate searches (no shard-offset adds in the loop bodies);
    # mid <= hi <= fbase + nf - 1 is a loop invariant, so the fence gather
    # needs no clamping at all
    nf = bmat.fences.shape[1]
    fanout = static.fanout
    fflat = bmat.fences.reshape(-1)
    fbase = sid * nf
    fence_iters = max(1, int(np.ceil(np.log2(nf + 1))))
    node_iters = max(1, int(np.ceil(np.log2(fanout + 1))))

    def fsearch(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        go = fflat[mid] < q
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    lo, hi = jax.lax.fori_loop(
        0, fence_iters, fsearch, (fbase, fbase + nf - 1)
    )
    node_lo = base + jnp.maximum(lo - fbase - 1, 0) * fanout
    kcap = base + cap - 1

    def nsearch(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        go = kflat[jnp.minimum(mid, kcap)] < q
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    nlo, nhi = jax.lax.fori_loop(
        0, node_iters, nsearch,
        (node_lo, jnp.minimum(node_lo + fanout, base + cap)),
    )
    return jnp.minimum(nlo - base, cap)


def _bmat_probe_stacked(bmat: BMATState, ranks, q, sid):
    S, cap = bmat.keys.shape
    idx = jnp.minimum(ranks, cap - 1)
    g = sid * cap + idx
    kk = bmat.keys.reshape(-1)[g]
    vv = bmat.vals.reshape(-1)[g]
    present = (kk == q) & (q != KEY_MAX)
    alive = present & (vv != TOMBSTONE)
    return present, alive, jnp.where(alive, vv, 0), idx


def _seg_add(S, sid, mask):
    """Per-shard count of True entries (segmented sum via tiny scatter)."""
    return jnp.zeros((S,), dtype=jnp.int64).at[
        jnp.where(mask, sid, S)
    ].add(1, mode="drop")


def _route_on_device(boundaries, q):
    """Per-query shard id from the S-1 partition boundaries (log2(S) ops —
    cheaper than shipping a host-built sid array alongside every batch)."""
    return jnp.searchsorted(boundaries, q, side="right").astype(jnp.int64)


@functools.partial(jax.jit, static_argnames=("static",))
def slookup(state: UpLIFState, q, boundaries, codes=None, *,
            static: UpLIFStatic):
    """Stacked lookup: state leaves are [S, ...]; q is flat [N].
    ``codes`` is the per-shard strategy index (None unless ``static.locate``
    is a mixed tuple — see ``_locate_stacked``)."""
    sid = _route_on_device(boundaries, q)
    # named phases group the device operations in a profiler trace
    with jax.named_scope("locate"):
        j, _ = _locate_stacked(
            static, state.slots.keys, state.model, q, sid,
            halves=state.halves, codes=codes,
        )
        _, alive, vals, _ = _probe_stacked(state.slots, j, q, sid)
    with jax.named_scope("rank"):
        ranks = _bmat_rank_stacked(
            static, state.bmat, q, sid, halves=state.halves, codes=codes
        )
        _, b_alive, b_vals, _ = _bmat_probe_stacked(state.bmat, ranks, q, sid)
    b_alive = b_alive & ~alive
    return alive | b_alive, jnp.where(b_alive, b_vals, vals)


@functools.partial(jax.jit, static_argnames=("static",))
def sdelete(state: UpLIFState, q, boundaries, codes=None, *,
            static: UpLIFStatic):
    """Stacked tombstone delete -> (state, hit [N])."""
    S, cap = state.slots.keys.shape
    sid = _route_on_device(boundaries, q)
    canonical = ~_dedup_last_wins(q)

    j, _ = _locate_stacked(
        static, state.slots.keys, state.model, q, sid,
        halves=state.halves, codes=codes,
    )
    _, alive, _, jj = _probe_stacked(state.slots, j, q, sid)
    once = alive & canonical
    sv = state.slots.vals.reshape(-1).at[
        jnp.where(once, sid * cap + jj, S * cap + 1)
    ].set(TOMBSTONE, mode="drop").reshape(S, cap)

    bcap = state.bmat.keys.shape[1]
    ranks = _bmat_rank_stacked(
        static, state.bmat, q, sid, halves=state.halves, codes=codes
    )
    _, b_alive, _, bidx = _bmat_probe_stacked(state.bmat, ranks, q, sid)
    b_alive = b_alive & ~alive
    b_once = b_alive & canonical
    bvals = state.bmat.vals.reshape(-1).at[
        jnp.where(b_once, sid * bcap + bidx, S * bcap + 1)
    ].set(TOMBSTONE, mode="drop").reshape(S, bcap)

    c = state.counters
    counters = c._replace(
        n_keys=c.n_keys - _seg_add(S, sid, once),
        n_bmat_live=c.n_bmat_live - _seg_add(S, sid, b_once),
    )
    new_state = state._replace(
        slots=state.slots._replace(vals=sv),
        bmat=state.bmat._replace(vals=bvals),
        counters=counters,
    )
    return new_state, alive | b_alive


@functools.partial(jax.jit, static_argnames=("static",))
def srank(state: UpLIFState, q, boundaries, codes=None, *,
          static: UpLIFStatic):
    """Stacked shard-local adjusted rank (O(cap) reduce — API/tests only)."""
    sid = _route_on_device(boundaries, q)
    live = state.slots.occ & (state.slots.vals != TOMBSTONE)
    keys_q = state.slots.keys[sid]   # [N, cap] batched gather (cold path)
    live_q = live[sid]
    arr_rank = jnp.sum(live_q & (keys_q < q[:, None]), axis=1)
    return arr_rank + _bmat_rank_stacked(
        static, state.bmat, q, sid, halves=state.halves, codes=codes
    )


def _merge_pending_stacked(static, bmat: BMATState, keys, vals, pending, sid,
                           n_bmat_live, halves=None, codes=None):
    """Segmented (per-shard) BMAT merge over the flat [S*bcap] view.
    Returns refreshed (bmat_hi, bmat_lo, fence_hi, fence_lo) halves last
    (None when ``halves`` is None) — the merge rewrites the packed arrays,
    so splitting its output is proportional work done once per batch."""
    S, bcap = bmat.keys.shape
    qk = jnp.where(pending, keys, KEY_MAX)
    ranks = _bmat_rank_stacked(
        static, bmat, qk, sid, halves=halves, codes=codes
    )
    present, _, _, idx = _bmat_probe_stacked(bmat, ranks, qk, sid)
    present = present & pending
    bv_flat = bmat.vals.reshape(-1)
    revived = present & (bv_flat[sid * bcap + idx] == TOMBSTONE)
    new_vals = bv_flat.at[
        jnp.where(present, sid * bcap + idx, S * bcap + 1)
    ].set(vals, mode="drop")
    fresh = pending & ~present
    cnt = _seg_add(S, sid, fresh)            # fresh keys per shard
    shard_start = jnp.cumsum(cnt) - cnt      # exclusive prefix

    # keys are range-partitioned, so sorting by key groups fresh entries by
    # shard while ordering them within the shard — exactly the layout the
    # per-shard merged positions need
    mk = jnp.where(fresh, keys, KEY_MAX)
    order = jnp.argsort(mk)
    mk = mk[order]
    mv = jnp.where(fresh, vals, 0)[order]
    fr = fresh[order]
    sid_s = jnp.where(fr, sid[order], 0)
    r2 = _bmat_rank_stacked(
        static, bmat, mk, sid_s, halves=halves, codes=codes
    )
    g_idx = jnp.cumsum(fr) - 1               # global index among fresh
    within = g_idx - shard_start[sid_s]
    new_pos = r2 + within
    tgt = jnp.where(fr, sid_s * bcap + new_pos, S * bcap)

    N = mk.shape[0]
    mark = jnp.zeros((S * bcap,), dtype=jnp.int32).at[tgt].set(1, mode="drop")
    new_at = jnp.full((S * bcap,), -1, dtype=jnp.int32).at[tgt].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop"
    )
    cum = prefix_count(mark).reshape(S, bcap)
    seg_base = jnp.concatenate([jnp.zeros(1, cum.dtype), cum[:-1, -1]])
    nb = cum - seg_base[:, None]
    i = jnp.arange(bcap, dtype=jnp.int64)[None, :]
    new_at = new_at.reshape(S, bcap)
    is_new = new_at >= 0
    old_idx = jnp.clip(i - nb, 0, bcap - 1)
    from_old = ~is_new & ((i - nb) < bmat.size[:, None])
    pick = jnp.clip(new_at, 0, N - 1)
    bbase = (jnp.arange(S, dtype=jnp.int64) * bcap)[:, None]
    g = bbase + old_idx
    out_keys = jnp.where(
        is_new, mk[pick],
        jnp.where(from_old, bmat.keys.reshape(-1)[g], KEY_MAX),
    )
    out_vals = jnp.where(is_new, mv[pick], jnp.where(from_old, new_vals[g], 0))
    out_fences = _make_fences_stacked(out_keys, static.fanout)
    out = BMATState(
        keys=out_keys,
        vals=out_vals,
        fences=out_fences,
        size=bmat.size + cnt.astype(bmat.size.dtype),
    )
    bmat_halves = None
    if halves is not None:
        bmat_halves = kops.split_key(out_keys) + kops.split_key(out_fences)
    n_over = _seg_add(S, sid, pending)
    return (
        out, n_bmat_live + _seg_add(S, sid, revived) + cnt, n_over,
        bmat_halves,
    )


def _make_fences_stacked(keys, fanout: int):
    S = keys.shape[0]
    f = keys[:, ::fanout]
    return jnp.concatenate(
        [f, jnp.full((S, 1), KEY_MAX, dtype=keys.dtype)], axis=1
    )


@functools.partial(jax.jit, static_argnames=("static",))
def sinsert(state: UpLIFState, keys, vals, boundaries, codes=None, *,
            static: UpLIFStatic):
    """Stacked upsert: keys/vals/sid are flat [N]. One flat program — the
    grid windows of all shards tile the concatenated slot array (per-shard
    capacities are W-aligned), so the global grid-segment accept and the
    window writeback run exactly like the single-shard path on the
    [S*cap] view."""
    W = static.window
    S, cap = state.slots.keys.shape
    assert cap % W == 0
    N = keys.shape[0]
    sid = _route_on_device(boundaries, keys)
    nw_per = cap // W
    sk = state.slots.keys.reshape(-1)
    sv = state.slots.vals.reshape(-1)
    so = state.slots.occ.reshape(-1)
    bmat = state.bmat
    c = state.counters
    halves = state.halves
    # the in-loop window writeback runs on the flat [S*cap] view, so the
    # slot halves travel flat too; reshaped back to [S, cap] at the end
    slot_halves = (
        None if halves is None
        else (halves.slot_hi.reshape(-1), halves.slot_lo.reshape(-1))
    )

    pending = (keys != KEY_MAX) & ~_dedup_last_wins(keys)
    n_keys, n_bmat_live = c.n_keys, c.n_bmat_live

    def locate(sk, slot_halves, pending):
        h = halves
        if h is not None:
            h = h._replace(
                slot_hi=slot_halves[0].reshape(S, cap),
                slot_lo=slot_halves[1].reshape(S, cap),
            )
        qk = jnp.where(pending, keys, KEY_MAX)
        j, icap = _locate_stacked(
            static, sk.reshape(S, cap), state.model, qk, sid,
            halves=h, codes=codes,
        )
        return qk, j, icap

    # ---- upsert keys already in the slot array or live in the BMAT ------
    # (named phases group the device operations in a profiler trace)
    with jax.named_scope("probe"):
        qk, j, icap = locate(sk, slot_halves, pending)
        slots2 = SlotsState(
            keys=sk.reshape(S, cap), vals=sv.reshape(S, cap),
            occ=so.reshape(S, cap),
        )
        hit, alive, _, jj = _probe_stacked(slots2, j, qk, sid)
        n_keys = n_keys + _seg_add(S, sid, hit & ~alive)
        sv = sv.at[jnp.where(hit, sid * cap + jj, S * cap + 1)].set(
            vals, mode="drop"
        )
        ranks = _bmat_rank_stacked(
            static, bmat, qk, sid, halves=halves, codes=codes
        )
        _, b_alive, _, bidx = _bmat_probe_stacked(bmat, ranks, qk, sid)
        upd = b_alive & pending
        bcap = bmat.keys.shape[1]
        bvals = bmat.vals.reshape(-1).at[
            jnp.where(upd, sid * bcap + bidx, S * bcap + 1)
        ].set(vals, mode="drop").reshape(S, bcap)
        bmat = bmat._replace(vals=bvals)
        pending = pending & ~hit & ~upd
    n_round_keys = jnp.sum(pending, dtype=jnp.int64)

    rounds = max(1, static.insert_rounds)

    def accept_round(carry):
        """One global grid-segment accept over the flat view. The rounds
        run as a loop, not unrolled: the TPU compiler then compiles the
        round once instead of once per round. The probe's locate serves
        round 0; a later round first locates over the slots the round
        before it rewrote."""
        (i, _, sk, sv, so, slot_halves, pending, n_keys, n_inplace,
         min_gran, j, icap) = carry
        if rounds > 1:
            j, icap = jax.lax.cond(
                i > 0,
                lambda: locate(sk, slot_halves, pending)[1:],
                lambda: (j, icap),
            )
        qk = jnp.where(pending, keys, KEY_MAX)
        ins_slot = jnp.clip(jnp.minimum(j + 1, icap), 0, cap - 1)
        bucket = jnp.where(
            pending, sid * nw_per + ins_slot // W, jnp.int64(S * nw_per + 1)
        )
        order = jnp.argsort(bucket)
        qs = qk[order]
        vs = vals[order]
        bs = bucket[order]
        ps = pending[order]
        first = jnp.concatenate([jnp.ones(1, dtype=bool), bs[1:] != bs[:-1]])
        accept = ps & first
        starts = jnp.clip(bs * W, 0, S * cap - W)
        sk, sv, so, can, failed_span, slot_halves = _inplace_window_insert(
            sk, sv, so, qs, vs, starts, accept, ps, W, static.movement_k,
            slot_halves=slot_halves,
        )
        ok = can & ps
        sid_w = jnp.clip(bs // nw_per, 0, S - 1)
        ok_per = _seg_add(S, sid_w, ok)
        span_per = jnp.full((S,), _I64_MAX).at[
            jnp.where(failed_span < _I64_MAX, sid_w, S)
        ].min(failed_span, mode="drop")
        pending = pending & ~jnp.zeros(N, dtype=bool).at[order].set(ok)
        return (i + 1, jnp.sum(ok, dtype=jnp.int32), sk, sv, so, slot_halves,
                pending, n_keys + ok_per, n_inplace + ok_per,
                jnp.minimum(min_gran, span_per), j, icap)

    def can_place(carry):
        """Run a round while a key is pending and the round before placed
        one: a round that places none leaves the slots, ``pending`` and
        the counters as they were, so every later round would repeat it."""
        i, n_placed, pending = carry[0], carry[1], carry[6]
        return (i < rounds) & jnp.any(pending) & ((i == 0) | (n_placed > 0))

    with jax.named_scope("rounds"):
        (n_rounds, _, sk, sv, so, slot_halves, pending, n_keys, n_inplace,
         min_gran, _, _) = jax.lax.while_loop(
            can_place, accept_round,
            (jnp.int32(0), jnp.int32(0), sk, sv, so, slot_halves, pending,
             n_keys, c.n_inplace, c.min_granularity, j, icap),
        )

    if halves is not None:
        halves = halves._replace(
            slot_hi=slot_halves[0].reshape(S, cap),
            slot_lo=slot_halves[1].reshape(S, cap),
        )
    with jax.named_scope("merge"):
        bmat, n_bmat_live, n_over, bh = _merge_pending_stacked(
            static, bmat, keys, vals, pending, sid, n_bmat_live,
            halves=halves, codes=codes,
        )
    if halves is not None:
        halves = halves._replace(
            bmat_hi=bh[0], bmat_lo=bh[1], fence_hi=bh[2], fence_lo=bh[3]
        )
    counters = Counters(
        n_keys=n_keys,
        n_bmat_live=n_bmat_live,
        n_inplace=n_inplace,
        n_overflow=c.n_overflow + n_over,
        min_granularity=min_gran,
    )
    new_state = UpLIFState(
        slots=SlotsState(
            keys=sk.reshape(S, cap), vals=sv.reshape(S, cap),
            occ=so.reshape(S, cap),
        ),
        model=state.model,
        bmat=bmat,
        counters=counters,
        halves=halves,
    )
    return new_state, InsertResult(
        pending=pending, n_overflow=jnp.sum(n_over),
        n_round_keys=n_round_keys, n_rounds=n_rounds,
    )


# ---------------------------------------------------------------------------
# logical rank (paper Eq. 1 — validation / RL features only)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("static",))
def adjusted_rank(state: UpLIFState, queries, *, static: UpLIFStatic):
    """M'(k) = live in-place rank + BMAT bias r(k) (O(cap) reduce)."""
    sk, sv, so = state.slots
    live = so & (sv != TOMBSTONE)
    arr_rank = jnp.sum(
        live[None, :] & (sk[None, :] < queries[:, None]), axis=1
    )
    return arr_rank + _bmat_rank(
        static, state.bmat, queries, halves=state.halves
    ).astype(jnp.int64)
