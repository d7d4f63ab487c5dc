"""Fused RadixSpline lookup kernel (the paper's Module-1 hot path).

Per query: radix-table prefix probe → bounded binary search over spline knots
→ linear interpolation. One kernel launch handles a full query batch; the
radix table and knot arrays are VMEM-resident (see ops.py for size guards),
queries are tiled Q_BLK at a time.

TPU notes:
  * keys are (hi:int32, lo:uint32) pairs — no int64 on the VPU;
  * positions are float32 (precision bound: capacity < 2^24 exact; above
    that the last-mile window absorbs <=0.5-slot rounding, ops.py widens
    the caller's search margin by 1);
  * Q_BLK = 1024 keeps the per-step working set (queries + outputs) at a
    few KB; the knot arrays dominate VMEM (12B/knot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rows import (
    SIGN,
    f32_of_u32,
    key_cmp_at,
    last_in_span,
    pad_queries,
    pick,
    q_block,
    rows_bytes,
    to_rows,
    vmem_limit,
    whole,
)

Q_BLK = 1024


def _kernel(shift: int, n_iters: int, table_ref, sk_hi_ref, sk_lo_ref, sp_ref,
            q_hi_ref, q_lo_ref, out_ref):
    table = table_ref[...]
    sk_hi = sk_hi_ref[...]
    sk_lo = sk_lo_ref[...]
    sp = sp_ref[...]
    q_hi = q_hi_ref[...]
    q_lo = q_lo_ref[...]

    n_spline = sk_hi.shape[0] - 1
    n_buckets = table.shape[0] - 2
    # radix prefix: the table shift consumes >= 32 low bits for the assigned
    # key domain, so the prefix comes from hi alone (guarded in ops.py).
    b = jnp.clip(q_hi >> (shift - 32), 0, n_buckets - 1)
    lo = jnp.maximum(jnp.take(table, b), 1) - 1
    hi = jnp.clip(jnp.take(table, b + 1), 0, n_spline - 1)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) >> 1
        m_hi = jnp.take(sk_hi, mid)
        m_lo = jnp.take(sk_lo, mid)
        go = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo <= q_lo))
        return jnp.where(go, mid, lo), jnp.where(go, hi, mid - 1)

    lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    s = jnp.clip(lo, 0, n_spline - 1)

    k0_hi = jnp.take(sk_hi, s)
    k0_lo = jnp.take(sk_lo, s)
    k1_hi = jnp.take(sk_hi, s + 1)
    k1_lo = jnp.take(sk_lo, s + 1)
    # 52-bit deltas fit float32 *relatively*: dk/seg is computed from
    # hi/lo-decomposed differences accumulated in f32
    two32 = jnp.float32(4294967296.0)
    dk = (q_hi - k0_hi).astype(jnp.float32) * two32 + (
        q_lo.astype(jnp.float32) - k0_lo.astype(jnp.float32)
    )
    seg = (k1_hi - k0_hi).astype(jnp.float32) * two32 + (
        k1_lo.astype(jnp.float32) - k0_lo.astype(jnp.float32)
    )
    t = jnp.clip(dk / jnp.maximum(seg, 1.0), 0.0, 1.0)
    p0 = jnp.take(sp, s)
    p1 = jnp.take(sp, s + 1)
    out_ref[...] = p0 + t * (p1 - p0)


def spline_lookup_pallas(
    table, sk_hi, sk_lo, sp, q_hi, q_lo, *, shift: int, n_iters: int,
    interpret: bool,
):
    """Launch over ceil(Q / Q_BLK) grid steps; Q must be Q_BLK-aligned."""
    q = q_hi.shape[0]
    assert q % Q_BLK == 0, "pad queries to Q_BLK (ops.py does this)"
    t = table.shape[0]
    s = sk_hi.shape[0]
    grid = (q // Q_BLK,)
    full = lambda n: pl.BlockSpec((n,), lambda i: (0,))
    per_q = pl.BlockSpec((Q_BLK,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_kernel, shift, n_iters),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.float32),
        grid=grid,
        in_specs=[full(t), full(s), full(s), full(s), per_q, per_q],
        out_specs=per_q,
        interpret=interpret,
    )(table, sk_hi, sk_lo, sp, q_hi, q_lo)


# ---------------------------------------------------------------------------
# Fused locate: radix predict + knot search + interpolation + bounded
# 3-row window search over the slot array — ONE launch per query batch.
#
# This is the hot-path form of the kernel above: instead of returning the
# float prediction (and paying a second launch + an HBM round-trip for the
# last-mile search), the kernel carries the prediction straight into the
# drift-proof 3-row span search over the slot keys and emits the final
# located index. It walks its query block one query at a time on the
# scalar unit (see ``rows``: Mosaic lowers no per-element vector gather),
# so it compiles for the TPU. All array inputs arrive FLATTENED over the
# shard axis and every query carries base offsets into them (tbase = sid*T,
# sbase = sid*K, slot base = sid*cap), so S stacked shards run in the same
# launch with the same per-query op count as one shard — the offset-aware
# generalization the stacked fops variants need. The radix shift is a
# per-query vector too (shards retrain independently, so their shifts
# differ); prefixes are assembled from the (hi, lo) halves for any shift
# in [0, 63].
# ---------------------------------------------------------------------------


def _locate_kernel(
    n_table: int, n_knots: int, cap: int, window: int, L: int,
    rs_iters: int, blk: int,
    table_ref, sk_hi_ref, sk_lo_ref, sp_ref, sl_hi_ref, sl_lo_ref,
    q_hi_ref, q_lo_ref, tb_ref, sb_ref, slb_ref, sh_ref,
    j_ref, start_ref,
):
    i32 = np.int32
    n_buckets = i32(n_table - 2)
    log_w = window.bit_length() - 1
    two32 = np.float32(4294967296.0)

    def clip(x, lo, hi):
        return jnp.minimum(jnp.maximum(x, i32(lo)), i32(hi))

    def one(i, carry):
        qh = q_hi_ref[i]
        ql = q_lo_ref[i]
        qlf = ql ^ SIGN
        sh = sh_ref[i]
        sb = sb_ref[i]
        slb = slb_ref[i]

        # radix prefix = key >> shift, assembled from the halves: shift >= 32
        # reads hi alone; below 32 it splices hi's low bits above lo's
        # surviving bits. The splice SATURATES instead of wrapping: a query
        # key above the trained domain (hi >= 2**(shift-1)) must land in the
        # LAST bucket exactly like the jnp path's clip. The splice is a
        # uint32 held in int32 bits, so "negative" means >= 2**31 and
        # saturates too (n_buckets - 1 < 2**31).
        pref_hi = qh >> clip(sh - i32(32), 0, 31)
        pref_u = jax.lax.shift_left(qh, clip(i32(32) - sh, 0, 31)) | (
            jax.lax.shift_right_logical(ql, clip(sh, 0, 31))
        )
        over = qh >= jax.lax.shift_left(i32(1), clip(sh - i32(1), 0, 31))
        last = n_buckets - i32(1)
        pref_lo = jnp.where(
            over | (pref_u < i32(0)), last, jnp.minimum(pref_u, last)
        )
        b = clip(jnp.where(sh >= i32(32), pref_hi, pref_lo), 0, n_buckets - 1)

        # knot search in GLOBAL (flat) coordinates — no offset adds in the body
        tb = tb_ref[i] + b
        lo = sb + jnp.maximum(table_ref[tb], i32(1)) - i32(1)
        hi = sb + clip(table_ref[tb + i32(1)], 0, n_knots - 2)

        def sbody(_, carry):
            lo, hi = carry
            mid = (lo + hi + i32(1)) >> i32(1)
            go = key_cmp_at(sk_hi_ref, sk_lo_ref, mid, qh, qlf, strict=False)
            return jnp.where(go, mid, lo), jnp.where(go, hi, mid - i32(1))

        lo, _ = jax.lax.fori_loop(i32(0), i32(rs_iters), sbody, (lo, hi))
        s = clip(lo - sb, 0, n_knots - 2) + sb
        s1 = s + i32(1)

        k0_hi = pick(sk_hi_ref, s)
        k0_lo = f32_of_u32(pick(sk_lo_ref, s))
        k1_hi = pick(sk_hi_ref, s1)
        k1_lo = f32_of_u32(pick(sk_lo_ref, s1))
        p0 = pick(sp_ref, s)
        p1 = pick(sp_ref, s1)
        # 52-bit deltas fit float32 *relatively*: accumulated from the
        # (hi, lo)-decomposed differences
        dk = (qh - k0_hi).astype(jnp.float32) * two32 + (f32_of_u32(ql) - k0_lo)
        seg = (k1_hi - k0_hi).astype(jnp.float32) * two32 + (k1_lo - k0_lo)
        t = jnp.minimum(
            jnp.maximum(dk / jnp.maximum(seg, np.float32(1.0)), np.float32(0.0)),
            np.float32(1.0),
        )
        p = p0 + t * (p1 - p0)

        # positions are f32: exact below 2**24 (ops.py guards capacity), and
        # the 3-row span has >= W/2 slots of slack on either side of the
        # truth, so sub-slot interpolation jitter vs the f64 jnp path cannot
        # push a live key out of the searched span (DESIGN §Locate-strategy).
        c = clip(jnp.round(p).astype(jnp.int32), 0, cap - 1)
        start = clip(((c >> i32(log_w)) - i32(1)) * i32(window), 0, max(cap - L, 0))
        # the slot array is sorted (fill-forward invariant), so the last
        # slot <= q in the span is the 3-row bisect's answer
        glo = slb + start
        jg = last_in_span(sl_hi_ref, sl_lo_ref, glo, glo + i32(L), L, qh, qlf,
                          strict=False)
        j_ref[i] = jg - slb
        start_ref[i] = start
        return carry

    jax.lax.fori_loop(i32(0), i32(blk), one, i32(0))


def fused_locate_pallas(
    table, sk_hi, sk_lo, sp, sl_hi, sl_lo,
    q_hi, q_lo, tbase, sbase, slot_base, shift,
    *, n_table: int, n_knots: int, cap: int, window: int, rs_iters: int,
    interpret: bool,
):
    """(j, start) per query: j = shard-local index of the last slot with
    key <= q inside the 3-row span (start - 1 when the span holds no such
    slot); start = shard-local span start, so icap = start + L - 1.
    ``n_table``/``n_knots``/``cap`` are PER-SHARD dims of the flattened
    inputs (the shard count is implicit in the base offsets). The radix
    table sits in SMEM; knots and slots stay whole in VMEM as [R, 128]
    rows; the per-query vectors stream through SMEM blocks."""
    q = q_hi.shape[0]
    blk = q_block(q)
    assert q == pad_queries(q), "pad queries with rows.pad_queries (ops.py does)"
    assert window & (window - 1) == 0, "window must be a power of two"
    L = min(3 * window, cap)
    resident = [to_rows(a) for a in (sk_hi, sk_lo, sp, sl_hi, sl_lo)]
    per_q = pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM)
    vmem = whole(pltpu.VMEM, 2)
    as_i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)
    return pl.pallas_call(
        functools.partial(
            _locate_kernel, n_table, n_knots, cap, window, L, rs_iters, blk,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((q,), jnp.int32),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ),
        grid=(q // blk,),
        in_specs=[whole(pltpu.SMEM, 1)] + [vmem] * 5 + [per_q] * 6,
        out_specs=(per_q, per_q),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(rows_bytes(*resident))
        ),
        interpret=interpret,
        name="fused_locate",
    )(table, *resident, q_hi, as_i32(q_lo), tbase, sbase, slot_base, shift)
