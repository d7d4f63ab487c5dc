"""BMAT rank kernel — the bias query r(k) of Definition 1, fused.

Two-level B+MAT search in one kernel: (1) bounded binary search over the
fence array (every FANOUT-th key; VMEM-resident — the analogue of inner
nodes living in cache), (2) search inside the located node. The kernel is
offset-aware: key/fence arrays arrive flattened over the shard axis and
every query carries its base offsets (kbase = sid * cap, fbase = sid * nf),
so the stacked fops rank path runs S BMATs in one launch with the
per-query op count of a single shard — the same generalization the fused
locate kernel uses. A single BMAT is just the all-zero-bases case, so one
kernel serves both (test_kernels pins byte-identity per shard). Searches
run in GLOBAL (flat) coordinates so the loop bodies contain no offset
adds; ``mid <= fbase + nf - 1`` is a fence-loop invariant, so the fence
probe needs no clamping (mirrors fops._bmat_rank_stacked). The node is a
contiguous run of at most ``fanout`` sorted keys, searched a row at a time
(``rows.last_in_span``). Keys and fences stay whole in VMEM as [R, 128]
rows (see ``rows``) up to ops.MAX_VMEM_KEYS; larger buffers fall back to
the two-level tile_search composition in ops.py.
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
    key_cmp_at,
    last_in_span,
    pad_queries,
    q_block,
    rows_bytes,
    to_rows,
    vmem_limit,
    whole,
)


def _offset_kernel(fanout: int, fence_iters: int, cap: int, nf: int,
                   blk: int,
                   keys_hi_ref, keys_lo_ref, f_hi_ref, f_lo_ref,
                   q_hi_ref, q_lo_ref, kbase_ref, fbase_ref, out_ref):
    i32 = np.int32

    def one(i, carry):
        qh = q_hi_ref[i]
        qlf = q_lo_ref[i] ^ SIGN
        kbase = kbase_ref[i]
        fbase = fbase_ref[i]

        def fstep(_, carry):
            lo, hi = carry
            mid = (lo + hi) >> i32(1)
            go = key_cmp_at(f_hi_ref, f_lo_ref, mid, qh, qlf, strict=True)
            return jnp.where(go, mid + i32(1), lo), jnp.where(go, hi, mid)

        lo, _ = jax.lax.fori_loop(
            i32(0), i32(fence_iters), fstep, (fbase, fbase + i32(nf - 1))
        )
        node_lo = kbase + jnp.maximum(lo - fbase - i32(1), i32(0)) * i32(fanout)
        node_hi = jnp.minimum(node_lo + i32(fanout), kbase + i32(cap))
        # searchsorted-left inside the node = one past the last key < q
        nlo = last_in_span(keys_hi_ref, keys_lo_ref, node_lo, node_hi, fanout,
                           qh, qlf, strict=True) + i32(1)
        out_ref[i] = jnp.minimum(nlo - kbase, i32(cap))
        return carry

    jax.lax.fori_loop(i32(0), i32(blk), one, i32(0))


def bmat_rank_offset_pallas(
    keys_hi, keys_lo, f_hi, f_lo, q_hi, q_lo, kbase, fbase, *,
    cap: int, nf: int, fanout: int, interpret: bool,
):
    """Shard-local searchsorted-left rank per query (int32, in [0, cap]).
    ``cap``/``nf`` are PER-SHARD dims of the flattened key/fence arrays."""
    q = q_hi.shape[0]
    blk = q_block(q)
    assert q == pad_queries(q), "pad queries with rows.pad_queries (ops.py does)"
    fence_iters = int(np.ceil(np.log2(nf + 1)))
    resident = [to_rows(a) for a in (keys_hi, keys_lo, f_hi, f_lo)]
    per_q = pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_offset_kernel, fanout, fence_iters, cap, nf, blk),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        grid=(q // blk,),
        in_specs=[whole(pltpu.VMEM, 2)] * 4 + [per_q] * 4,
        out_specs=per_q,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(rows_bytes(*resident))
        ),
        interpret=interpret,
        name="bmat_rank",
    )(*resident, q_hi, jax.lax.bitcast_convert_type(q_lo, jnp.int32),
      kbase, fbase)
