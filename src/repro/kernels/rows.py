"""Row layout and scalar reads shared by the search kernels.

Mosaic (the TPU Pallas compiler) lowers no vector gather with per-element
indices into a VMEM array, and loads only whole lanes at a dynamic offset.
The search kernels therefore walk their query block one query at a time
on the scalar unit: every searched array is VMEM-resident as ``[R, 128]``
rows, a probe of element ``idx`` loads row ``idx >> 7`` at a dynamic
sublane offset, and the lane ``idx & 127`` comes out through a masked
``max`` (the one reduction x64-safe in Mosaic for int32). A contiguous
range of a sorted array is searched a row at a time with a vector compare,
which needs no probe per bisect step.

``lo`` key halves arrive as uint32 and are reinterpreted as int32 bits (the
scalar unit has no unsigned reductions); unsigned order is signed order of
``bits ^ SIGN``. Every constant is an explicit 32-bit numpy scalar: under
global x64 a Python int passed to a jitted jnp helper becomes int64, which
Mosaic rejects.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.shapes import pow2_at_least

LANES = 128
Q_TILE = 1024
_PAD = 8 * LANES  # whole (8, 128) tiles
SIGN = np.int32(-(2**31))
_NEG_INF = np.float32(-np.inf)


def to_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Flat [n] -> [R, 128] rows (R a multiple of 8, zero padded); uint32
    becomes its int32 bit pattern."""
    if x.dtype == jnp.uint32:
        x = jax.lax.bitcast_convert_type(x, jnp.int32)
    n = x.shape[0]
    m = -(-n // _PAD) * _PAD
    if m != n:
        x = jnp.pad(x, (0, m - n))
    return x.reshape(m // LANES, LANES)


def whole(memory_space, ndim: int) -> pl.BlockSpec:
    """The whole array resident in ``memory_space`` for the entire grid
    (copied in once, never re-fetched). The index map returns int32 zeros:
    Pallas' default map returns Python ints, which x64 makes int64."""
    return pl.BlockSpec(
        None, lambda *_: (np.int32(0),) * ndim, memory_space=memory_space
    )


def q_block(q: int) -> int:
    """Per-query SMEM block: the whole batch up to 1024 queries, else 1024
    (XLA tiles 1-D int32 HBM arrays by 1024, and an SMEM block must match
    that tiling). ``q`` comes from ``pad_queries``."""
    return min(q, Q_TILE)


def pad_queries(n: int) -> int:
    """Padded batch length for the search kernels: a power of two from 256
    up to 1024, then a multiple of 1024 (see ``q_block``)."""
    if n <= Q_TILE:
        return max(256, pow2_at_least(n))
    return -(-n // Q_TILE) * Q_TILE


def rows_bytes(*arrays) -> int:
    """VMEM bytes of the given [R, 128] arrays."""
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


def vmem_limit(resident: int) -> int:
    """Scoped-VMEM limit for a kernel keeping ``resident`` bytes whole in
    VMEM (single-buffered) plus its small pipelined query blocks."""
    return int(resident + (8 << 20))


def n_span_rows(length: int) -> int:
    """Rows a contiguous span of ``length`` elements can touch."""
    return (length + 2 * LANES - 2) // LANES


def _lane():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _row(ref, idx):
    return ref[pl.ds(idx >> np.int32(7), 1), :]


def pick(ref, idx):
    """Scalar ``ref[idx]`` of an int32 or float32 [R, 128] ref."""
    fill = _NEG_INF if ref.dtype == jnp.float32 else np.iinfo(np.int32).min
    sel = _lane() == (idx & np.int32(LANES - 1))
    return jnp.max(jnp.where(sel, _row(ref, idx), ref.dtype.type(fill)))


def key_cmp(h, lf, qh, qlf, strict: bool):
    """key < q (``strict``) or key <= q on int32 hi / sign-flipped lo."""
    tie = (lf < qlf) if strict else (lf <= qlf)
    return (h < qh) | ((h == qh) & tie)


def key_cmp_at(hi_ref, lo_ref, idx, qh, qlf, strict: bool):
    """Scalar bool: key[idx] < q (``strict``) or key[idx] <= q."""
    c = key_cmp(_row(hi_ref, idx), _row(lo_ref, idx) ^ SIGN, qh, qlf, strict)
    sel = (_lane() == (idx & np.int32(LANES - 1))) & c
    return jnp.max(jnp.where(sel, np.int32(1), np.int32(0))) > np.int32(0)


def last_in_span(hi_ref, lo_ref, lo, hi, max_len: int, qh, qlf,
                 strict: bool):
    """Largest index in [lo, hi) whose key is < q (``strict``) or <= q,
    else lo - 1; ``hi - lo <= max_len``. Over a sorted span this is the
    bisect answer, found a row at a time with no per-step probe."""
    n_rows = hi_ref.shape[0]
    lane = _lane()
    r0 = lo >> np.int32(7)
    none = lo - np.int32(1)
    best = jnp.full((1, LANES), none, jnp.int32)
    for k in range(n_span_rows(max_len)):
        r = r0 + np.int32(k)
        rr = jnp.minimum(r, np.int32(n_rows - 1))
        h = hi_ref[pl.ds(rr, 1), :]
        lf = lo_ref[pl.ds(rr, 1), :] ^ SIGN
        idx = r * np.int32(LANES) + lane
        ok = (idx >= lo) & (idx < hi) & key_cmp(h, lf, qh, qlf, strict)
        best = jnp.maximum(best, jnp.where(ok, idx, none))
    return jnp.max(best)


def f32_of_u32(bits):
    """float32 of a uint32 held as int32 bits, rounded once like a direct
    u32 -> f32 conversion (both 16-bit halves are exact in f32)."""
    hi16 = jax.lax.shift_right_logical(bits, np.int32(16)).astype(jnp.float32)
    lo16 = (bits & np.int32(0xFFFF)).astype(jnp.float32)
    return hi16 * np.float32(65536.0) + lo16
