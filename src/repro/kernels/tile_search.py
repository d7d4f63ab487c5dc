"""Last-mile tile search kernel.

The aggregator routes each query (by its model prediction) to a 2048-slot
tile of the gapped array; queries are sorted by tile id on the host/XLA side
(sort-based gather — the TPU-native replacement for random HBM probes). Each
grid step loads one slot tile + its query block into VMEM and computes, per
query, the index of the last slot key <= q via broadcast-compare-reduce
(TILE x Q_BLK vector ops — no serial dependency, VPU-friendly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048   # slots per tile (hi/lo int32: 16KB per tile in VMEM)
Q_BLK = 512   # queries routed per tile (padded; KEY_MAX padding never hits)


def _kernel(tile_hi_ref, tile_lo_ref, q_hi_ref, q_lo_ref, out_ref):
    th = tile_hi_ref[0, :]
    tl = tile_lo_ref[0, :]
    qh = q_hi_ref[0, :]
    ql = q_lo_ref[0, :]
    leq = (th[None, :] < qh[:, None]) | (
        (th[None, :] == qh[:, None]) & (tl[None, :] <= ql[:, None])
    )
    # dtype pinned: with x64 enabled jnp.sum would promote int32 -> int64,
    # which the int32 output ref rejects
    out_ref[0, :] = jnp.sum(leq, axis=1, dtype=jnp.int32) - 1


def tile_search_pallas(
    tiles_hi, tiles_lo, q_hi, q_lo, *, interpret: bool
):
    """tiles_*: (n_tiles, TILE) slot keys; q_*: (n_tiles, Q_BLK) routed
    queries. Returns (n_tiles, Q_BLK) local indices (-1 if q below tile)."""
    n_tiles = tiles_hi.shape[0]
    assert tiles_hi.shape[1] == TILE and q_hi.shape[1] == Q_BLK
    tile_spec = pl.BlockSpec((1, TILE), lambda i: (i, 0))
    q_spec = pl.BlockSpec((1, Q_BLK), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles, Q_BLK), jnp.int32),
        grid=(n_tiles,),
        in_specs=[tile_spec, tile_spec, q_spec, q_spec],
        out_specs=q_spec,
        interpret=interpret,
    )(tiles_hi, tiles_lo, q_hi, q_lo)
