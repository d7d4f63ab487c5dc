"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import pytest

import repro.core  # noqa: F401 — x64
import jax.numpy as jnp
from repro.core.radix_spline import build_radix_spline, rs_predict
from repro.kernels import ops, ref
from repro.kernels.gmm_estep import gmm_estep_pallas
from repro.kernels.tile_search import Q_BLK as TS_QBLK, TILE, tile_search_pallas
from tests.conftest import make_keys


@pytest.mark.parametrize("n_keys", [1_000, 50_000, 200_000])
@pytest.mark.parametrize("q", [512, 4096])
def test_spline_lookup_sweep(n_keys, q):
    keys = make_keys(n_keys, n_keys)
    pos = np.arange(len(keys), dtype=np.int64) * 2
    model, static = build_radix_spline(keys, pos, max_error=24)
    r = np.random.default_rng(q)
    queries = jnp.asarray(
        np.concatenate([r.choice(keys, q // 2),
                        r.integers(0, 1 << 48, q - q // 2)]).astype(np.int64)
    )
    out = np.asarray(
        ops.spline_lookup(model.table, model.spline_keys, model.spline_pos,
                          int(model.shift), queries, static.n_search_iters)
    )
    gold = np.asarray(rs_predict(model, static, queries))
    # float32 kernel vs float64 oracle: positions < 2^24 are near-exact
    assert np.abs(out - gold).max() < 1.0
    # parity with the decomposed-key jnp ref (same f32 math)
    sk_hi, sk_lo = ops.split_key(model.spline_keys)
    qh, ql = ops.split_key(queries)
    qh2, _ = ops._pad_to(qh, 1024, 0)
    ql2, _ = ops._pad_to(ql, 1024, 0)
    r_ = ref.spline_lookup_ref(
        model.table, sk_hi, sk_lo, model.spline_pos.astype(jnp.float32),
        qh2, ql2, int(model.shift), static.n_search_iters,
    )[: len(out)]
    # kernel computes dk from (hi,lo) split f32 arithmetic (two roundings) vs
    # the ref's single int64->f32 rounding: agreement to ~1 ulp of position
    np.testing.assert_allclose(out, np.asarray(r_), rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("n_slots", [10_000, 300_000])
def test_tile_search_routing(n_slots):
    r = np.random.default_rng(n_slots)
    slots = np.sort(r.integers(0, 1 << 48, n_slots).astype(np.int64))
    q = r.integers(0, 1 << 48, 2048).astype(np.int64)
    pred = np.searchsorted(slots, q).astype(np.float32)
    j, ok = ops.route_and_search(
        jnp.asarray(slots), jnp.asarray(q), jnp.asarray(pred)
    )
    j, ok = np.asarray(j), np.asarray(ok)
    gt = np.searchsorted(slots, q, side="right") - 1
    assert ok.all()
    assert np.array_equal(j, gt)


def test_tile_search_kernel_vs_ref():
    r = np.random.default_rng(5)
    tiles = np.sort(
        r.integers(0, 1 << 48, (4, TILE)).astype(np.int64), axis=1
    )
    q = r.integers(0, 1 << 48, (4, TS_QBLK)).astype(np.int64)
    th, tl = ops.split_key(jnp.asarray(tiles))
    qh, ql = ops.split_key(jnp.asarray(q))
    out = np.asarray(tile_search_pallas(th, tl, qh, ql, interpret=True))
    for t in range(4):
        gold = np.asarray(
            ref.tile_search_ref(th[t], tl[t], qh[t], ql[t])
        )
        assert np.array_equal(out[t], gold)


@pytest.mark.parametrize("cap", [4096, 65536])
@pytest.mark.parametrize("fanout", [8, 16, 64])
def test_bmat_rank_kernel(cap, fanout):
    r = np.random.default_rng(cap + fanout)
    n = cap // 2
    arr = np.full(cap, np.iinfo(np.int64).max, np.int64)
    arr[:n] = np.sort(r.integers(0, 1 << 48, n).astype(np.int64))
    fences = np.concatenate([arr[::fanout], [np.iinfo(np.int64).max]])
    q = r.integers(0, 1 << 48, 2048).astype(np.int64)
    got = np.asarray(
        ops.bmat_rank(jnp.asarray(arr), jnp.asarray(fences), jnp.asarray(q), fanout)
    )
    assert np.array_equal(got, np.searchsorted(arr, q, "left"))


@pytest.mark.parametrize("n", [100, 2048, 5000])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_gmm_estep_sweep(n, k):
    r = np.random.default_rng(n * k)
    x = jnp.asarray(r.normal(0, 5, n))
    w = jnp.asarray(np.full(k, 1.0 / k))
    mu = jnp.asarray(np.linspace(-4, 4, k))
    sd = jnp.asarray(r.uniform(0.5, 2.0, k))
    got = np.asarray(ops.gmm_estep(x, w, mu, sd))
    gold = np.asarray(
        ref.gmm_estep_ref(
            x.astype(jnp.float32), w.astype(jnp.float32),
            mu.astype(jnp.float32), sd.astype(jnp.float32),
        )
    )
    np.testing.assert_allclose(got, gold, atol=1e-5)
    np.testing.assert_allclose(got.sum(1), 1.0, atol=1e-5)


def test_bmat_rank_big_buffer_tiled():
    """Above MAX_VMEM_KEYS the rank wrapper must use the two-level
    tile_search composition (bounded memory, on-device) and stay exact —
    including under heavily duplicated query batches that overflow a
    tile's per-pass block capacity."""
    r = np.random.default_rng(11)
    cap = ops.MAX_VMEM_KEYS * 2
    n = cap - 777
    arr = np.full(cap, np.iinfo(np.int64).max, np.int64)
    arr[:n] = np.sort(r.integers(0, 1 << 52, n).astype(np.int64))
    fences = np.concatenate([arr[::16], [np.iinfo(np.int64).max]])
    q = np.concatenate([
        r.integers(0, 1 << 52, 1024),
        r.choice(arr[:n], 512),
        np.full(TS_QBLK + 100, arr[5]),  # one tile, > one pass
        [0, 1, arr[0], arr[n - 1], 1 << 52],
    ]).astype(np.int64)
    got = np.asarray(
        ops.bmat_rank(jnp.asarray(arr), jnp.asarray(fences), jnp.asarray(q), 16)
    )
    assert np.array_equal(got, np.searchsorted(arr, q, "left"))


@pytest.mark.parametrize("n_shards", [1, 4])
def test_bmat_rank_offset_kernel(n_shards):
    """Offset-aware rank kernel vs per-shard searchsorted."""
    r = np.random.default_rng(21 + n_shards)
    cap, fanout = 2048, 16
    keys = np.full((n_shards, cap), np.iinfo(np.int64).max, np.int64)
    for s in range(n_shards):
        m = cap // 2 + 37 * s
        keys[s, :m] = np.sort(r.integers(0, 1 << 48, m).astype(np.int64))
    fences = np.concatenate(
        [keys[:, ::fanout], np.full((n_shards, 1), np.iinfo(np.int64).max,
                                    np.int64)], axis=1
    )
    q = r.integers(0, 1 << 48, 1024).astype(np.int64)
    sid = r.integers(0, n_shards, 1024).astype(np.int64)
    got = np.asarray(ops.bmat_rank_fused(
        jnp.asarray(keys.reshape(-1)), jnp.asarray(fences.reshape(-1)),
        jnp.asarray(q), jnp.asarray(sid),
        cap=cap, nf=fences.shape[1], fanout=fanout,
    ))
    gold = np.asarray(
        [np.searchsorted(keys[s], k, "left") for s, k in zip(sid, q)]
    )
    assert np.array_equal(got, gold)


@pytest.mark.parametrize("n_shards", [1, 3])
def test_fused_locate_kernel_vs_fops(n_shards):
    """The fused locate adapter must return the same (j, icap) as the jnp
    spline locate it replaces, single-shard and stacked."""
    from repro.core import fops
    from repro.core.state import UpLIFStatic
    from repro.core.uplif import UpLIF, UpLIFConfig
    from repro.core.sharded import ShardedUpLIF

    keys = make_keys(4000, 31 + n_shards, hi=1 << 44)
    r = np.random.default_rng(5)
    q = np.concatenate([
        r.choice(keys, 800), r.integers(0, 1 << 44, 200)
    ]).astype(np.int64)
    if n_shards == 1:
        idx = UpLIF(keys, keys + 1, UpLIFConfig(locate="spline"))
        st_sp = idx.fstatic()
        st_fu = st_sp._replace(locate="fused")
        jq = jnp.asarray(q)
        j0, c0 = fops._locate(st_sp, idx.slots.keys, idx.rs_model, jq)
        j1, c1 = fops._locate(st_fu, idx.slots.keys, idx.rs_model, jq)
    else:
        # radix_bits=14 keeps the stacked radix tables inside the fused
        # guard (S * n_table <= MAX_VMEM_KEYS), so the stacked kernel runs
        idx = ShardedUpLIF(
            keys, keys + 1, UpLIFConfig(locate="spline", radix_bits=14),
            n_shards=n_shards,
        )
        assert fops.fused_fits(
            idx.state.slots.keys, idx.state.model, idx.state.bmat
        )
        st_sp = idx._static()
        st_fu = st_sp._replace(locate="fused")
        jq = jnp.asarray(q)
        sid = jnp.asarray(np.searchsorted(idx.boundaries, q, "right"))
        j0, c0 = fops._locate_stacked(
            st_sp, idx.state.slots.keys, idx.state.model, jq, sid
        )
        j1, c1 = fops._locate_stacked(
            st_fu, idx.state.slots.keys, idx.state.model, jq, sid
        )
    # j is exact in both paths whenever the span covers the truth — which
    # the drift-proof 3-row construction guarantees for this workload
    assert np.array_equal(np.asarray(j0), np.asarray(j1))
    # icap may differ only when f32 interpolation rounds the predicted slot
    # across a row edge: by at most one W-row
    W = st_sp.window
    assert np.abs(np.asarray(c0) - np.asarray(c1)).max() <= W


def test_split_key_roundtrip_order():
    r = np.random.default_rng(77)
    a = jnp.asarray(np.sort(r.integers(0, 1 << 52, 1000).astype(np.int64)))
    hi, lo = ops.split_key(a)
    back = (np.asarray(hi).astype(np.int64) << 32) | np.asarray(lo).astype(
        np.int64
    )
    assert np.array_equal(back, np.asarray(a))
