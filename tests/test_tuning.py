"""Online self-tuning subsystem (ISSUE 2): forecaster accuracy, scheduler
invariants (maintenance never alters lookup results), controller action
masking on sharded state, and the structural router entry points."""
import numpy as np
import pytest

import repro.core  # noqa: F401 — x64
import jax.numpy as jnp
from repro.core import ShardedUpLIF
from repro.core.gmm import e_step, gmm_cdf, gmm_cdf_np, init_gmm_uniform
from repro.core.uplif import UpLIFConfig
from repro import obs
from repro.kernels import ops as kops, ref as kref
from repro.kernels.gmm_estep import N_BLK as GMM_N_BLK, gmm_estep_pallas
from repro.serve import GatewayConfig, RequestGateway
from repro.tuning import (
    ACTIONS,
    A_KEEP,
    A_MERGE_SHARDS,
    A_RETRAIN_SHARD,
    A_SPLIT_SHARD,
    A_SWITCH_BMAT,
    ControllerConfig,
    ForecastConfig,
    SchedulerConfig,
    SelfTuner,
    ShardTuningController,
    Telemetry,
    TunerConfig,
    UpdateForecaster,
)
from tests.conftest import make_keys

CFG = UpLIFConfig(batch_bucket=256)


def _router(n=20_000, seed=7, shards=4):
    keys = make_keys(n, seed)
    return keys, ShardedUpLIF(keys, keys * 2, CFG, n_shards=shards)


# ---------------------------------------------------------------------------
# forecaster
# ---------------------------------------------------------------------------


def test_forecaster_tracks_shifted_mass():
    """Stream keys whose distribution shifts mid-run; the forecast per-shard
    mass must converge to the empirical histogram of the NEW regime."""
    rng = np.random.default_rng(0)
    boundaries = np.array([250_000, 500_000, 750_000], dtype=np.int64)
    fc = UpdateForecaster(0, 1_000_000, ForecastConfig(seed=0))
    # phase 1: uniform over the whole domain
    for _ in range(20):
        fc.observe(rng.integers(0, 1_000_000, 1024).astype(np.int64))
    mass_uniform = fc.shard_mass(boundaries)
    assert np.all(np.abs(mass_uniform - 0.25) < 0.1)
    # phase 2: everything lands in the top shard
    shifted = lambda: rng.integers(800_000, 1_000_000, 1024).astype(np.int64)
    for _ in range(20):
        fc.observe(shifted())
    mass = fc.shard_mass(boundaries)
    sample = np.concatenate([shifted() for _ in range(8)])
    emp = np.bincount(
        np.searchsorted(boundaries, sample, side="right"), minlength=4
    ) / len(sample)
    assert fc.hottest_shard(boundaries) == 3
    assert np.abs(mass - emp).sum() < 0.25  # L1 distance to the empirical
    assert fc.imbalance(boundaries) > 2.0   # split/rebalance trigger fires


def test_forecaster_pallas_estep_matches_oracle():
    """The Pallas E-step path (explicitly enabled; interpret mode on CPU)
    must produce the same responsibilities as the pure-JAX oracle."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1 << 40, 512).astype(np.int64)
    fc = UpdateForecaster(0, float(1 << 40), ForecastConfig(use_pallas=True))
    resp_k = fc._responsibilities(x.astype(np.float64))
    assert fc.cfg.use_pallas, "pallas path must not have silently degraded"
    oracle, _ = e_step(fc.gmm, jnp.asarray(x, dtype=jnp.float64))
    np.testing.assert_allclose(resp_k, np.asarray(oracle), atol=2e-3)


def _estep_eager(x, weights, means, stds):
    """The E-step dispatch as it stood before the padded program: eager
    casts, a device-side pad to a multiple of the block, an eager slice."""
    x32 = x.astype(jnp.float32)
    n = x32.shape[0]
    m = -(-n // GMM_N_BLK) * GMM_N_BLK
    if m != n:
        x32 = jnp.concatenate([x32, jnp.full((m - n,), 0.0, x32.dtype)])
    out = gmm_estep_pallas(
        x32, weights.astype(jnp.float32), means.astype(jnp.float32),
        stds.astype(jnp.float32), interpret=True,
    )
    return out[:n]


class _EagerForecaster(UpdateForecaster):
    """The forecaster's Pallas branch as it stood before the padded
    program, over ``_estep_eager``."""

    def _responsibilities(self, x):
        xs = jnp.asarray((x - self.lo) / self.span)
        ms = (self.gmm.means - self.lo) / self.span
        ss = jnp.maximum(self.gmm.stds / self.span, 1e-6)
        return np.asarray(_estep_eager(xs, self.gmm.weights, ms, ss),
                          dtype=np.float64)


def _estep_args(n, k=4, seed=0):
    r = np.random.default_rng(seed + n)
    return (jnp.asarray(r.normal(0, 5, n)),
            jnp.asarray(r.dirichlet(np.ones(k))),
            jnp.asarray(np.linspace(-4, 4, k)),
            jnp.asarray(r.uniform(0.5, 2.0, k)))


@pytest.mark.parametrize("n", [1, 700, 1013, 1024, 2047, 2049, 8192])
def test_gmm_estep_padded_matches_eager_dispatch(n):
    """Host padding and one program per width give the eager dispatch's
    numbers bit for bit, and the oracle's at the sweep's tolerance."""
    x, w, mu, sd = _estep_args(n)
    got = kops.gmm_estep(x, w, mu, sd)
    assert got.shape == (n, 4) and got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(_estep_eager(x, w, mu, sd)))
    f32 = lambda a: a.astype(jnp.float32)
    gold = np.asarray(kref.gmm_estep_ref(f32(x), f32(w), f32(mu), f32(sd)))
    np.testing.assert_allclose(got, gold, atol=1e-5)


def _compiles():
    return sum(v for k, v in obs.RECORDER.snapshot()["counters"].items()
               if k.startswith("compiles."))


def test_gmm_estep_compiles_once_per_width():
    """Batch lengths within one power-of-two width reuse one program; the
    widths 4096 and 8192 add one program each."""
    _, w, mu, sd = _estep_args(1)
    x = np.random.default_rng(1).normal(0, 5, 8192)
    kops.gmm_estep(x[:1000], w, mu, sd)
    size, compiles = kops._gmm_estep_padded._cache_size(), _compiles()
    for n in np.random.default_rng(2).choice(np.arange(1, 2049), 50,
                                             replace=False):
        kops.gmm_estep(x[:n], w, mu, sd)
    assert kops._gmm_estep_padded._cache_size() == size
    assert _compiles() == compiles
    for n in (2049, 3000, 4096, 4097, 6000, 8192):
        kops.gmm_estep(x[:n], w, mu, sd)
    assert kops._gmm_estep_padded._cache_size() <= size + 2


def test_forecaster_padded_estep_matches_eager_stream():
    """A stream of batches of changing lengths ends in the eager dispatch's
    mixture and drift; the padding counters reach the gateway's stats."""
    rng = np.random.default_rng(4)
    cfg = ForecastConfig(use_pallas=True, max_batch=4096, seed=3)
    lo, hi = float(1 << 40), float(1 << 41)
    new = UpdateForecaster(lo, hi, cfg)
    old = _EagerForecaster(lo, hi, cfg)
    keys0 = obs.RECORDER.counter("forecast.estep.keys")
    lanes0 = obs.RECORDER.counter("forecast.estep.lanes")
    for i, n in enumerate((300, 1013, 700, 2048, 1500, 5000, 64, 999)):
        centre = lo + (0.2 + 0.07 * i) * (hi - lo)
        x = (centre + rng.normal(0, 2e9, n)).astype(np.int64)
        new.observe(x)
        old.observe(x)
    for a, b in ((new.gmm.weights, old.gmm.weights),
                 (new.gmm.means, old.gmm.means),
                 (new.gmm.stds, old.gmm.stds)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)
    np.testing.assert_allclose(new.drift_ewma, old.drift_ewma, rtol=1e-12)
    assert new.drift_ewma > 0
    sent = 300 + 1013 + 700 + 2048 + 1500 + 4096 + 64 + 999
    assert obs.RECORDER.counter("forecast.estep.keys") - keys0 == sent
    assert (obs.RECORDER.counter("forecast.estep.lanes") - lanes0
            == 7 * 2048 + 4096)

    keys = make_keys(4096, 11)
    idx = ShardedUpLIF(keys, keys * 2, CFG, n_shards=2)
    tuner = SelfTuner(TunerConfig(forecast=cfg)).attach(idx)
    gw = RequestGateway(
        idx, tuner=tuner, config=GatewayConfig(max_batch=64, max_delay_s=0.001)
    )
    try:
        for f in [gw.submit_insert(int(k) + 1, 5) for k in keys[:100:2]]:
            f.result(30.0)
    finally:
        gw.close()
        tuner.close()
    counters = gw.stats()["obs"]["counters"]
    assert counters["forecast.estep.keys"] - keys0 >= sent + 50
    assert counters["forecast.estep.lanes"] > counters["forecast.estep.keys"]


def test_forecaster_gap_sizes_follow_forecast():
    """Eq. 6 via the forecast: gaps concentrate where the predicted insert
    mass is."""
    rng = np.random.default_rng(1)
    fc = UpdateForecaster(0, 100_000, ForecastConfig(seed=1))
    for _ in range(10):
        fc.observe(rng.normal(80_000, 3_000, 1024).astype(np.int64))
    keys = np.arange(0, 100_000, 50, dtype=np.int64)
    g = fc.gap_sizes(keys, alpha_target=1.0, d_max=16)
    lo_half = g[: len(g) // 2].sum()
    hi_half = g[len(g) // 2 :].sum()
    assert hi_half > 3 * max(lo_half, 1)


def test_gmm_cdf_np_matches_jit():
    g = init_gmm_uniform(0.0, 1e6, 4)
    x = np.linspace(-1e5, 1.2e6, 257)
    np.testing.assert_allclose(
        gmm_cdf_np(g, x), np.asarray(gmm_cdf(g, jnp.asarray(x))), atol=1e-12
    )


# ---------------------------------------------------------------------------
# structural entry points + scheduler invariant: maintenance never alters
# lookup results
# ---------------------------------------------------------------------------


def _assert_same_view(idx, probe, want_found, want_vals, ctx):
    f, v = idx.lookup(probe)
    assert np.array_equal(f, want_found), ctx
    assert np.array_equal(v[want_found], want_vals[want_found]), ctx


def test_maintenance_actions_preserve_lookups():
    """Index equivalence before/after EVERY maintenance action the
    controller can take (the scheduler's core guarantee)."""
    keys, idx = _router()
    rng = np.random.default_rng(8)
    new = np.setdiff1d(rng.integers(0, 1 << 48, 6000).astype(np.int64), keys)
    idx.insert(new, new + 1)
    idx.delete(keys[:500])
    probe = np.concatenate(
        [keys[:2000], new[:2000], rng.integers(0, 1 << 48, 500)]
    )
    f0, v0 = idx.lookup(probe)

    steps = [
        ("retrain_shard", lambda: idx.retrain_shard(int(np.argmax(
            np.asarray(idx.state.bmat.size))))),
        ("split", lambda: idx.split_shard(1)),
        ("merge", lambda: idx.merge_shards(0)),
        ("switch_bmat", idx.switch_bmat_type),
        ("presize", lambda: idx.presize_bmat(
            2 * int(idx.state.bmat.keys.shape[1]))),
        ("retrain_full", idx.retrain_full),
    ]
    for name, step in steps:
        step()
        _assert_same_view(idx, probe, f0, v0, name)
        # range queries agree too (maintenance must not break range order)
        ks, _ = idx.range_query(int(keys[100]), int(keys[300]), max_out=512)
        assert np.all(np.diff(ks) > 0)


def test_split_merge_roundtrip_counts():
    keys, idx = _router(shards=2)
    size0, n0 = idx.size, idx.n_shards
    assert idx.split_shard(0)
    assert idx.n_shards == n0 + 1 and len(idx.boundaries) == n0
    assert idx.size == size0
    assert idx.merge_shards(0)
    assert idx.n_shards == n0 and idx.size == size0
    # degenerate guards
    assert not idx.merge_shards(idx.n_shards - 1)  # no right neighbor
    one = ShardedUpLIF(keys[:10], keys[:10], CFG, n_shards=1)
    assert not one.merge_shards(0)


def test_scheduler_closed_loop_preserves_semantics():
    """Drive the full SelfTuner loop on a shifted stream; whatever actions
    it takes, the stored mapping stays exact and stats stay consistent."""
    keys, idx = _router(n=30_000, seed=9)
    tuner = SelfTuner(
        TunerConfig(
            controller=ControllerConfig(seed=0, min_split_keys=2048,
                                        merge_max_keys=2048),
            forecast=ForecastConfig(min_obs=128, seed=0),
            scheduler=SchedulerConfig(decide_every=2),
        )
    ).attach(idx)
    rng = np.random.default_rng(5)
    base = int(keys.max())
    inserted = []
    for wave in range(14):
        ins = np.unique(
            (base + rng.integers(1, 1 << 30, 512)).astype(np.int64)
        )
        idx.insert(ins, ins + 1)
        inserted.append(ins)
        idx.lookup(rng.choice(keys, 512))
        tuner.observe_inserts(ins)
        tuner.after_wave(1024, 0.01)
    all_ins = np.unique(np.concatenate(inserted))
    f, v = idx.lookup(all_ins)
    assert f.all() and np.array_equal(v, all_ins + 1)
    f, v = idx.lookup(keys)
    assert f.all() and np.array_equal(v, keys * 2)
    st = tuner.stats()
    assert st["waves"] == 14 and st["forecast_obs"] > 0


# ---------------------------------------------------------------------------
# controller: action masking on sharded state
# ---------------------------------------------------------------------------


def _snapshot(idx):
    return Telemetry().snapshot(idx)


def test_controller_masks_follow_sharded_state():
    keys, idx = _router(n=20_000, shards=4)
    ctl = ShardTuningController(
        ControllerConfig(max_shards=4, min_split_keys=1000,
                         merge_max_keys=100)
    )
    snap = _snapshot(idx)
    s = 0
    mask = ctl.action_mask(snap, s)
    assert mask[A_KEEP] and mask[A_SWITCH_BMAT]
    assert not mask[A_RETRAIN_SHARD]      # empty delta buffer
    assert not mask[A_SPLIT_SHARD]        # already at max_shards
    assert not mask[A_MERGE_SHARDS]       # pairs all above merge_max_keys

    # fill a buffer -> retrain unlocks; raise limits -> split/merge unlock
    rng = np.random.default_rng(2)
    new = np.setdiff1d(rng.integers(0, 1 << 48, 4000).astype(np.int64), keys)
    idx.insert(new, new)
    snap = _snapshot(idx)
    hot = int(np.argmax(snap.bmat_size))
    ctl2 = ShardTuningController(
        ControllerConfig(max_shards=16, min_split_keys=1000,
                         merge_max_keys=1 << 40)
    )
    mask2 = ctl2.action_mask(snap, hot)
    assert mask2[A_RETRAIN_SHARD] and mask2[A_SPLIT_SHARD]
    assert mask2[A_MERGE_SHARDS]

    # a tiny shard never splits
    small = ShardedUpLIF(keys[:64], keys[:64], CFG, n_shards=2)
    snap_s = _snapshot(small)
    assert not ctl2.action_mask(snap_s, 0)[A_SPLIT_SHARD]

    # single shard: merge impossible
    one = ShardedUpLIF(keys, keys, CFG, n_shards=1)
    assert not ctl2.action_mask(_snapshot(one), 0)[A_MERGE_SHARDS]


def test_controller_choose_respects_mask():
    ctl = ShardTuningController(ControllerConfig(epsilon=1.0, seed=3))
    mask = np.zeros(len(ACTIONS), dtype=bool)
    mask[[A_KEEP, A_SWITCH_BMAT]] = True
    for _ in range(50):  # epsilon=1: pure exploration, masked draws only
        a = ctl.choose((0,) * 7, mask)
        assert mask[a]
    # exploit mode on an unseen state without heuristic context -> KEEP
    assert ctl.choose((9,) * 7, mask, explore=False) == A_KEEP
    # learned values dominate, but never through the mask
    row = ctl._q_row((1,) * 7)
    row[A_RETRAIN_SHARD] = 5.0
    row[A_SWITCH_BMAT] = 1.0
    assert ctl.choose((1,) * 7, mask, explore=False) == A_SWITCH_BMAT


def test_controller_learning_updates_q():
    ctl = ShardTuningController(ControllerConfig(seed=0))
    s0, s1 = (0,) * 7, (1,) * 7
    mask = np.ones(len(ACTIONS), dtype=bool)
    ctl._q_row(s1)[A_KEEP] = 2.0
    ctl.update(s0, A_RETRAIN_SHARD, 1.0, s1, mask)
    cfg = ctl.cfg
    want = cfg.alpha * (1.0 + cfg.gamma * 2.0)
    assert abs(ctl.q[s0][A_RETRAIN_SHARD] - want) < 1e-9


def test_import_q_keeps_columns_of_a_wider_stored_row():
    """A Q-table saved while a sixth action existed (the last column)
    imports as the five actions there are, each column still the value
    of the action with that index; a narrower row zero-pads."""
    ctl = ShardTuningController()
    wide = [0.5, 1.0, 2.0, 3.0, 4.0, 9.0]
    ctl.import_q({"1,2,3,0,0,1,2": wide, "0,0,0,0,0,0,0": [1.5, 2.5]})
    row = ctl.q[(1, 2, 3, 0, 0, 1, 2)]
    assert len(ACTIONS) == 5 and row.shape == (5,)
    for a, want in ((A_KEEP, 0.5), (A_RETRAIN_SHARD, 1.0),
                    (A_SWITCH_BMAT, 2.0), (A_SPLIT_SHARD, 3.0),
                    (A_MERGE_SHARDS, 4.0)):
        assert row[a] == want
    np.testing.assert_array_equal(ctl.q[(0,) * 7], [1.5, 2.5, 0, 0, 0])


def test_telemetry_signals_match_measures():
    keys, idx = _router(n=16_000, shards=4)
    rng = np.random.default_rng(4)
    new = np.setdiff1d(rng.integers(0, 1 << 48, 3000).astype(np.int64), keys)
    idx.insert(new, new)
    tel = Telemetry()
    tel.observe_wave(1000, 0.5)
    snap = tel.snapshot(idx)
    m = idx.measures()
    assert snap.n_shards == idx.n_shards
    assert int(snap.bmat_size.sum()) == m["bmat_size"]
    assert int(snap.n_keys.sum()) == m["n_keys"]
    assert int(snap.bmat_height.max()) == m["bmat_height"]
    assert snap.throughput_ewma == pytest.approx(2000.0)
    sm = snap.shard_measures(0)
    assert set(sm) >= {"bmat_height", "bmat_fill", "occupancy", "n_shards"}
