"""Strategy-equivalence property suite for the fused Pallas locate/rank path.

The three ``UpLIFStatic.locate`` strategies (binsearch / spline / fused) are
different SEARCH plans over the same index state, so every visible result
must coincide: lookups, delete hit masks, range extractions and the final
live contents are asserted byte-identical across strategies on the same op
tape — drift-heavy hotspot inserts, in-batch duplicate keys, value updates,
tombstone revivals and shard-boundary queries included. On CPU the fused
strategy runs the kernels in Pallas interpret mode, so this suite pins the
TPU hot path's semantics without TPU hardware.

What is deliberately NOT compared: insert overflow counts. The model-guided
strategies bound placement to their searched span (``ins_cap``), so a key
at the very edge of a span may overflow to the BMAT under one strategy and
sit in the slot array under another — visible results are identical either
way, which is exactly what these tests pin.

Strategies go through ``tests/_hypothesis_compat``: with hypothesis
installed each case explores random tapes; without it the deterministic
boundary grid runs the same oracles.
"""
import types

import numpy as np
import pytest

import repro.core  # noqa: F401 — x64
import jax.numpy as jnp
from repro.core import ShardedUpLIF, UpLIF
from repro.core.uplif import UpLIFConfig
from repro.kernels import ops as kops
from repro.tuning.controller import A_RETRAIN_SHARD
from repro.tuning.executor import build as build_plan
from tests._hypothesis_compat import HealthCheck, given, settings, st
from tests.conftest import make_keys

STRATEGIES = ("spline", "binsearch", "fused")
KEY_HI = 1 << 44


def _tape(seed: int):
    """One deterministic op tape: (base keys/vals, list of op batches)."""
    r = np.random.default_rng(seed)
    base = make_keys(1200, seed, hi=KEY_HI)
    vals = base * 3 + 1
    fresh = np.setdiff1d(
        r.integers(0, KEY_HI, 900).astype(np.int64), base
    )
    # drift-heavy hotspot: a narrow key range absorbing many inserts, the
    # regime where in-row drift approaches W-1 and the 3-row span matters
    lo_h, hi_h = int(base[200]), int(base[230])
    hot = r.integers(lo_h, hi_h + 1, 500).astype(np.int64)
    # in-batch duplicates (last-wins) + updates of existing keys
    dups = np.concatenate([hot[:60], hot[:60], base[100:160]])
    ops_tape = [
        ("insert", fresh, fresh + 11),
        ("insert", hot, hot + 13),
        ("delete", np.concatenate([base[150:260], fresh[:80], hot[:40]])),
        ("insert", dups, dups + 17),  # revives tombstones among hot[:40]
        ("insert", base[100:200], base[100:200] + 23),  # pure value updates
    ]
    probes = np.concatenate([
        base[::7], fresh[::5], hot[::3],
        r.integers(0, KEY_HI, 150).astype(np.int64),       # mostly misses
        np.asarray([0, 1, KEY_HI - 1], dtype=np.int64),
    ])
    ranges = [
        (int(base[40]), int(base[90])),
        (lo_h - 1, hi_h + 1),            # the drifted hotspot
        (0, int(base[5])),
    ]
    return base, vals, ops_tape, probes, ranges


def _run_tape(idx, ops_tape, probes, ranges):
    """Apply the tape, recording every visible result after every op."""
    out = []
    for op in ops_tape:
        if op[0] == "insert":
            idx.insert(op[1], op[2])
        else:
            out.append(("delete_hits", idx.delete(op[1])))
        f, v = idx.lookup(probes)
        out.append(("lookup", f, v))
    for lo, hi in ranges:
        ks, vs = idx.range_query(lo, hi, max_out=256)
        out.append(("range", ks, vs))
    return out


def _assert_identical(name, a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[0] == rb[0]
        for xa, xb in zip(ra[1:], rb[1:]):
            np.testing.assert_array_equal(xa, xb, err_msg=f"{name}/{ra[0]}")


@settings(max_examples=5, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 2), kind=st.sampled_from(["b+mat", "rbmat"]))
def test_single_shard_strategy_equivalence(seed, kind):
    base, vals, ops_tape, probes, ranges = _tape(seed)
    results = {}
    live = {}
    for strat in STRATEGIES:
        cfg = UpLIFConfig(locate=strat, bmat_type=kind)
        idx = UpLIF(base, vals, cfg)
        results[strat] = _run_tape(idx, ops_tape, probes, ranges)
        live[strat] = idx.extract_live()
    for strat in ("binsearch", "fused"):
        _assert_identical(strat, results["spline"], results[strat])
        np.testing.assert_array_equal(live["spline"][0], live[strat][0])
        np.testing.assert_array_equal(live["spline"][1], live[strat][1])


@settings(max_examples=4, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 2), n_shards=st.sampled_from([2, 3]))
def test_stacked_strategy_equivalence(seed, n_shards):
    """Sharded router: the fused kernels take per-query shard base offsets,
    so S shards run in one launch — results must still match the jnp
    strategies everywhere, INCLUDING on shard-boundary keys. radix_bits=14
    keeps the stacked radix tables inside the fused guard
    (S * n_table <= MAX_VMEM_KEYS), so the stacked kernels really run."""
    base, vals, ops_tape, probes, ranges = _tape(seed)
    results = {}
    for strat in STRATEGIES:
        cfg = UpLIFConfig(locate=strat, batch_bucket=256, radix_bits=14)
        idx = ShardedUpLIF(base, vals, cfg, n_shards=n_shards)
        assert idx.shard_locate() == (strat,) * n_shards
        # boundary queries: the first key of each shard and its neighbors
        # exercise the sid routing + per-query offset arithmetic edges
        b = idx.boundaries.astype(np.int64)
        probes_b = np.concatenate([probes, b, b - 1, b + 1])
        results[strat] = _run_tape(idx, ops_tape, probes_b, ranges)
        results[strat].append(("size", np.asarray([idx.size])))
        assert idx.shard_locate() == (strat,) * n_shards
    for strat in ("binsearch", "fused"):
        _assert_identical(strat, results["spline"], results[strat])


WRITE_PATHS = ("dups_revivals", "deletes", "bmat_growth", "commit_mid_drain",
               "split_merge")


class _Ref:
    """Sorted-dict model of the index: the answers every write path must
    leave behind, whatever strategy located the keys."""

    def __init__(self, keys, vals):
        self.d = dict(zip(keys.tolist(), vals.tolist()))

    def insert(self, keys, vals):
        self.d.update(zip(keys.tolist(), vals.tolist()))  # last one wins

    def delete(self, keys):
        hit = np.asarray([k in self.d for k in keys.tolist()])
        for k in keys.tolist():
            self.d.pop(k, None)
        return hit

    def lookup(self, keys):
        found = np.asarray([k in self.d for k in keys.tolist()])
        vals = np.asarray([self.d.get(k, 0) for k in keys.tolist()])
        return found, vals


def _drive_write_path(case, idx, ref, base, fresh):
    """Run one write path on ``idx`` and ``ref``; returns what the index
    answered on the way (delete hits, lookups mid-drain)."""
    out = []

    def insert(k, v, **kw):
        idx.insert(k, v, **kw)
        ref.insert(k, v)

    def delete(k):
        hit = idx.delete(k)
        np.testing.assert_array_equal(hit, ref.delete(k), err_msg=case)
        out.append(hit)

    if case == "dups_revivals":
        delete(base[100:160])
        dup = np.concatenate([base[100:130], fresh[:40]])
        insert(np.concatenate([dup, dup]), np.concatenate([dup + 5, dup + 6]))
        insert(fresh[:40], fresh[:40] + 7)       # pure value updates
    elif case == "deletes":
        delete(np.concatenate([base[::4], fresh[:20], base[:40:4]]))
        delete(base[::4])                        # all gone: no hits
        insert(base[:200:8], base[:200:8] + 9)   # revives some
    elif case == "bmat_growth":
        bcap = int(idx.state.bmat.keys.shape[1])
        insert(fresh[:200], fresh[:200] + 3, pad_to=bcap)
        assert int(idx.state.bmat.keys.shape[1]) > bcap
        lo, hi = int(base[300]), int(base[303])
        hot = np.arange(lo + 1, hi, max((hi - lo) // 120, 1))[:100]
        insert(hot, hot + 4)                     # a hotspot into the BMAT
    elif case == "commit_mid_drain":
        snap = idx.snapshot(shards=[0])
        insert(fresh[:150], fresh[:150] + 2)     # logged for the build
        delete(base[:60])
        plan = types.SimpleNamespace(action=A_RETRAIN_SHARD, shard=0,
                                     gmm=None)
        assert idx.commit(build_plan(plan, snap), replay_cap=8)
        assert idx.draining                      # parked: log above the cap
        insert(base[:30], base[:30] + 11)        # appends to the log
        out.append(idx.lookup(base[:120]))       # old rows still serve
        while idx.draining:
            idx.advance_drains(replay_cap=64)
        assert idx.n_commits == 1
    elif case == "split_merge":
        assert idx.split_shard(idx.n_shards - 1)
        insert(fresh[:100], fresh[:100] + 1)
        assert idx.merge_shards(0)
        delete(fresh[:100:3])
    else:
        raise ValueError(case)
    return out


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("case", WRITE_PATHS)
def test_fused_matches_spline_after_write_paths(case, n_shards):
    """Every write path leaves a state the fused kernels read exactly as
    the jnp spline path does: the kernels split the int64 keys they read
    inside each call, so no write path has a second copy of the keys to
    keep up to date. radix_bits=14 keeps the stacked state inside the
    fused guard, so every shard runs the kernels to the end."""
    base = make_keys(1200, 41, hi=KEY_HI)
    fresh = np.setdiff1d(make_keys(800, 42, hi=KEY_HI), base)
    answers, sizes = {}, {}
    for strat in ("fused", "spline"):
        cfg = UpLIFConfig(locate=strat, batch_bucket=256, radix_bits=14)
        idx = ShardedUpLIF(base, base * 3 + 1, cfg, n_shards=n_shards)
        ref = _Ref(base, base * 3 + 1)
        out = _drive_write_path(case, idx, ref, base, fresh)
        assert idx.shard_locate() == (strat,) * idx.n_shards
        b = idx.boundaries.astype(np.int64)
        probes = np.concatenate([base[::3], fresh[::2], b, b - 1, b + 1])
        f, v = idx.lookup(probes)
        wf, wv = ref.lookup(probes)
        np.testing.assert_array_equal(f, wf, err_msg=f"{case}/{strat}")
        np.testing.assert_array_equal(v, wv, err_msg=f"{case}/{strat}")
        ks, vs = idx.range_query(int(base[250]), int(base[420]), max_out=256)
        answers[strat] = out + [(f, v), (ks, vs)]
        sizes[strat] = idx.size
    assert sizes["fused"] == sizes["spline"]
    for a, b in zip(answers["fused"], answers["spline"]):
        for xa, xb in zip(a if isinstance(a, tuple) else (a,),
                          b if isinstance(b, tuple) else (b,)):
            np.testing.assert_array_equal(xa, xb, err_msg=case)


@pytest.mark.parametrize("case", [
    "fused_refused_at_build", "fused_refused_after_growth", "split", "merge",
    "bmat_growth",
])
def test_router_runs_one_strategy(case):
    """A router runs the one strategy it was built with on every shard,
    held to the fused guard at every shape change: fused where the guard
    refuses (the stacked radix tables of S = 2 at 2**16 buckets, or a
    BMAT grown past the rank kernel's VMEM budget) runs and reports spline
    on every shard, and a split, a merge or a BMAT growth keeps the
    strategy."""
    keys = make_keys(1500, 43, hi=KEY_HI)
    fresh = np.setdiff1d(make_keys(300, 44, hi=KEY_HI), keys)
    locate, n_shards, radix_bits, want = {
        "fused_refused_at_build": ("fused", 2, 16, "spline"),
        "fused_refused_after_growth": ("fused", 1, 14, "spline"),
        "split": ("binsearch", 2, 16, "binsearch"),
        "merge": ("binsearch", 3, 16, "binsearch"),
        "bmat_growth": ("binsearch", 2, 16, "binsearch"),
    }[case]
    cfg = UpLIFConfig(locate=locate, batch_bucket=256, radix_bits=radix_bits)
    idx = ShardedUpLIF(keys, keys + 1, cfg, n_shards=n_shards)
    if case == "fused_refused_after_growth":
        assert idx.shard_locate() == ("fused",)
        assert idx.presize_bmat(2 * kops.MAX_VMEM_KEYS)
    elif case == "split":
        assert idx.split_shard(0)
    elif case == "merge":
        assert idx.merge_shards(1)
    elif case == "bmat_growth":
        bcap = int(idx.state.bmat.keys.shape[1])
        idx._ensure_bmat_capacity(bcap)
        assert int(idx.state.bmat.keys.shape[1]) > bcap
    idx.insert(fresh, fresh + 1)
    assert idx.shard_locate() == (want,) * idx.n_shards
    assert idx._static().locate == want
    probes = np.concatenate([keys[::5], fresh])
    f, v = idx.lookup(probes)
    assert f.all() and np.array_equal(v, probes + 1)


def test_fused_locate_kernel_is_wired(monkeypatch):
    """The fused strategy must actually route through the Pallas adapters
    (a silent fall-through to the jnp path would pass the equivalence
    tests while leaving the kernels unwired)."""
    calls = {"locate": 0, "rank": 0}
    orig_locate = kops.fused_locate
    orig_rank = kops.bmat_rank_fused

    def spy_locate(*a, **k):
        calls["locate"] += 1
        return orig_locate(*a, **k)

    def spy_rank(*a, **k):
        calls["rank"] += 1
        return orig_rank(*a, **k)

    monkeypatch.setattr(kops, "fused_locate", spy_locate)
    monkeypatch.setattr(kops, "bmat_rank_fused", spy_rank)
    keys = make_keys(700, 99, hi=KEY_HI)
    # window=128 gives this test its own jit variants, so the traces (and
    # with them the spy calls) cannot be served from another test's cache
    idx = UpLIF(keys, keys + 1, UpLIFConfig(locate="fused", window=128))
    f, v = idx.lookup(keys[:300])
    assert f.all() and np.array_equal(v, keys[:300] + 1)
    assert calls["locate"] > 0 and calls["rank"] > 0


def test_small_shift_prefix_saturates():
    """Regression: with a small key domain the radix shift drops below 32
    and the kernel assembles the prefix from both (hi, lo) halves. A query
    key ABOVE the trained domain must saturate to the last bucket exactly
    like the jnp path's clip — an int32 wrap here silently mispredicted
    the bucket and force-routed every above-domain insert to the BMAT
    (diverging overflow counters, identical-looking lookups)."""
    r = np.random.default_rng(7)
    keys = np.unique(r.integers(1, 1 << 20, 3000).astype(np.int64))
    big = np.asarray(
        [1 << 36, (1 << 44) + 5, (1 << 31) + 3, (1 << 52) - 1],
        dtype=np.int64,
    )
    overflow = {}
    results = {}
    for strat in ("spline", "fused"):
        idx = UpLIF(keys, keys + 1, UpLIFConfig(locate=strat))
        assert int(idx.rs_model.shift) < 32  # the regime under test
        overflow[strat] = idx.insert(big, big + 1)
        f, v = idx.lookup(np.concatenate([big, keys[:50]]))
        results[strat] = (f, v, idx.n_overflow)
    assert overflow["fused"] == overflow["spline"]
    for a, b in zip(results["spline"], results["fused"]):
        np.testing.assert_array_equal(a, b)


def test_fused_guard_falls_back_cleanly():
    """Shapes outside the VMEM/precision guards resolve to the jnp spline
    path, and the router reports the strategy that runs: every shard of a
    router whose stacked radix tables exceed the guard (S = 2 at the
    default 2**16 buckets) says spline, and fops refuses a fused static at
    those shapes instead of silently running something else."""
    from repro.core import fops

    assert not kops.locate_fusable(kops.MAX_F32_POSITIONS + 1, 64, 64, 1)
    assert not kops.locate_fusable(1024, 64, 64,
                                   kops.MAX_VMEM_SLOTS // 1024 + 1)
    assert kops.locate_fusable(1024, 64, 64, 1)
    assert not kops.rank_fusable(kops.MAX_VMEM_KEYS + 1, 64)

    keys = make_keys(900, 5, hi=KEY_HI)
    idx = ShardedUpLIF(keys, keys + 1, UpLIFConfig(locate="fused"),
                       n_shards=2)
    assert idx.shard_locate() == ("spline", "spline")
    st = idx.state
    assert not fops.fused_fits(st.slots.keys, st.model, st.bmat)
    assert idx._static().locate == "spline"
    f, v = idx.lookup(keys[:50])
    assert f.all() and np.array_equal(v, keys[:50] + 1)
    with pytest.raises(ValueError, match="fused"):
        fops.slookup(st, jnp.asarray(keys[:256]), idx._jbounds,
                     static=idx._static()._replace(locate="fused"))


def test_auto_resolution():
    from repro.core.state import (
        LOCATE_FUSED,
        LOCATE_SPLINE,
        resolve_locate,
    )

    # auto is spline on every platform; fused is an explicit choice
    assert resolve_locate("auto") == LOCATE_SPLINE
    assert resolve_locate("fused") == LOCATE_FUSED
    # the shape guard wins over the request: fused never runs where the
    # kernels refuse the shapes
    assert resolve_locate("auto", fits=False) == LOCATE_SPLINE
    assert resolve_locate("fused", fits=False) == LOCATE_SPLINE
    assert resolve_locate("binsearch", fits=False) == "binsearch"
    with pytest.raises(ValueError):
        resolve_locate("nope")
    # config validation rejects unknown strategies up front
    with pytest.raises(AssertionError):
        UpLIFConfig(locate="nope")
