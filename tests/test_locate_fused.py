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
import numpy as np
import pytest

import repro.core  # noqa: F401 — x64
import jax.numpy as jnp
from repro.core import ShardedUpLIF, UpLIF
from repro.core.uplif import UpLIFConfig
from repro.kernels import ops as kops
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


@settings(max_examples=4, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 1), assignment=st.sampled_from([
    ("spline", "binsearch", "fused"),
    ("fused", "binsearch", "fused"),
    ("binsearch", "spline", "spline"),
]))
def test_mixed_per_shard_strategy_equivalence(seed, assignment):
    """Per-shard dispatch (ISSUE 8): one wave may run DIFFERENT locate
    strategies on different shards — a per-query strategy mask partitions
    the wave across at most three launches, each query taking (j, ins_cap)
    from its own shard's branch. Every visible result must match the
    uniform-strategy router on the same tape, shard-boundary keys and
    mid-tape strategy flips included. radix_bits=14 keeps the fused guard
    open at S=3, so the fused shards run the kernels."""
    base, vals, ops_tape, probes, ranges = _tape(seed)
    cfg = UpLIFConfig(locate="spline", batch_bucket=256, radix_bits=14)
    ref = ShardedUpLIF(base, vals, cfg, n_shards=3)
    mixed = ShardedUpLIF(base, vals, cfg, n_shards=3)
    for s, strat in enumerate(assignment):
        mixed.set_shard_locate(s, strat)
    assert mixed.shard_locate() == assignment
    b = ref.boundaries.astype(np.int64)
    probes_b = np.concatenate([probes, b, b - 1, b + 1])
    r_ref = _run_tape(ref, ops_tape, probes_b, ranges)
    r_mix = _run_tape(mixed, ops_tape, probes_b, ranges)
    _assert_identical(f"mixed{assignment}", r_ref, r_mix)
    assert ref.size == mixed.size
    assert mixed.shard_locate() == assignment
    # a controller flip mid-stream must not disturb state or results
    mixed.set_shard_locate(1, "fused")
    assert mixed.shard_locate()[1] == "fused"
    fa, va = ref.lookup(probes_b)
    fb, vb = mixed.lookup(probes_b)
    np.testing.assert_array_equal(fa, fb)
    np.testing.assert_array_equal(va, vb)


def test_mixed_codes_reuse_jit_variants():
    """Jit-cache flatness (§7.5 shapes discipline): the per-shard strategy
    CODES are a traced argument — repinning shards within the same
    distinct-strategy set changes code values, never the trace. Only the
    sorted deduplicated strategy tuple is static, so controller flips stay
    inside the warmed pow2 variant family instead of growing the cache per
    assignment permutation."""
    from repro.core import fops

    keys = make_keys(900, 3, hi=KEY_HI)
    cfg = UpLIFConfig(locate="spline", batch_bucket=256)
    idx = ShardedUpLIF(keys, keys + 1, cfg, n_shards=3)
    idx.set_shard_locate(0, "binsearch")  # distinct set {binsearch, spline}
    q = keys[:100]
    idx.lookup(q)   # warm the mixed variant at this pow2 pad width
    idx.delete(keys[:0])
    n0 = fops.slookup._cache_size()
    nd = fops.sdelete._cache_size()
    # permute the assignment inside the same distinct set: same static
    # tuple, same shapes, different code values -> the warmed variants
    # must serve every one of them
    for flip in ((0, "spline", 1, "binsearch"), (1, "spline", 2, "binsearch")):
        idx.set_shard_locate(flip[0], flip[1])
        idx.set_shard_locate(flip[2], flip[3])
        idx.lookup(q)
        idx.delete(keys[:0])
    assert fops.slookup._cache_size() == n0
    assert fops.sdelete._cache_size() == nd


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
    assert "fused" not in idx.locate_options()
    assert not idx.set_shard_locate(1, "fused")
    f, v = idx.lookup(keys[:50])
    assert f.all() and np.array_equal(v, keys[:50] + 1)
    st = idx.state
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
