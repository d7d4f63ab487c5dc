"""Functional core (fops) + sharded router vs host oracles.

Covers the ISSUE-1 tentpole surface:
  * fops.lookup / insert / delete / range_scan agree with a dict/sorted-array
    oracle when driven directly (pure pytree in, pure pytree out);
  * ShardedUpLIF matches single-shard UpLIF on mixed workloads;
  * slot-array invariants survive the on-device grid-accept insert path;
  * PrefixCacheIndex honors capacity_hint and counts hits/misses
    consistently under eviction;
  * QLearningAgent.policy masks admin-disabled actions.
"""
import numpy as np
import pytest

import repro.core  # noqa: F401 — x64
import jax.numpy as jnp
from repro.core import ShardedUpLIF, UpLIF, fops
from repro.core.types import KEY_MAX
from repro.core.uplif import UpLIFConfig
from tests._hypothesis_compat import HealthCheck, given, settings, st
from tests.conftest import make_keys

CFG = UpLIFConfig(batch_bucket=256)


def _pad(arr, fill, n=256):
    m = max(n, 1 << max(int(len(arr) - 1).bit_length(), 0))
    out = np.full(m, fill, dtype=np.int64)
    out[: len(arr)] = arr
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# pure functional layer vs host oracle
# ---------------------------------------------------------------------------


def test_fops_lookup_insert_delete_oracle():
    keys = make_keys(6000, 101)
    idx = UpLIF(keys, keys * 2, CFG)
    oracle = {int(k): int(k) * 2 for k in keys}
    static = idx.fstatic()

    r = np.random.default_rng(102)
    new = np.setdiff1d(r.integers(0, 1 << 48, 3000).astype(np.int64), keys)
    state = idx.fstate
    idx._ensure_bmat_capacity(len(_pad(new, KEY_MAX)))
    state = idx.fstate
    state, res = fops.insert(
        state, _pad(new, KEY_MAX), _pad(new * 3, 0), static=static
    )
    for k in new.tolist():
        oracle[k] = k * 3

    q = np.concatenate([keys[:1000], new[:1000], r.integers(0, 1 << 48, 500)])
    qp = _pad(q, KEY_MAX)
    found, vals = fops.lookup(state, qp, static=static)
    found = np.asarray(found)[: len(q)]
    vals = np.asarray(vals)[: len(q)]
    want = np.asarray([k in oracle for k in q.tolist()])
    assert np.array_equal(found, want)
    assert np.array_equal(
        vals[found], np.asarray([oracle[int(k)] for k in q[want]])
    )

    dels = np.concatenate([keys[100:300], new[:200]])
    state, hit = fops.delete(state, _pad(dels, KEY_MAX), static=static)
    assert np.asarray(hit)[: len(dels)].all()
    for k in dels.tolist():
        oracle.pop(int(k))
    found, _ = fops.lookup(state, _pad(dels, KEY_MAX), static=static)
    assert not np.asarray(found)[: len(dels)].any()
    # counters track the oracle's live size exactly
    c = state.counters
    assert int(c.n_keys + c.n_bmat_live) == len(oracle)


def test_fops_range_scan_oracle():
    keys = make_keys(8000, 103)
    idx = UpLIF(keys, keys + 1, CFG)
    r = np.random.default_rng(104)
    new = np.setdiff1d(r.integers(0, 1 << 48, 4000).astype(np.int64), keys)
    idx.insert(new, new + 1)
    allk = np.sort(np.concatenate([keys, new]))
    static = idx.fstatic()
    state = idx.fstate

    los = np.sort(r.choice(allk, 8)).astype(np.int64)
    his = los + (1 << 44)
    res = fops.range_scan(
        state, _pad(los, KEY_MAX), _pad(his, 0), static=static, max_out=512
    )
    ks = np.asarray(res.keys)
    cn = np.asarray(res.count)
    for i, (lo, hi) in enumerate(zip(los, his)):
        want = allk[(allk >= lo) & (allk <= hi)][:512]
        got = ks[i, : cn[i]]
        assert np.array_equal(got, want)


def test_insert_preserves_slot_invariants():
    keys = make_keys(5000, 105)
    idx = UpLIF(keys, keys, CFG)
    r = np.random.default_rng(106)
    new = np.setdiff1d(r.integers(0, 1 << 48, 6000).astype(np.int64), keys)
    r.shuffle(new)
    idx.insert(new, new)
    idx.delete(keys[::7])
    assert idx.capacity % idx.cfg.window == 0, "W-aligned capacity"
    _assert_slot_invariants(np.asarray(idx.slots.keys),
                            np.asarray(idx.slots.occ))


def _assert_slot_invariants(sk, so):
    """Slot keys sorted, and an empty slot holds the key of the next
    occupied slot (fill-forward) or KEY_MAX."""
    assert np.all(np.diff(sk) >= 0), "slot keys must stay sorted"
    nxt = None
    for i in range(len(sk) - 1, -1, -1):
        if so[i]:
            nxt = sk[i]
        elif nxt is not None:
            assert sk[i] == nxt or sk[i] == KEY_MAX


# ---------------------------------------------------------------------------
# sharded router vs single shard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_matches_single_mixed_workload(n_shards):
    keys = make_keys(12000, 107)
    single = UpLIF(keys, keys * 2, CFG)
    shard = ShardedUpLIF(keys, keys * 2, CFG, n_shards=n_shards)
    r = np.random.default_rng(108)
    new = np.setdiff1d(r.integers(0, 1 << 48, 5000).astype(np.int64), keys)
    r.shuffle(new)
    assert shard.n_shards == n_shards

    single.insert(new, new * 2)
    shard.insert(new, new * 2)
    # adjusted rank against the exact oracle (pre-delete regime)
    allk = np.sort(np.concatenate([keys, new]))
    q0 = r.choice(allk, 400)
    assert np.array_equal(
        shard.adjusted_predict(q0), np.searchsorted(allk, q0, "left")
    )

    dels = np.concatenate([keys[1000:1200], new[:200]])
    h1, h2 = single.delete(dels), shard.delete(dels)
    assert np.array_equal(h1, h2) and h2.all()

    q = np.concatenate(
        [keys[:2000], new[200:1500], dels[:50],
         r.integers(0, 1 << 48, 1000).astype(np.int64)]
    )
    f1, v1 = single.lookup(q)
    f2, v2 = shard.lookup(q)
    assert np.array_equal(f1, f2)
    assert np.array_equal(v1[f1], v2[f2])
    assert single.size == shard.size

    los = np.sort(r.choice(keys, 8)).astype(np.int64)
    his = los + (1 << 45)  # wide ranges span shard boundaries
    k1, vv1 = single.range_query_batch(los, his, max_out=256)
    k2, vv2 = shard.range_query_batch(los, his, max_out=256)
    for a, b, va, vb in zip(k1, k2, vv1, vv2):
        assert np.array_equal(a, b)
        assert np.array_equal(va, vb)


def test_sharded_retrain_and_switch_preserve_content():
    keys = make_keys(8000, 109)
    shard = ShardedUpLIF(keys, keys + 7, CFG, n_shards=3)
    r = np.random.default_rng(110)
    new = np.setdiff1d(r.integers(0, 1 << 48, 4000).astype(np.int64), keys)
    shard.insert(new, new + 7)
    shard.delete(keys[:500])
    live = np.concatenate([keys[500:], new])
    shard.retrain_subset()
    shard.retrain_full()
    assert shard.measures()["bmat_size"] == 0
    f, v = shard.lookup(live)
    assert f.all() and np.array_equal(v, live + 7)
    f, _ = shard.lookup(keys[:500])
    assert not f.any()
    shard.switch_bmat_type()
    f, v = shard.lookup(live)
    assert f.all() and np.array_equal(v, live + 7)


def test_sharded_bmat_growth():
    keys = make_keys(2000, 111)
    shard = ShardedUpLIF(
        keys, None, UpLIFConfig(batch_bucket=256, bmat_capacity=256),
        n_shards=2,
    )
    r = np.random.default_rng(112)
    extra = np.setdiff1d(r.integers(0, 1 << 48, 15000).astype(np.int64), keys)
    shard.insert(extra, extra + 5)
    f, v = shard.lookup(extra)
    assert f.all() and np.array_equal(v, extra + 5)
    assert shard.size == len(keys) + len(extra)


@settings(
    max_examples=10,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)
@given(seed=st.integers(0, 10**6), n_shards=st.integers(2, 5))
def test_sharded_op_sequence_vs_oracle(seed, n_shards):
    r = np.random.default_rng(seed)
    keys = np.unique(r.integers(0, 1 << 40, 600).astype(np.int64))
    idx = ShardedUpLIF(keys, keys, UpLIFConfig(batch_bucket=256),
                       n_shards=n_shards)
    oracle = {int(k): int(k) for k in keys}
    for _ in range(3):
        op = r.integers(0, 3)
        if op == 0:
            ks = r.integers(0, 1 << 40, int(r.integers(1, 200))).astype(np.int64)
            vs = r.integers(0, 1 << 40, len(ks)).astype(np.int64)
            idx.insert(ks, vs)
            for k, v in zip(ks.tolist(), vs.tolist()):
                oracle[k] = v
        elif op == 1:
            pool = np.asarray(sorted(oracle), dtype=np.int64)
            take = r.choice(pool, min(len(pool), int(r.integers(1, 60))),
                            replace=False)
            idx.delete(take)
            for k in take.tolist():
                oracle.pop(int(k), None)
        else:
            pool = np.asarray(sorted(oracle), dtype=np.int64)
            hits = r.choice(pool, min(len(pool), 40), replace=False)
            f, v = idx.lookup(hits)
            assert f.all()
            assert np.array_equal(v, np.asarray([oracle[int(k)] for k in hits]))
    pool = np.asarray(sorted(oracle), dtype=np.int64)
    f, v = idx.lookup(pool)
    assert f.all()
    assert np.array_equal(v, np.asarray([oracle[int(k)] for k in pool]))
    assert idx.size == len(oracle)


# ---------------------------------------------------------------------------
# serving-engine prefix cache (satellite: capacity_hint + hit/miss)
# ---------------------------------------------------------------------------


def test_prefix_cache_capacity_hint_and_eviction_consistency():
    from repro.serve.engine import PrefixCacheIndex

    small = PrefixCacheIndex(capacity_hint=2048)
    big = PrefixCacheIndex(capacity_hint=32768)
    assert small.index.n_shards == 1
    assert big.index.n_shards == 8
    assert big.capacity_hint == 32768

    pc = PrefixCacheIndex(capacity_hint=4096)
    r = np.random.default_rng(113)
    fps = r.integers(1, 1 << 50, 4).astype(np.int64)
    sid, nblk = pc.match(fps)
    assert (sid, nblk) == (-1, 0) and pc.misses == 1

    slot = pc.admit(fps, state="decoded-state")
    sid, nblk = pc.match(fps)
    assert sid == slot and nblk == len(fps) and pc.hits == 1

    # evict the slot: a stale index match must count as a miss, not a hit
    pc.evict(slot, np.zeros(0, dtype=np.int64))  # slot gone, fps still indexed
    sid, nblk = pc.match(fps)
    assert (sid, nblk) == (-1, 0)
    assert pc.misses == 2 and pc.hits == 1


# ---------------------------------------------------------------------------
# RL agent (satellite: policy() must honor available_actions)
# ---------------------------------------------------------------------------


def test_policy_masks_disabled_actions():
    from repro.core.rl_agent import (
        A_KEEP,
        A_RETRAIN,
        A_SWITCH,
        AgentConfig,
        QLearningAgent,
    )

    agent = QLearningAgent(
        AgentConfig(epsilon=0.0), available_actions=(A_KEEP, A_RETRAIN)
    )
    s = (1, 1, 1, 1, 0)
    agent._q_row(s)[A_SWITCH] = 10.0  # best raw Q, but admin-disabled
    agent._q_row(s)[A_RETRAIN] = 1.0
    assert agent.choose(s, explore=False) == A_RETRAIN
    assert agent.policy()[s] == A_RETRAIN, "policy() must mask like choose()"


# ---------------------------------------------------------------------------
# what the write path reports and how its device phases are named
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sinsert_round_keys_count_only_keys_not_yet_stored(n_shards):
    """Upserts of stored keys are resolved by the probe, so the accept
    rounds get none of them and none runs; fresh keys all reach the
    rounds, which run at most ``insert_rounds`` times a call. The round
    count travels in the one host sync the call already makes."""
    from repro import obs

    keys = make_keys(4000, 303)
    idx = ShardedUpLIF(keys, keys, CFG, n_shards=n_shards)
    rec = obs.RECORDER
    names = ("sinsert.keys", "sinsert.round_keys", "sinsert.rounds",
             "sinsert.calls", "host_syncs.router.insert")

    def wave(batch):
        before = [rec.counter(n) for n in names]
        idx.insert(batch, batch * 5, pad_to=512)
        return dict(zip(names, (rec.counter(n) - b
                                for n, b in zip(names, before))))

    stored = np.random.default_rng(304).choice(keys, 300, replace=False)
    d = wave(stored)
    assert (d["sinsert.keys"], d["sinsert.round_keys"]) == (300, 0)
    assert (d["sinsert.rounds"], d["sinsert.calls"]) == (0, 1)
    assert d["host_syncs.router.insert"] == 1
    fresh = np.setdiff1d(
        np.random.default_rng(305).integers(0, 1 << 48, 400), keys
    )[:300]
    d = wave(fresh)
    assert (d["sinsert.keys"], d["sinsert.round_keys"]) == (300, 300)
    assert 1 <= d["sinsert.rounds"] <= CFG.insert_rounds
    assert d["sinsert.calls"] == d["host_syncs.router.insert"] == 1
    d = wave(fresh)                          # now stored (slots or BMAT)
    assert (d["sinsert.keys"], d["sinsert.round_keys"]) == (300, 0)
    assert d["sinsert.rounds"] == 0
    assert d["sinsert.calls"] == d["host_syncs.router.insert"] == 1
    found, vals = idx.lookup(np.concatenate([stored, fresh]))
    assert found.all() and (vals == np.concatenate([stored, fresh]) * 5).all()


def _open_gaps(idx):
    """Fresh keys by (shard, W-window): ``k + 1`` for each occupied slot
    ``k`` in the middle half of its window that an empty slot follows,
    with no key at ``k + 1``. Alone in its window such a key is placed in
    the first round without a shift, and placing it leaves the window's
    other such keys placeable."""
    W = idx.cfg.window
    sk = np.asarray(idx.state.slots.keys)
    so = np.asarray(idx.state.slots.occ)
    off = np.arange(sk.shape[1] - 1) % W
    ok = (so[:, :-1] & ~so[:, 1:] & (sk[:, 1:] > sk[:, :-1] + 1)
          & (off >= W // 4) & (off < 3 * W // 4))
    gaps = {}
    for s, i in zip(*np.nonzero(ok)):
        gaps.setdefault((int(s), int(i) // W), []).append(int(sk[s, i]) + 1)
    return gaps


def _window_heads(idx):
    """Fresh keys one above the occupied last slot of a W-window when the
    next window holds nothing below them: the insertion slot opens the
    next window, where no window insert can place a key (too near its
    edge), so each goes to the BMAT."""
    W = idx.cfg.window
    sk = np.asarray(idx.state.slots.keys)
    so = np.asarray(idx.state.slots.occ)
    last, head = sk[:, W - 1:-1:W], sk[:, W::W]
    ok = so[:, W - 1:-1:W] & (head > last + 1)
    return (last[ok] + 1).astype(np.int64)


def _sinsert_case(case, idx, keys):
    rng = np.random.default_rng(310)
    gaps = _open_gaps(idx)
    if case == "stored":
        return rng.choice(keys, 200, replace=False)
    if case == "one_per_window":
        return np.asarray([g[0] for g in list(gaps.values())[:40]])
    if case in ("two_per_window", "five_per_window"):
        per = 2 if case == "two_per_window" else 5
        full = [g[:per] for g in gaps.values() if len(g) >= per]
        assert len(full) >= 4
        return np.concatenate(full[:10])
    return _window_heads(idx)[:40]


@pytest.mark.parametrize("case,n_rounds", [
    ("stored", 0),            # the probe places every key
    ("one_per_window", 1),    # one round places every key
    ("two_per_window", 2),    # a window takes one key a round
    ("five_per_window", 3),   # ... until insert_rounds; the rest: BMAT
    ("window_heads", 1),      # a round that places none ends the loop
])
def test_sinsert_rounds_stop_when_no_key_can_be_placed(case, n_rounds):
    """``sinsert`` at S = 8 runs its accept rounds only while a key is
    pending and the round before placed one, and ends as the single-shard
    ``fops.insert`` (which always runs ``insert_rounds``) does shard by
    shard: the same slots, BMAT content, counters and lookups."""
    keys = make_keys(8000, 311)
    idx = ShardedUpLIF(keys, keys, CFG, n_shards=8)
    batch = _sinsert_case(case, idx, keys).astype(np.int64)
    vals = batch * 7
    q, _, vm = idx._pad_route(batch, vals, width=256)
    idx._ensure_bmat_capacity(int(q.shape[0]))

    # the single-shard path on each shard's shell, before the stacked call
    sid = idx._route(batch)
    shells = []
    for s in range(idx.n_shards):
        sh = idx._unstack_shell(s)
        m = sid == s
        if m.any():
            sq, _ = sh._pad(batch[m], KEY_MAX)
            sv, _ = sh._pad(vals[m], 0)
            sh._ensure_bmat_capacity(int(sq.shape[0]))
            st, res = fops.insert(sh.fstate, sq, sv, static=sh.fstatic())
            assert int(res.n_rounds) == CFG.insert_rounds
            sh._adopt(st)
        shells.append(sh)

    state, res = fops.sinsert(idx.state, q, vm, idx._jbounds, idx._jcodes,
                              static=idx._static())
    assert int(res.n_rounds) == n_rounds
    if case == "window_heads":
        assert int(res.n_overflow) == len(batch) > 0
    if case == "five_per_window":
        assert 0 < int(res.n_overflow) < len(batch)

    c = state.counters
    for s, sh in enumerate(shells):
        for f in ("n_keys", "n_bmat_live", "n_inplace", "n_overflow",
                  "min_granularity"):
            assert int(getattr(c, f)[s]) == int(getattr(sh._counters, f)), f
        for a, b in zip(state.slots, sh.slots):
            assert np.array_equal(np.asarray(a[s]), np.asarray(b))
        n = int(state.bmat.size[s])
        assert n == int(sh.bmat.state.size)
        assert np.array_equal(np.asarray(state.bmat.keys[s, :n]),
                              np.asarray(sh.bmat.state.keys[:n]))
        assert np.array_equal(np.asarray(state.bmat.vals[s, :n]),
                              np.asarray(sh.bmat.state.vals[:n]))
        _assert_slot_invariants(np.asarray(state.slots.keys[s]),
                                np.asarray(state.slots.occ[s]))

    idx.state = state
    allk = np.concatenate([keys, batch])
    want = {int(k): int(k) for k in keys}
    want.update((int(k), int(v)) for k, v in zip(batch, vals))
    found, got = idx.lookup(allk)
    assert found.all()
    assert np.array_equal(got, [want[int(k)] for k in allk])
    asid = idx._route(allk)
    for s, sh in enumerate(shells):
        f1, v1 = sh.lookup(allk[asid == s])
        assert f1.all() and np.array_equal(v1, got[asid == s])


def test_insert_reports_round_keys():
    keys = make_keys(3000, 306)
    idx = UpLIF(keys, keys, CFG)
    fresh = np.setdiff1d(
        np.random.default_rng(307).integers(0, 1 << 48, 200), keys
    )[:100]
    batch = np.concatenate([keys[:50], fresh])
    idx._ensure_bmat_capacity(256)
    _, res = fops.insert(idx.fstate, _pad(batch, KEY_MAX), _pad(batch, 0),
                         static=idx.fstatic())
    assert int(res.n_round_keys) == len(fresh)


def test_device_phases_are_named_in_the_lowered_programs():
    """``sinsert``'s probe, accept rounds and merge, and ``slookup``'s
    locate and rank, carry ``jax.named_scope`` names a profiler trace can
    group device operations by."""
    import re

    keys = make_keys(3000, 308)
    idx = ShardedUpLIF(keys, keys, CFG, n_shards=2)
    q = _pad(keys[:10], KEY_MAX)
    args = (idx.state, q, idx._jbounds, idx._jcodes)
    look = fops.slookup.lower(*args, static=idx._static())
    ins = fops.sinsert.lower(idx.state, q, q, idx._jbounds, idx._jcodes,
                             static=idx._static())

    def scopes(lowered, fn):
        text = lowered.as_text(debug_info=True)
        return set(re.findall(rf"jit\({fn}\)/(\w+)/", text))

    assert {"locate", "rank"} <= scopes(look, "slookup")
    assert {"probe", "rounds", "merge"} <= scopes(ins, "sinsert")
