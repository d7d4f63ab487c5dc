"""Gateway tests (ISSUE 7): flush triggers, §7.5 pad discipline + jit-cache
stability, shed-maintenance-before-reads ordering, read-your-writes under
threaded load, and idempotent/concurrent close with no hanging futures."""
import threading
import time

import numpy as np
import pytest

from conftest import make_keys

from repro.core import fops
from repro.core.sharded import ShardedUpLIF
from repro.shapes import (
    bucket_width,
    grow_capacity,
    padded_width,
    pow2_at_least,
)
from repro.core.uplif import UpLIFConfig
from repro.serve import (
    AdmissionController,
    GatewayClosed,
    GatewayConfig,
    PrefixCacheIndex,
    RequestGateway,
    RetryAfter,
)
from repro.tuning import A_RETRAIN_SHARD, SelfTuner


def _mk_index(n=2048, shards=2, seed=0):
    keys = make_keys(n, seed)
    return ShardedUpLIF(
        keys, keys * 2 + 1,
        UpLIFConfig(batch_bucket=256, bmat_capacity=1 << 13),
        n_shards=shards,
    ), keys


def _compile_counts():
    return {
        name: int(getattr(fops, name)._cache_size())
        for name in ("slookup", "sinsert", "sdelete", "range_scan")
    }


# ---------------------------------------------------------------- shapes


def test_shapes_quantization_family():
    assert [pow2_at_least(n) for n in (0, 1, 2, 3, 255, 256, 257)] == [
        1, 1, 2, 4, 256, 256, 512,
    ]  # n=0 must not hit (-1).bit_length() == 1
    for need in (1, 7, 256, 1000):
        cap = grow_capacity(need)
        assert cap >= 2 * need and cap & (cap - 1) == 0
    # below the bucket: pow2 with floor 256; above: bucket multiples
    assert bucket_width(10, 256) == 256
    assert bucket_width(300, 256) == 512
    assert bucket_width(1000, 256) == 1024
    assert bucket_width(1025, 256) == 1280  # non-pow2 multiple (bulk path)
    # the gateway family is pure pow2, floor/ceiling clamped
    assert padded_width(1) == 256
    assert padded_width(257) == 512
    assert padded_width(5000, floor=256, ceiling=1024) == 1024
    widths = {padded_width(n, floor=256, ceiling=2048) for n in range(1, 2049)}
    assert widths == {256, 512, 1024, 2048}


# ------------------------------------------------------------ flush triggers


def test_size_flush_fires_before_deadline():
    idx, keys = _mk_index()
    gw = RequestGateway(
        idx, config=GatewayConfig(max_batch=8, max_delay_s=30.0)
    )
    try:
        futs = [gw.submit_lookup(int(k)) for k in keys[:8]]
        for f, k in zip(futs, keys[:8]):
            found, v = f.result(20.0)
            assert found and v == int(k) * 2 + 1
        st = gw.stats()
        assert st["flush_triggers"]["size"] >= 1
        assert st["flush_triggers"]["deadline"] == 0
    finally:
        gw.close()


def test_deadline_flush_fires_below_size():
    idx, keys = _mk_index()
    gw = RequestGateway(
        idx, config=GatewayConfig(max_batch=1024, max_delay_s=0.01)
    )
    try:
        futs = [gw.submit_lookup(int(k)) for k in keys[:3]]
        for f in futs:
            assert f.result(20.0)[0]
        rk, rv = gw.submit_range(int(keys[0]), int(keys[10])).result(20.0)
        hits = rk[rk < np.iinfo(np.int64).max]
        assert len(hits) == 11 and int(hits[0]) == int(keys[0])
        st = gw.stats()
        assert st["flush_triggers"]["deadline"] >= 1
        assert st["flush_triggers"]["size"] == 0
        # the batching delay is bounded by the deadline (+ service time)
        assert all(f.queue_latency_s < 5.0 for f in futs)
    finally:
        gw.close()


# ------------------------------------------------- §7.5 padding + jit cache


def test_pad_widths_quantized_and_jit_cache_flat():
    idx, keys = _mk_index(4096)
    gw = RequestGateway(
        idx, config=GatewayConfig(max_batch=512, max_delay_s=0.002)
    )
    try:
        primed = gw.warmup()
        assert primed["lookup"] == [256, 512]
        counts0 = _compile_counts()
        rng = np.random.default_rng(7)
        # a live stream of awkward burst sizes — every flush must still
        # land on a warmed pow2 width and mint zero new jit entries
        futs = []
        for burst in (1, 3, 17, 130, 300, 511, 97):
            pick = rng.choice(keys, burst)
            futs += [gw.submit_lookup(int(k)) for k in pick]
            futs.append(gw.submit_insert(int(pick[0]), 5))
            futs.append(gw.submit_delete(int(pick[-1])))
            time.sleep(0.004)
        for f in futs:
            f.result(30.0)
        st = gw.stats()
        for op, hist in st["pad_widths"].items():
            for w in hist:
                assert w & (w - 1) == 0, (op, w)
                assert 256 <= w <= 512, (op, w)
        assert _compile_counts() == counts0
    finally:
        gw.close()


# ------------------------------------------------------- overload ladder


def test_admission_ladder_sheds_maintenance_strictly_first():
    adm = AdmissionController(capacity=100)
    assert adm.level(49) == 0
    assert adm.level(50) == 1     # maintenance shed here...
    assert adm.level(89) == 1
    assert adm.level(90) == 2     # ...requests only here
    # structural: any growing backlog crosses level 1 before level 2
    with pytest.raises(AssertionError):
        AdmissionController(
            capacity=100, shed_maintenance_at=0.9, shed_requests_at=0.5
        )
    assert 0.001 <= adm.retry_after(95, 0.0) <= 5.0
    assert adm.retry_after(200, 10.0) >= adm.retry_after(95, 10.0)


def test_scheduler_sheds_under_pressure():
    idx, _ = _mk_index()
    tuner = SelfTuner().attach(idx)
    sched = tuner.scheduler
    tuner.set_pressure(1)
    b0 = sched._budget
    tuner.after_wave(1000, 0.5)
    assert sched.n_shed_waves == 1
    assert sched._budget == b0          # no refill while shedding
    assert not sched._admit(idx, A_RETRAIN_SHARD, 0, False)  # no new plans
    tuner.set_pressure(0)
    tuner.after_wave(1000, 0.5)
    assert sched._budget > b0           # healthy again → budget accrues
    assert tuner.stats()["shed_waves"] == 1


class _SlowIndex:
    """Router wrapper: every wave takes ``delay`` — backlog builds fast."""

    def __init__(self, inner, delay=0.05):
        self._inner = inner
        self.delay = delay

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def apply_wave(self, wave):
        time.sleep(self.delay)
        return self._inner.apply_wave(wave)


class _StubTuner:
    def __init__(self):
        self.pressure_calls = []

    def set_pressure(self, level):
        self.pressure_calls.append((time.perf_counter(), level))

    def observe_inserts(self, keys):
        pass

    def after_wave(self, n_ops, seconds):
        pass


def test_overload_sheds_maintenance_before_rejecting_reads():
    idx, keys = _mk_index()
    tuner = _StubTuner()
    gw = RequestGateway(
        _SlowIndex(idx), tuner=tuner,
        config=GatewayConfig(max_batch=8, max_delay_s=0.001, max_pending=40),
    )
    events0 = gw.stats()["pressure_events"]
    try:
        rejected_at = None
        futs = []
        for i in range(200):
            try:
                futs.append(gw.submit_lookup(int(keys[i % len(keys)])))
            except RetryAfter as e:
                rejected_at = time.perf_counter()
                assert 0.0 < e.retry_after_s <= 5.0
                break
        assert rejected_at is not None, "overload never hit level 2"
        shed_at = [t for t, lvl in tuner.pressure_calls if lvl >= 1]
        assert shed_at, "maintenance was never shed"
        assert shed_at[0] < rejected_at, (
            "requests were rejected before maintenance was shed"
        )
        assert gw.first_reject_t is not None
        for f in futs:
            f.result(30.0)
    finally:
        gw.close()
    # recovery: once drained, the gateway reports pressure 0 downstream
    assert tuner.pressure_calls[-1][1] == 0
    # one pressure event per level change the tuner was told of
    assert gw.stats()["pressure_events"] - events0 == len(tuner.pressure_calls)


def test_stats_obs_nests_router_and_tuner_spans_under_each_wave():
    from repro import obs

    idx, keys = _mk_index()
    tuner = SelfTuner().attach(idx)
    gw = RequestGateway(
        idx, tuner=tuner, config=GatewayConfig(max_batch=64, max_delay_s=0.001)
    )
    before = gw.stats()
    t0 = time.perf_counter_ns()
    try:
        for i in range(3):
            futs = [gw.submit_insert(int(k) + 1, 5) for k in keys[i * 40:(i + 1) * 40]]
            futs += [gw.submit_lookup(int(k)) for k in keys[:50]]
            for f in futs:
                f.result(30.0)
    finally:
        gw.close()
        tuner.close()
    after = gw.stats()
    waves = after["waves"] - before["waves"]
    assert waves >= 3

    def delta(name):
        b = before["obs"]["spans"].get(name, {"count": 0})["count"]
        return after["obs"]["spans"][name]["count"] - b

    assert delta("gateway.wave") == waves
    assert delta("gateway.drain") == delta("gateway.complete") == waves
    assert delta("router.apply_wave") == delta("tuner.after_wave") == waves
    syncs = after["obs"]["counters"]["host_syncs"]
    assert syncs > before["obs"]["counters"].get("host_syncs", 0)
    assert after["obs"]["counters"]["host_syncs.router.lookup"] > 0

    spans = obs.RECORDER.spans(t0, time.perf_counter_ns())
    by_id = {s.id: s for s in spans}
    top = {s.wave: s for s in spans if s.name == "gateway.wave"}
    assert len(top) == waves
    for s in spans:
        if s.name.split(".")[0] not in ("router", "tuner", "gateway"):
            continue
        assert s.wave in top, s
        root = s
        while root.parent != -1:
            root = by_id[root.parent]
            assert root.wave == s.wave
        assert root is top[s.wave]
    names = {s.name for s in spans}
    assert {"router.insert", "router.lookup", "router.launch", "router.wait",
            "tuner.observe_inserts", "tuner.forecast", "tuner.telemetry",
            "tuner.decide"} <= names


# ------------------------------------------------------ read-your-writes


def test_threaded_clients_read_their_own_writes():
    idx, _ = _mk_index(4096)
    gw = RequestGateway(
        idx, config=GatewayConfig(max_batch=64, max_delay_s=0.001)
    )
    errors = []

    def client(tid):
        try:
            base = (1 << 45) + tid * 10_000
            for r in range(15):
                k, v = base + r, tid * 1000 + r
                assert gw.submit_insert(k, v).result(30.0) is True
                found, got = gw.submit_lookup(k).result(30.0)
                assert found and got == v, (tid, r, found, got)
                if r % 3 == 0:
                    assert gw.submit_delete(k).result(30.0) is True
                    found, _ = gw.submit_lookup(k).result(30.0)
                    assert not found, (tid, r)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    try:
        ts = [
            threading.Thread(target=client, args=(i,)) for i in range(16)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60.0)
        assert not errors, errors[:3]
    finally:
        gw.close()


# ---------------------------------------------------------------- close


def test_close_is_idempotent_and_concurrent_safe():
    idx, keys = _mk_index()
    gw = RequestGateway(
        _SlowIndex(idx, delay=0.02),
        config=GatewayConfig(max_batch=4, max_delay_s=0.001),
    )
    futs = [gw.submit_lookup(int(k)) for k in keys[:40]]
    closers = [threading.Thread(target=gw.close) for _ in range(4)]
    for t in closers:
        t.start()
    # every pre-close future completes — value or GatewayClosed, never a hang
    for f in futs:
        try:
            found, v = f.result(30.0)
            assert found
        except GatewayClosed:
            pass
    for t in closers:
        t.join(30.0)
        assert not t.is_alive()
    with pytest.raises(GatewayClosed):
        gw.submit_lookup(int(keys[0]))
    gw.close()  # idempotent
    assert gw.backlog == 0


def test_prefix_cache_index_close_idempotent_and_gateway_aware():
    pci = PrefixCacheIndex(capacity_hint=4096, tuner=SelfTuner())
    gw = pci.open_gateway(GatewayConfig(max_batch=16, max_delay_s=0.001))
    assert pci.open_gateway() is gw          # open is idempotent too
    found, _ = gw.submit_lookup(12345).result(30.0)
    assert not found                          # nothing admitted yet
    closers = [threading.Thread(target=pci.close) for _ in range(4)]
    for t in closers:
        t.start()
    for t in closers:
        t.join(30.0)
        assert not t.is_alive()
    assert gw.closed
    with pytest.raises(GatewayClosed):
        gw.submit_lookup(1)
    with pytest.raises(RuntimeError):
        pci.open_gateway()
    pci.close()  # idempotent
