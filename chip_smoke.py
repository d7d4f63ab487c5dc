"""Smoke test of the served index path on one TPU chip.

    python3 chip_smoke.py

Runs in ONE process (a chip belongs to one process) and drives the index
through the entry points a user calls:

  (a) kernels on chip — one router at a shape the fused kernels admit
      (S=1, 200k wikits keys, ``locate="fused"``) plays a seeded tape of
      inserts, deletes, lookups (hits and misses) and range scans. Every
      answer must match a ``locate="spline"`` and a ``locate="binsearch"``
      twin and a plain numpy reference. The lowered lookup must hold the
      compiled kernels (``tpu_custom_call``), and the on-chip GMM E-step
      must agree with the numpy E-step to f32 tolerance. Warm lookup and
      insert waves are then timed for the three strategies at the same
      shape (host wall clock, median of interleaved repetitions).
  (b) the deployment path — ``ShardedUpLIF(n_shards=8)`` over 24M wikits
      keys, a ``SelfTuner.overlapped`` tuner and a ``RequestGateway`` set up
      as in ``examples/serve_gateway.py``. 24M is the most that keeps every
      shard's capacity at 2**23 slots: at 30M the shards round up to 2**24,
      which doubles the device state. One insert wave then needs 3.1 GiB
      of state in, 3.1 GiB out and 5.8 GiB of temporaries (the TPU
      compiler's memory analysis of ``fops.sinsert``), and a snapshot that
      an in-flight maintenance build holds keeps another 3.1 GiB alive:
      within about 1 GiB of the chip's 16 GiB. Client threads send
      lookups, inserts, deletes and range scans in phases whose answers
      the reference knows; every future is checked. A hot-spot insert burst
      forces a maintenance commit, after which every touched key is read
      back again.

Progress and numbers go to stdout; the last line is the JSON result
``{"ok": true, "device": {...}}``, printed only when JAX runs on a TPU and
every check passed. Anywhere else the script exits non-zero without it.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

VAL_MUL, VAL_ADD = 3, 7  # bootstrap value of key k: 3k + 7


def log(msg: str):
    print(msg, flush=True)


class Reference:
    """Plain model of the index's semantics: a sorted key array with values.
    Batches of one phase touch each key at most once, so batch order is
    the only order that matters."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.keys = np.asarray(keys, dtype=np.int64)
        self.vals = np.asarray(vals, dtype=np.int64)

    def _find(self, q: np.ndarray):
        i = np.searchsorted(self.keys, q)
        ic = np.minimum(i, len(self.keys) - 1)
        return i, ic, (i < len(self.keys)) & (self.keys[ic] == q)

    def lookup(self, q: np.ndarray):
        _, ic, hit = self._find(np.asarray(q, dtype=np.int64))
        return hit, np.where(hit, self.vals[ic], 0)

    def upsert(self, k: np.ndarray, v: np.ndarray):
        k = np.asarray(k, dtype=np.int64)
        v = np.asarray(v, dtype=np.int64)
        i, ic, hit = self._find(k)
        self.vals[ic[hit]] = v[hit]
        order = np.argsort(k[~hit])
        fresh, fv = k[~hit][order], v[~hit][order]
        pos = np.searchsorted(self.keys, fresh)
        self.keys = np.insert(self.keys, pos, fresh)
        self.vals = np.insert(self.vals, pos, fv)

    def delete(self, k: np.ndarray) -> np.ndarray:
        _, ic, hit = self._find(np.asarray(k, dtype=np.int64))
        keep = np.ones(len(self.keys), dtype=bool)
        keep[ic[hit]] = False
        self.keys, self.vals = self.keys[keep], self.vals[keep]
        return hit

    def range(self, lo: int, hi: int, max_out: int):
        a = np.searchsorted(self.keys, lo, "left")
        b = np.searchsorted(self.keys, hi, "right")
        b = min(b, a + max_out)
        return self.keys[a:b], self.vals[a:b]

    def absent(self, rng, n: int, lo: int, hi: int) -> np.ndarray:
        """``n`` distinct keys in [lo, hi) that are not stored."""
        out = np.zeros(0, dtype=np.int64)
        while len(out) < n:
            c = np.unique(rng.integers(lo, hi, 2 * n + 16).astype(np.int64))
            c = c[~self._find(c)[2]]
            out = np.union1d(out, c)
        return rng.permutation(out)[:n]


class Checks:
    """Counts answers and collects mismatches (first few kept verbatim)."""

    def __init__(self, name: str):
        self.name = name
        self.n = 0
        self.bad = []
        self._lock = threading.Lock()

    def expect(self, what: str, got, want):
        ok = bool(np.array_equal(np.asarray(got), np.asarray(want)))
        with self._lock:
            self.n += 1
            if not ok:
                self.bad.append((what, got, want))

    def done(self):
        if self.bad:
            for what, got, want in self.bad[:5]:
                log(f"  MISMATCH {what}: got {got!r} want {want!r}")
            raise AssertionError(
                f"{self.name}: {len(self.bad)} of {self.n} answers wrong"
            )
        log(f"  {self.name}: {self.n} answers checked, all correct")


def _ranges(ref: Reference, rng, n: int, span_keys: int, extra=()):
    """``n`` narrow ranges (about ``span_keys`` stored keys each) drawn over
    the stored keys, plus ranges around the keys in ``extra``."""
    idx = rng.integers(0, len(ref.keys) - span_keys - 1, n)
    out = [(int(ref.keys[i]) - 1, int(ref.keys[i + span_keys])) for i in idx]
    for k in extra:
        j = int(np.searchsorted(ref.keys, k))
        a = max(j - span_keys // 2, 0)
        b = min(j + span_keys // 2, len(ref.keys) - 1)
        out.append((int(ref.keys[a]), int(ref.keys[b])))
    return out


# ---------------------------------------------------------------------------
# (a) kernels on chip
# ---------------------------------------------------------------------------


def phase_kernels(n_keys: int = 200_000, seed: int = 0, rounds: int = 4,
                  batch: int = 2048, reps: int = 7):
    import jax.numpy as jnp

    from repro.core import ShardedUpLIF, fops
    from repro.core.uplif import UpLIFConfig
    from repro.data import make_dataset
    from repro.kernels import ops as kops
    from repro.tuning.forecast import ForecastConfig, UpdateForecaster

    log(f"[a] kernels: {n_keys:,} wikits keys, S=1, locate=fused vs spline "
        "vs binsearch")
    rng = np.random.default_rng(seed)
    keys = make_dataset("wikits", n_keys, seed)
    vals = keys * VAL_MUL + VAL_ADD
    indexes = {
        strat: ShardedUpLIF(keys, vals, UpLIFConfig(locate=strat), n_shards=1)
        for strat in ("fused", "spline", "binsearch")
    }
    fused = indexes["fused"]
    ref = Reference(keys, vals)

    def check_strategies():
        for strat, idx in indexes.items():
            assert idx.shard_locate() == (strat,), idx.shard_locate()

    check_strategies()

    state, _, jb, static = fused._read_view()
    q = jnp.asarray(keys[:batch])
    text = fops.slookup.lower(state, q, jb, static=static).as_text()
    compiled = "tpu_custom_call" in text
    assert compiled != kops.interpret_mode(), (
        "fused lookup lowered to the wrong kernel form"
    )
    log(f"  kernels {'compiled (tpu_custom_call in the lowered lookup)' if compiled else 'in Pallas interpret mode'}")

    chk = Checks("[a] fused vs spline vs binsearch vs reference")
    lo_k, hi_k = int(keys[0]), int(keys[-1]) + 1
    t0 = time.perf_counter()
    for r in range(rounds):
        fresh = ref.absent(rng, batch // 2, lo_k, hi_k)
        upd = rng.choice(ref.keys, batch // 4, replace=False)
        ins = np.concatenate([fresh, upd])
        iv = ins * 5 + r
        for idx in indexes.values():
            idx.insert(ins, iv)
        ref.upsert(ins, iv)

        dels = np.concatenate([
            rng.choice(ref.keys, batch // 4, replace=False),
            ref.absent(rng, batch // 8, lo_k, hi_k),
        ])
        want = ref.delete(dels)
        for name, idx in indexes.items():
            chk.expect(f"round {r} delete hits ({name})", idx.delete(dels), want)

        probes = np.concatenate([
            rng.choice(ref.keys, batch // 2), ins[: batch // 4], dels,
            ref.absent(rng, batch // 4, lo_k, hi_k),
        ])
        wf, wv = ref.lookup(probes)
        for name, idx in indexes.items():
            f, v = idx.lookup(probes)
            chk.expect(f"round {r} lookup found ({name})", f, wf)
            chk.expect(f"round {r} lookup values ({name})", v, wv)

        rr = _ranges(ref, rng, 24, 12, extra=fresh[:8])
        lo = np.asarray([a for a, _ in rr], dtype=np.int64)
        hi = np.asarray([b for _, b in rr], dtype=np.int64)
        for name, idx in indexes.items():
            ks, vs = idx.range_query_batch(lo, hi, max_out=256)
            for i, (a, b) in enumerate(rr):
                ek, ev = ref.range(a, b, 256)
                chk.expect(f"round {r} range {i} keys ({name})", ks[i], ek)
                chk.expect(f"round {r} range {i} vals ({name})", vs[i], ev)
    log(f"  tape: {rounds} rounds in {time.perf_counter() - t0:.3f}s")

    # warm waves: one untimed repetition, then ``reps`` timed ones,
    # strategies interleaved; the last inserts are read back after them.
    # The widths pad to those the tape compiled (4096 lookups, 2048 inserts)
    look_q = rng.choice(ref.keys, 2 * batch)
    secs = {name: {"lookup": [], "insert": []} for name in indexes}
    for rep in range(reps + 1):
        fresh = ref.absent(rng, batch, lo_k, hi_k)
        fv = fresh * 9 + rep
        ref.upsert(fresh, fv)
        for name, idx in indexes.items():
            t1 = time.perf_counter()
            idx.lookup(look_q)
            t2 = time.perf_counter()
            idx.insert(fresh, fv)
            t3 = time.perf_counter()
            if rep:
                secs[name]["lookup"].append(t2 - t1)
                secs[name]["insert"].append(t3 - t2)
    probes = np.concatenate([look_q, fresh])
    wf, wv = ref.lookup(probes)
    for name, idx in indexes.items():
        f, v = idx.lookup(probes)
        chk.expect(f"after timed waves, lookup found ({name})", f, wf)
        chk.expect(f"after timed waves, lookup values ({name})", v, wv)
    chk.done()
    check_strategies()
    med = {name: {op: float(np.median(t)) for op, t in ops.items()}
           for name, ops in secs.items()}
    for op, width in (("lookup", 2 * batch), ("insert", batch)):
        log(f"  warm {op} wave of {width} keys, median of {reps} (host wall "
            "clock): " + ", ".join(
                f"{name} {1e3 * m[op]:.3f} ms" for name, m in med.items()))

    # GMM E-step: the forecaster's kernel path vs its numpy path
    x = rng.integers(lo_k, hi_k, 8192).astype(np.float64)
    on = UpdateForecaster(lo_k, hi_k, ForecastConfig(use_pallas=True))
    off = UpdateForecaster(lo_k, hi_k, ForecastConfig(use_pallas=False))
    got, want = on._responsibilities(x), off._responsibilities(x)
    err = float(np.abs(got - want).max())
    log(f"  gmm E-step: kernel vs numpy max |diff| = {err:.3e} over {x.size} samples")
    assert err < 1e-4, f"E-step kernel disagrees with numpy: {err}"
    return med


# ---------------------------------------------------------------------------
# (b) the deployment path
# ---------------------------------------------------------------------------


def _submit(gw, op: str, *args):
    from repro.serve import RetryAfter

    fn = getattr(gw, f"submit_{op}")
    while True:
        try:
            return fn(*args)
        except RetryAfter as e:
            time.sleep(e.retry_after_s)


def run_clients(gw, requests, chk: Checks, n_clients: int):
    """Send ``requests`` — (op, args, expected) — from ``n_clients``
    threads, each owning a strided share, and check every future."""
    errors = []

    def client(tid: int):
        try:
            mine = requests[tid::n_clients]
            futs = [_submit(gw, op, *args) for op, args, _ in mine]
            for (op, args, want), fut in zip(mine, futs):
                got = fut.result(600.0)
                if op == "range":
                    chk.expect(f"range {args}", got[0], want[0])
                    chk.expect(f"range vals {args}", got[1], want[1])
                else:
                    chk.expect(f"{op} {args[0]}", got, want)
        except Exception as e:  # noqa: BLE001 — reported by the caller
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"client errors: {errors[:3]}")


def _read_requests(ref: Reference, keys: np.ndarray, ranges, max_out: int):
    found, vals = ref.lookup(keys)
    req = [("lookup", (int(k),), (bool(f), int(v)))
           for k, f, v in zip(keys, found, vals)]
    req += [("range", (a, b), ref.range(a, b, max_out)) for a, b in ranges]
    return req


def phase_serve(n_keys: int = 24_000_000, n_shards: int = 8, seed: int = 0,
                n_clients: int = 4, n_reads: int = 4096, n_writes: int = 3072,
                n_hot: int = 24576, timeouts: float = 600.0):
    from repro.core import ShardedUpLIF
    from repro.data import make_dataset
    from repro.serve import GatewayConfig, RequestGateway
    from repro.tuning import SelfTuner

    log(f"[b] serve: {n_keys:,} wikits keys, S={n_shards}, {n_clients} "
        "client threads through the gateway")
    rng = np.random.default_rng(seed + 1)
    t0 = time.perf_counter()
    keys = make_dataset("wikits", n_keys, seed)
    vals = keys * VAL_MUL + VAL_ADD
    t_data = time.perf_counter() - t0
    index = ShardedUpLIF(keys, vals, n_shards=n_shards)
    setup_s = time.perf_counter() - t0
    ref = Reference(keys, vals)
    del keys, vals
    log(f"  setup {setup_s:.3f}s (data {t_data:.3f}s); strategies that run: "
        f"{index.shard_locate()}")
    tuner = SelfTuner.overlapped(
        max_concurrent_builds=2, commit_replay_cap=4096
    ).attach(index)
    cfg = GatewayConfig(max_batch=1024, max_delay_s=0.002)
    gw = RequestGateway(index, tuner=tuner, config=cfg)
    try:
        t1 = time.perf_counter()
        primed = gw.warmup()
        warmup_s = time.perf_counter() - t1
        log(f"  warmup {warmup_s:.3f}s, primed widths {primed}")
        lo_k, hi_k = int(ref.keys[0]), int(ref.keys[-1]) + 1
        mo = cfg.range_max_out

        # 1. reads over the bootstrap: hits, misses, narrow ranges
        chk = Checks("[b] reads")
        probe = np.concatenate([
            rng.choice(ref.keys, n_reads * 3 // 4),
            ref.absent(rng, n_reads // 4, lo_k, hi_k),
        ])
        run_clients(gw, _read_requests(ref, probe, _ranges(ref, rng, 64, 16),
                                       mo), chk, n_clients)
        chk.done()

        # 2. writes: fresh inserts, updates, deletes (hits and misses)
        chk = Checks("[b] writes")
        fresh = ref.absent(rng, n_writes * 2 // 3, lo_k, hi_k)
        upd = rng.choice(ref.keys, n_writes // 3, replace=False)
        ins = np.concatenate([fresh, upd])
        dels = np.concatenate([
            np.setdiff1d(rng.choice(ref.keys, n_writes // 3, replace=False),
                         upd),
            ref.absent(rng, n_writes // 12, lo_k, hi_k),
        ])
        dels = np.setdiff1d(dels, fresh)
        iv = ins * 11 + 1
        hits = ref.delete(dels)
        ref.upsert(ins, iv)
        req = [("insert", (int(k), int(v)), True) for k, v in zip(ins, iv)]
        req += [("delete", (int(k),), bool(h)) for k, h in zip(dels, hits)]
        run_clients(gw, req, chk, n_clients)
        chk.done()

        # 3. read back every written key, plus ranges around them
        chk = Checks("[b] read-your-writes")
        touched = np.concatenate([ins, dels])
        run_clients(gw, _read_requests(
            ref, touched, _ranges(ref, rng, 16, 16, extra=fresh[:48]), mo
        ), chk, n_clients)
        chk.done()

        # 4. hot-spot burst: fresh keys packed between a few thousand
        # neighbouring keys overflow that shard's delta buffer until the
        # tuner's capacity guard (or its own choice) retrains or splits it
        base = int(rng.integers(0, len(ref.keys) - 8192))
        hot = ref.absent(rng, n_hot, int(ref.keys[base]),
                         int(ref.keys[base + 4096]))
        st0 = tuner.stats()
        chk = Checks("[b] hot-spot inserts")
        t_hot = time.perf_counter()
        for part in np.array_split(hot, 8):
            hv = part * 13 + 5
            ref.upsert(part, hv)
            run_clients(gw, [("insert", (int(k), int(v)), True)
                             for k, v in zip(part, hv)], chk, n_clients)
        chk.done()
        # keep waves flowing until a build has committed and drained
        chk = Checks("[b] reads while maintenance lands")
        while True:
            st = tuner.stats()
            if (st["commits"] > st0["commits"] and st["drain_backlog_ops"] == 0
                    and not index.draining):
                break
            if time.perf_counter() - t_hot > timeouts:
                raise TimeoutError(f"no maintenance commit landed: {st}")
            sample = rng.choice(hot, 256)
            run_clients(gw, _read_requests(ref, sample, (), mo), chk, n_clients)
        chk.done()
        st = tuner.stats()
        log(f"  maintenance: commits {st0['commits']}->{st['commits']}, "
            f"actions {st['actions']}, epoch {st['epoch']}, "
            f"retrains {index.n_retrains} splits {index.n_splits}, "
            f"{time.perf_counter() - t_hot:.3f}s after the burst began")

        # 5. after the commit: every key this run touched, plus the
        # bootstrap, plus ranges over the rebuilt region
        chk = Checks("[b] after maintenance")
        again = np.concatenate([touched, hot, rng.choice(ref.keys, n_reads)])
        run_clients(gw, _read_requests(
            ref, again, _ranges(ref, rng, 32, 16, extra=hot[:32]), mo
        ), chk, n_clients)
        chk.done()
    finally:
        gw.close()
        tuner.close()
    st = tuner.stats()
    gs = gw.stats()
    assert gs["last_error"] is None, gs["last_error"]
    assert st["abandoned"] == 0, f"abandoned builds: {st}"
    assert st["last_build_error"] is None, st["last_build_error"]
    log(f"  index_bytes {index.index_bytes()} (slots+model+bmat "
        f"{index.memory_bytes()}), {index.size} keys, S={index.n_shards}")
    log(f"  strategies that ran: {index.shard_locate()}")
    log(f"  gateway: {gs}")
    log(f"  tuner: {st}")
    return {"setup_s": setup_s, "warmup_s": warmup_s}


# ---------------------------------------------------------------------------


class CompileClock:
    """Sums XLA backend-compile seconds (in all and per jitted function)
    and persistent-cache events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.per_fun = {}
        self.events = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            n, s = self.per_fun.get(fun_name, (0, 0.0))
            self.per_fun[fun_name] = (n + 1, s + secs)

    def top(self, k: int = 6) -> str:
        worst = sorted(self.per_fun.items(), key=lambda kv: -kv[1][1])[:k]
        return ", ".join(f"{f} {n}x {s:.3f}s" for f, (n, s) in worst)

    def _evt(self, name, **_):
        if name.startswith("/jax/compilation_cache/"):
            k = name.rsplit("/", 1)[1]
            self.events[k] = self.events.get(k, 0) + 1


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    clock = CompileClock()
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        phase_kernels()
        log(f"[a] done: {time.perf_counter() - t0:.3f}s, compile "
            f"{clock.seconds:.3f}s, cache {clock.events}")
        t1 = time.perf_counter()
        res = phase_serve()
        log(f"[b] done: {time.perf_counter() - t1:.3f}s")
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        log("chip_smoke: FAILED")
        return 1
    stats = dev.memory_stats() or {}
    log(f"total {time.perf_counter() - t0:.3f}s; setup {res['setup_s']:.3f}s, "
        f"warmup {res['warmup_s']:.3f}s, backend compile {clock.seconds:.3f}s, "
        f"cache {clock.events}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')}")
    log(f"slowest compiles: {clock.top()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
