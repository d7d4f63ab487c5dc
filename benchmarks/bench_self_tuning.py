"""Online self-tuning under distribution shift (ISSUE 2/3 acceptance bench).

Reproduces the Section 5.3 regime end to end on the sharded router: a
write-heavy workload whose insert stream SHIFTS mid-run from the bootstrap
key range to a previously-unseen upper range. Four maintenance policies
run the identical (deterministically seeded) op sequence:

  tuned           — the tuning subsystem with SYNC builds: plan/build/commit
                    all run between waves on the serving path (the stall the
                    paper's "no retraining stalls" claim is measured against);
  tuned_async     — same planner, ONE build on the executor thread: the
                    serving path pays only plan + commit (row write + full
                    op-log replay in one wave), the host rebuild overlaps
                    the following waves;
  tuned_concurrent— the ISSUE 4 pipeline: up to 2 builds on DISJOINT shard
                    intervals in flight at once (per-interval op-logs) and
                    PACED commits — each commit replays at most
                    ``--replay-cap`` logged ops per wave, draining across
                    waves, so the replay burst (the last unbounded
                    serving-path cost) is bounded like every other op;
  never_tune      — no maintenance: the delta buffer absorbs the shift
                    (grows, reallocates, recompiles, slows every op);
  always_retrain  — full retrain on a fixed cadence, paying the whole-index
                    rebuild whether or not any shard needs it.

The policies run one after another in this process (a chip belongs to
one process, so a child could not open it). Before each one
``jax.clear_caches()`` empties the jit caches and the persistent
compilation cache is off, so every policy pays its own cold jit-compile
and reallocation debt — otherwise whoever runs second would reuse the
first policy's compiled variants, which is exactly the cost axis the
policies differ on. Reported throughput covers the FULL run:
maintenance, reallocation and recompilation included.

Per-wave serving-path latency (lookup + insert + range scans + the
between-wave tuner hook) is recorded per policy; the ``async_vs_sync`` row
compares the post-warmup p50/p95 and checks final index contents are
equivalent (identical lookup results over every key the run inserted —
the delta-replay rebase must lose nothing). The ``concurrent_vs_async``
row is the ISSUE 4 acceptance check: per-wave p95 with 2 concurrent
builds + paced commits must not exceed single-build async p95, final
digests must match sync exactly, and the per-wave replay-burst histogram
(ops rebased at each wave boundary) shows the pacing cap actually
bounding the bursts. The comparison rows also report the paper's Section
4.3 composite objective R = η·tput/max_tput − (1−η)·mem/max_mem (η =
0.7), the quantity the controller optimizes.

Each wave issues a few range scans and reports their latency through
``tuner.observe_range`` — the telemetry signal that folds scan cost into
the controller reward (ROADMAP "Range-heavy tuning rewards").
"""
from __future__ import annotations

import argparse
import hashlib
import time

import numpy as np

ETA = 0.7  # Section 5.1 reward weight

POLICIES = (
    "tuned", "tuned_async", "tuned_concurrent", "never_tune",
    "always_retrain",
)
WARMUP_WAVES = 5       # excluded from latency percentiles (cold jit debt)
RANGES_PER_WAVE = 2    # range scans issued (and timed) per wave


def _workload(n_keys: int, waves: int, batch: int, seed: int):
    """Deterministic wave list: (read_keys, insert_keys, range_los) tuples
    with the insert stream shifting to the upper key range at waves//3."""
    from repro.data import make_dataset

    keys = np.sort(make_dataset("wikits", n_keys, seed))
    n_init = n_keys // 2
    init = keys[:n_init]
    upper = keys[n_init:].copy()
    rng = np.random.default_rng(seed + 1)
    rng.shuffle(upper)
    # phase-1 inserts: fresh keys interleaved INSIDE the bootstrap range
    lo, hi = int(init[0]), int(init[-1])
    in_range = rng.integers(lo, hi, waves * batch).astype(np.int64)
    in_range = np.setdiff1d(in_range, init)[: waves * batch]
    rng.shuffle(in_range)
    shift_at = waves // 3
    plan = []
    known = init
    ip1 = ip2 = 0
    n_w = batch // 2
    for w in range(waves):
        if w < shift_at:
            ins = in_range[ip1 : ip1 + n_w]
            ip1 += n_w
        else:
            ins = upper[ip2 : ip2 + n_w]
            ip2 += n_w
            if ip2 + n_w > len(upper):
                ip2 = 0
        reads = rng.choice(known, batch - n_w)
        scans = rng.choice(known, RANGES_PER_WAVE)
        if w % 8 == 0:
            known = np.concatenate([known, ins])
        plan.append((reads, ins, scans))
    return init, plan, shift_at


def _content_digest(idx, keys: np.ndarray) -> str:
    """Order-independent digest of the index's view of ``keys`` (found
    flags + values) — the cross-policy contents-equivalence check."""
    keys = np.unique(keys)
    h = hashlib.sha256()
    for a in range(0, len(keys), 65536):
        f, v = idx.lookup(keys[a : a + 65536])
        h.update(f.astype(np.uint8).tobytes())
        h.update(np.where(f, v, 0).astype(np.int64).tobytes())
    return h.hexdigest()


def _run_policy(
    policy: str,
    init: np.ndarray,
    plan,
    *,
    n_shards: int,
    retrain_every: int,
    seed: int,
    replay_cap: int = 2048,
):
    import repro.core  # noqa: F401 — x64
    from repro.core import ShardedUpLIF
    from repro.core.uplif import UpLIFConfig
    from repro.tuning import SelfTuner, TunerConfig
    from repro.tuning.controller import ControllerConfig
    from repro.tuning.forecast import ForecastConfig
    from repro.tuning.scheduler import SchedulerConfig

    idx = ShardedUpLIF(
        init, init + 1, UpLIFConfig(batch_bucket=4096), n_shards=n_shards
    )
    tuner = None
    if policy in ("tuned", "tuned_async", "tuned_concurrent"):
        if policy == "tuned_concurrent":
            sched = SchedulerConfig(
                async_build=True,
                max_concurrent_builds=2,
                commit_replay_cap=replay_cap,
            )
        else:
            sched = SchedulerConfig(async_build=(policy != "tuned"))
        tuner = SelfTuner(
            TunerConfig(
                controller=ControllerConfig(seed=seed),
                forecast=ForecastConfig(seed=seed),
                scheduler=sched,
            )
        ).attach(idx)
    from benchmarks.common import LatencyHistogram

    ops = 0
    # shared streaming histogram (benchmarks/common.py): same log-bucketed
    # p50/p95/p99.9 machinery bench_gateway uses for its tail rows
    wave_hist = LatencyHistogram()
    replay_bursts = []  # ops rebased at each wave boundary (commit pacing)
    t0 = time.perf_counter()
    for w, (reads, ins, scans) in enumerate(plan):
        w0 = time.perf_counter()
        rep0 = idx.n_replayed_ops
        idx.lookup(reads)
        idx.insert(ins, ins + 1)
        r0 = time.perf_counter()
        idx.range_query_batch(scans, scans + (1 << 24), max_out=256)
        r1 = time.perf_counter()
        ops += len(reads) + len(ins)
        if tuner is not None:
            tuner.observe_inserts(ins)
            tuner.observe_range(len(scans), r1 - r0)
            tuner.after_wave(
                len(reads) + len(ins), time.perf_counter() - w0
            )
        elif policy == "always_retrain" and (w + 1) % retrain_every == 0:
            idx.retrain_full()
        if w >= WARMUP_WAVES:  # cold jit debt stays out of the percentiles
            wave_hist.record(time.perf_counter() - w0)
        replay_bursts.append(int(idx.n_replayed_ops - rep0))
    if tuner is not None:
        tuner.drain()
    dt = time.perf_counter() - t0
    # correctness probe: every policy must agree on what it stored
    _, probe_i, _ = plan[-1]
    f, v = idx.lookup(probe_i)
    assert f.all() and np.array_equal(v, probe_i + 1), policy
    all_keys = np.concatenate([init] + [p[1] for p in plan])
    bursts = np.asarray(replay_bursts[WARMUP_WAVES:])
    nz = bursts[bursts > 0]
    res = {
        "policy": policy,
        "ops_per_s": ops / dt,
        "seconds": dt,
        "p50_wave_ms": wave_hist.percentile(50) * 1e3,
        "p95_wave_ms": wave_hist.percentile(95) * 1e3,
        "p999_wave_ms": wave_hist.percentile(99.9) * 1e3,
        "max_wave_ms": wave_hist.max_s * 1e3,
        # per-wave replay-burst histogram: the commit-pacing evidence —
        # with a cap, max must stay within cap + one logged batch
        "replay_burst_per_wave": [int(b) for b in bursts],
        "replay_burst_waves": int(len(nz)),
        "replay_burst_p50": float(np.percentile(nz, 50)) if len(nz) else 0.0,
        "replay_burst_p95": float(np.percentile(nz, 95)) if len(nz) else 0.0,
        "replay_burst_max": int(nz.max()) if len(nz) else 0,
        "digest": _content_digest(idx, all_keys),
        "index_bytes": int(idx.index_bytes()),
        "n_shards": idx.n_shards,
        "n_retrains": idx.n_retrains,
        "n_splits": idx.n_splits,
        "n_merges": idx.n_merges,
        "epoch": idx.epoch,
        "bmat_size": int(np.asarray(idx.state.bmat.size).sum()),
        "tuner": tuner.stats() if tuner else None,
    }
    if tuner is not None:
        tuner.close()
    return res


def _run_cold(policy: str, init, plan, **kw) -> dict:
    """Run one policy with empty jit caches and no persistent cache."""
    import jax

    from repro import compile_cache

    jax.clear_caches()
    with compile_cache.disabled():
        return _run_policy(policy, init, plan, **kw)


def run(
    n_keys: int = 200_000,
    waves: int = 90,
    batch: int = 4096,
    n_shards: int = 4,
    retrain_every: int = 8,
    seed: int = 0,
    replay_cap: int = 2048,
):
    from benchmarks.common import emit

    init, plan, _ = _workload(n_keys, waves, batch, seed)
    results = {
        p: _run_cold(
            p, init, plan, n_shards=n_shards, retrain_every=retrain_every,
            seed=seed, replay_cap=replay_cap,
        )
        for p in POLICIES
    }
    max_tput = max(r["ops_per_s"] for r in results.values())
    max_mem = max(r["index_bytes"] for r in results.values())
    rows = []
    for policy, res in results.items():
        res["objective"] = (
            ETA * res["ops_per_s"] / max_tput
            - (1 - ETA) * res["index_bytes"] / max_mem
        )
        extra = ""
        if res["tuner"]:
            acts = res["tuner"]["actions"]
            extra = " " + ",".join(f"{k}={v}" for k, v in acts.items() if v)
        rows.append(
            {
                "name": policy,
                "us_per_call": round(1e6 / res["ops_per_s"], 3),
                "derived": (
                    f"{res['ops_per_s']/1e6:.4f} Mops/s, "
                    f"p50={res['p50_wave_ms']:.1f}ms "
                    f"p95={res['p95_wave_ms']:.1f}ms, "
                    f"{res['index_bytes']/2**20:.2f} MiB, "
                    f"R={res['objective']:.3f}, "
                    f"bmat={res['bmat_size']}, S={res['n_shards']}" + extra
                ),
                **{k: v for k, v in res.items() if k != "tuner"},
                "tuner_stats": res["tuner"],
            }
        )
    best_fixed = max(
        results["never_tune"]["objective"],
        results["always_retrain"]["objective"],
    )
    best_fixed_tput = max(
        results["never_tune"]["ops_per_s"],
        results["always_retrain"]["ops_per_s"],
    )
    shift_at = waves // 3
    rows.append(
        {
            "name": "tuned_vs_best_fixed",
            "us_per_call": "",
            "derived": (
                f"objective {results['tuned']['objective']:.3f} vs "
                f"{best_fixed:.3f}, tput ratio "
                f"{results['tuned']['ops_per_s']/best_fixed_tput:.3f}, "
                f"shift_at_wave={shift_at}/{waves}"
            ),
            "tuned_objective": results["tuned"]["objective"],
            "best_fixed_objective": best_fixed,
            "tput_ratio": results["tuned"]["ops_per_s"] / best_fixed_tput,
            "shift_at": shift_at,
            "waves": waves,
        }
    )
    # ISSUE 3 acceptance: the async pipeline must take the maintenance
    # stall off the serving path (p50 wave latency strictly below sync)
    # without changing what the index stores (digests over every key the
    # run inserted must match exactly).
    sync_r, async_r = results["tuned"], results["tuned_async"]
    contents_equal = sync_r["digest"] == async_r["digest"]
    rows.append(
        {
            "name": "async_vs_sync",
            "us_per_call": "",
            "derived": (
                f"p50 {async_r['p50_wave_ms']:.1f}ms vs "
                f"{sync_r['p50_wave_ms']:.1f}ms "
                f"(x{sync_r['p50_wave_ms']/max(async_r['p50_wave_ms'],1e-9):.2f}), "
                f"p95 {async_r['p95_wave_ms']:.1f}ms vs "
                f"{sync_r['p95_wave_ms']:.1f}ms, "
                f"contents_equal={contents_equal}, "
                f"commits={async_r['tuner']['commits']}, "
                f"conflicts={async_r['tuner']['conflicts']}"
            ),
            "sync_p50_wave_ms": sync_r["p50_wave_ms"],
            "async_p50_wave_ms": async_r["p50_wave_ms"],
            "sync_p95_wave_ms": sync_r["p95_wave_ms"],
            "async_p95_wave_ms": async_r["p95_wave_ms"],
            "async_p50_below_sync": (
                async_r["p50_wave_ms"] < sync_r["p50_wave_ms"]
            ),
            "contents_equal": contents_equal,
            "async_commits": async_r["tuner"]["commits"],
            "async_conflicts": async_r["tuner"]["conflicts"],
            "shift_at": shift_at,
            "waves": waves,
        }
    )
    # ISSUE 4 acceptance: 2 concurrent disjoint builds + paced commits must
    # keep per-wave serving-path p95 at or below single-build async (the
    # replay burst was the last unbounded wave cost) while storing exactly
    # what the sync pipeline stores.
    conc_r = results["tuned_concurrent"]
    conc_equal = sync_r["digest"] == conc_r["digest"]
    rows.append(
        {
            "name": "concurrent_vs_async",
            "us_per_call": "",
            "derived": (
                f"p95 {conc_r['p95_wave_ms']:.1f}ms vs "
                f"{async_r['p95_wave_ms']:.1f}ms "
                f"(le_async={conc_r['p95_wave_ms'] <= async_r['p95_wave_ms']}), "
                f"replay bursts p95 {conc_r['replay_burst_p95']:.0f} "
                f"max {conc_r['replay_burst_max']} ops "
                f"(cap={replay_cap}) vs async max "
                f"{async_r['replay_burst_max']}, "
                f"contents_equal={conc_equal}, "
                f"commits={conc_r['tuner']['commits']}, "
                f"drained={conc_r['tuner']['drained']}"
            ),
            "concurrent_p95_wave_ms": conc_r["p95_wave_ms"],
            "async_p95_wave_ms": async_r["p95_wave_ms"],
            "concurrent_p95_le_async": (
                conc_r["p95_wave_ms"] <= async_r["p95_wave_ms"]
            ),
            "concurrent_p50_wave_ms": conc_r["p50_wave_ms"],
            "replay_cap": replay_cap,
            "replay_burst_p50": conc_r["replay_burst_p50"],
            "replay_burst_p95": conc_r["replay_burst_p95"],
            "replay_burst_max": conc_r["replay_burst_max"],
            "async_replay_burst_max": async_r["replay_burst_max"],
            "contents_equal": conc_equal,
            "concurrent_commits": conc_r["tuner"]["commits"],
            "concurrent_drained": conc_r["tuner"]["drained"],
            "concurrent_conflicts": conc_r["tuner"]["conflicts"],
            "max_concurrent_builds": 2,
            "shift_at": shift_at,
            "waves": waves,
        }
    )
    emit(rows, "self_tuning")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-keys", type=int, default=200_000)
    ap.add_argument("--waves", type=int, default=90)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--retrain-every", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replay-cap", type=int, default=2048)
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    run(
        n_keys=args.n_keys, waves=args.waves, batch=args.batch,
        n_shards=args.shards, retrain_every=args.retrain_every,
        seed=args.seed, replay_cap=args.replay_cap,
    )


if __name__ == "__main__":
    main()
