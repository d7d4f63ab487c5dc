"""Paper Table 2: throughput across workloads x datasets x indexes.

Scaled from the paper's 100M-key / 64-core setting to this host (default
500k init keys, single core, batched ops) — we validate the paper's
*relative* claims: (1) UpLIF >= learned baselines with the gap widening as
write rate grows, (2) all learned indexes beat B+Tree on reads, (3) UpLIF
stays robust under distribution shift (Section 5.3).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, index_classes
from repro.core import ShardedUpLIF, UpLIF
from repro.core.uplif import UpLIFConfig
from repro.data import WORKLOADS, WorkloadRunner, make_dataset
from repro.kernels.ops import MAX_VMEM_KEYS

DATASETS = ("wikits", "logn", "fb")


def run_sharded(
    n_keys: int = 400_000, batch: int = 8192, n_iters: int = 15, seed: int = 0
):
    """Router vs single shard: batched lookup + insert throughput.

    This is the scaling-layer measurement the refactor exists for: the
    sharded rows run the SAME flat jitted programs as the single shard
    (fops §stacked adds only shard-offset index arithmetic), so S shards
    cost one dispatch. Variants are measured in interleaved rounds and
    reported as medians so host noise cannot bias the comparison; the
    delta buffer is presized for the whole insert stream so timed batches
    never hit a capacity-growth recompile."""
    rng = np.random.default_rng(seed)
    keys = make_dataset("wikits", n_keys, seed)
    init = keys[::2]
    fresh = np.setdiff1d(keys, init)
    rng.shuffle(fresh)
    cfg = UpLIFConfig(bmat_capacity=n_keys)
    variants = (("UpLIF", 1), ("ShardedUpLIF-2", 2), ("ShardedUpLIF-4", 4))
    indexes = {
        name: (
            UpLIF(init, init + 1, cfg)
            if s == 1
            else ShardedUpLIF(init, init + 1, cfg, n_shards=s)
        )
        for name, s in variants
    }

    # -- batched lookup (interleaved rounds, median) -------------------------
    qs = rng.choice(init, batch).astype(np.int64)
    for idx in indexes.values():  # compile outside the timed rounds
        idx.lookup(qs)
    look = {name: [] for name, _ in variants}
    for _ in range(n_iters):
        for name, _ in variants:
            t0 = time.perf_counter()
            indexes[name].lookup(qs)
            look[name].append(time.perf_counter() - t0)

    # -- batched insert (distinct fresh batches, interleaved) ----------------
    chunks = [
        fresh[i : i + batch] for i in range(0, len(fresh) - batch, batch)
    ]
    warm, timed = chunks[:2], chunks[2 : 2 + max(n_iters // 2, 6)]
    for idx in indexes.values():
        for c in warm:
            idx.insert(c, c + 1)
    ins = {name: [] for name, _ in variants}
    for c in timed:
        for name, _ in variants:
            t0 = time.perf_counter()
            indexes[name].insert(c, c + 1)
            ins[name].append(time.perf_counter() - t0)

    rows = []
    for op, samples in (("lookup", look), ("insert", ins)):
        for name, n_shards in variants:
            ts = sorted(samples[name])
            dt = ts[len(ts) // 2]
            rows.append(
                {
                    "name": f"{op}/{name}",
                    "us_per_call": round(1e6 * dt, 3),
                    "derived": f"{batch / dt / 1e6:.4f} Mops/s",
                    "mops": batch / dt / 1e6,
                    "op": op,
                    "index": name,
                    "n_shards": n_shards,
                    "batch": batch,
                }
            )
    emit(rows, "sharded_router")
    return rows


def run_locate_sweep(
    n_keys: int = 200_000, batch: int = 8192, n_iters: int = 11, seed: int = 0
):
    """Locate-strategy sweep: lookup + insert throughput of the
    binsearch / spline / fused search plans over identical index builds,
    single-shard AND stacked (S=4 — the stacked fused path runs all shards
    in ONE kernel launch via per-query shard base offsets; its adapters
    split the int64 keys into (hi, lo) halves inside every dispatch).
    Interleaved rounds, medians; off-TPU the fused rows run the kernels in
    interpret mode, so they prove the wiring rather than the TPU speed.

    Every variant stays inside the fused kernels' shape guard: a delta
    buffer of at most MAX_VMEM_KEYS keys in all, and radix_bits=14 so the
    S=4 radix tables fit. Each row is checked against the strategy the
    index reports before and after its timed rounds, so a row named fused
    never times the spline path."""
    rng = np.random.default_rng(seed)
    keys = make_dataset("wikits", n_keys, seed)
    init = keys[::2]
    fresh = np.setdiff1d(keys, init)
    rng.shuffle(fresh)
    variants = [
        (f"{strat}/S={s}", strat, s)
        for strat in ("binsearch", "spline", "fused")
        for s in (1, 4)
    ]
    indexes = {}
    for name, strat, s in variants:
        cfg = UpLIFConfig(
            bmat_capacity=min(n_keys, MAX_VMEM_KEYS), radix_bits=14,
            locate=strat,
        )
        indexes[name] = (
            UpLIF(init, init + 1, cfg)
            if s == 1
            else ShardedUpLIF(init, init + 1, cfg, n_shards=s)
        )

    def check_strategies():
        for name, strat, s in variants:
            idx = indexes[name]
            ran = (
                (idx.locate_strategy(),) if s == 1 else idx.shard_locate()
            )
            assert set(ran) == {strat}, f"{name} runs {ran}"

    check_strategies()
    qs = rng.choice(init, batch).astype(np.int64)
    for idx in indexes.values():
        idx.lookup(qs)  # compile outside the timed rounds
    look = {name: [] for name, _, _ in variants}
    for _ in range(n_iters):
        for name, _, _ in variants:
            t0 = time.perf_counter()
            indexes[name].lookup(qs)
            look[name].append(time.perf_counter() - t0)

    chunks = [
        fresh[i: i + batch] for i in range(0, len(fresh) - batch, batch)
    ]
    warm, timed = chunks[:2], chunks[2: 2 + max(n_iters // 2, 4)]
    for idx in indexes.values():
        for c in warm:
            idx.insert(c, c + 1)
    ins = {name: [] for name, _, _ in variants}
    for c in timed:
        for name, _, _ in variants:
            t0 = time.perf_counter()
            indexes[name].insert(c, c + 1)
            ins[name].append(time.perf_counter() - t0)
    check_strategies()

    rows = []
    for op, samples in (("lookup", look), ("insert", ins)):
        med = {
            name: sorted(samples[name])[len(samples[name]) // 2]
            for name, _, _ in variants
        }
        for name, strat, s in variants:
            dt = med[name]
            rows.append(
                {
                    "name": f"{op}/{name}",
                    "us_per_call": round(1e6 * dt, 3),
                    "derived": f"{batch / dt / 1e6:.4f} Mops/s",
                    "mops": batch / dt / 1e6,
                    "op": op,
                    "strategy": strat,
                    "n_shards": s,
                    "batch": batch,
                    "speedup_vs_binsearch": round(
                        med[f"binsearch/S={s}"] / dt, 3
                    ),
                }
            )
    emit(rows, "locate_sweep")
    return rows


def run(n_keys: int = 400_000, seconds: float = 3.0, seed: int = 0):
    rows = []
    workloads = dict(WORKLOADS)
    for wname, wrate in workloads.items():
        for ds in DATASETS:
            keys = make_dataset(ds, n_keys, seed)
            for iname, cls in index_classes().items():
                runner = WorkloadRunner(keys, init_frac=0.5, seed=seed)
                idx = cls(runner.init_keys, runner.init_keys + 1)
                res = runner.run(idx, wrate, seconds=seconds)
                rows.append(
                    {
                        "name": f"{wname}/{ds}/{iname}",
                        "us_per_call": round(1e6 * res.seconds / res.ops, 3),
                        "derived": f"{res.mops:.4f} Mops/s",
                        "mops": res.mops,
                        "workload": wname,
                        "dataset": ds,
                        "index": iname,
                        "index_bytes": res.index_bytes,
                    }
                )
    # distribution shift (Section 5.3): write-heavy on unseen upper range
    for ds in DATASETS:
        keys = make_dataset(ds, n_keys, seed)
        for iname, cls in index_classes().items():
            runner = WorkloadRunner(
                keys, init_frac=0.5, seed=seed, distribution_shift=True
            )
            idx = cls(runner.init_keys, runner.init_keys + 1)
            res = runner.run(idx, 0.5, seconds=seconds)
            rows.append(
                {
                    "name": f"dist_shift/{ds}/{iname}",
                    "us_per_call": round(1e6 * res.seconds / res.ops, 3),
                    "derived": f"{res.mops:.4f} Mops/s",
                    "mops": res.mops,
                    "workload": "dist_shift",
                    "dataset": ds,
                    "index": iname,
                    "index_bytes": res.index_bytes,
                }
            )
    emit(rows, "table2_throughput")
    rows.extend(run_sharded(n_keys=n_keys, seed=seed))
    # locate_sweep is its own harness section now (benchmarks/run.py) so
    # the decomposition comparison can be re-measured without Table 2
    return rows


if __name__ == "__main__":
    run()
