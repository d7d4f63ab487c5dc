"""End-to-end driver (the paper is an index/serving system): serve a large
key-value index with batched mixed request waves at sustained throughput,
with the online tuning subsystem — telemetry → forecast → controller →
scheduler (src/repro/tuning/) — maintaining the sharded structure between
waves: the production serving loop of UpLIF (Figure 1b), millions of
operations end to end.

  PYTHONPATH=src python examples/serve_index.py [--keys 1000000]
      [--seconds 8] [--shards 4] [--no-tune] [--async-build]

``--async-build`` routes maintenance through the plan/build/commit
pipeline: shard rebuilds run on the executor thread and land at wave
boundaries, so the serving loop never stalls on a retrain.
"""
import argparse
import time

import numpy as np

from repro import compile_cache
from repro.core import ShardedUpLIF
from repro.data import WORKLOADS, WorkloadRunner, make_dataset
from repro.tuning import SelfTuner

WAVE = 4096  # ops per request wave


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1_000_000)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--dataset", default="wikits")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--no-tune", action="store_true")
    ap.add_argument("--async-build", action="store_true")
    args = ap.parse_args()

    mode = "OFF" if args.no_tune else (
        "ON/async" if args.async_build else "ON/sync"
    )
    print(f"== UpLIF serving driver: {args.keys:,} {args.dataset} keys, "
          f"{args.shards} shards, tuning {mode} ==")
    keys = make_dataset(args.dataset, args.keys)
    runner = WorkloadRunner(keys, init_frac=0.5, batch=WAVE, seed=0)
    t0 = time.time()
    index = ShardedUpLIF(
        runner.init_keys, runner.init_keys + 1, n_shards=args.shards
    )
    print(f"bulk load: {time.time()-t0:.2f}s ({len(runner.init_keys):,} keys, "
          f"index {index.index_bytes()/2**20:.2f} MiB)")

    tuner = None
    if not args.no_tune:
        tuner = (
            SelfTuner.overlapped() if args.async_build else SelfTuner()
        ).attach(index)
    total_ops = 0
    t0 = time.time()
    for wname, wrate in WORKLOADS.items():
        ops = 0
        tw = time.time()
        while time.time() - tw < args.seconds:
            w0 = time.perf_counter()
            reads, ins = runner.next_batch(wrate)
            if len(reads):
                index.lookup(reads)
            if len(ins):
                index.insert(ins, ins + 1)
            ops += len(reads) + len(ins)
            if tuner is not None:
                tuner.observe_inserts(ins)
                tuner.after_wave(
                    len(reads) + len(ins), time.perf_counter() - w0
                )
        dt = time.time() - tw
        total_ops += ops
        m = index.measures()
        print(
            f"{wname:11s} {ops/dt/1e6:7.3f} Mops/s  "
            f"index={index.index_bytes()/2**20:7.2f} MiB  "
            f"bmat={m['bmat_size']:>7,d}  height={m['bmat_height']}  "
            f"shards={index.n_shards}"
        )
    dt = time.time() - t0
    print(f"\nTOTAL: {total_ops:,} ops in {dt:.1f}s "
          f"({total_ops/dt/1e6:.3f} Mops/s sustained), "
          f"{index.n_retrains} retrains, {index.n_splits} splits, "
          f"{index.n_merges} merges, final size {index.size:,} keys")
    if tuner is not None:
        tuner.drain()
        print(f"tuner: {tuner.stats()}")
        tuner.close()


if __name__ == "__main__":
    main()
