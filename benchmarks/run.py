"""Benchmark harness entry point — one section per paper table/figure.

  python -m benchmarks.run [--quick] [--only tableX,...]

Output: ``name,us_per_call,derived`` CSV per row (scaffold contract), plus
JSON under experiments/bench/ consumed by EXPERIMENTS.md.

Sections:
  table2_throughput  — Table 2 (workloads x datasets x 5 indexes + shift)
  fig4_bmat_types    — Fig 4 (RBMAT vs B+MAT crossover)
  fig6a_range        — Fig 6a (range query latency)
  fig6b_memory       — Fig 6b (index memory)
  fig6c_scalability  — Fig 6c (throughput vs init scale)
  rl_tuning          — Section 4 self-tuning agent vs fixed policies
  self_tuning        — online tuning subsystem vs fixed policies under a
                       mid-run distribution shift (ISSUE 2 acceptance)
  gateway            — async request gateway: closed-loop tail latency vs
                       offered load, batched vs batch-size-1 passthrough
                       (ISSUE 7 acceptance)
  locate_sweep       — binsearch/spline/fused locate strategies at
                       S = 1 and S = 4
  pipeline_index     — UpLIF as the framework's doc index
  kernels            — Pallas kernel micro (interpret mode)
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        bench_bmat_types,
        bench_gateway,
        bench_kernels,
        bench_memory,
        bench_pipeline,
        bench_range,
        bench_rl_tuning,
        bench_scalability,
        bench_self_tuning,
        bench_throughput,
    )

    q = args.quick
    sections = {
        "table2_throughput": lambda: bench_throughput.run(
            n_keys=100_000 if q else 400_000, seconds=1.0 if q else 3.0
        ),
        "fig4_bmat_types": lambda: bench_bmat_types.run(
            sizes=(1_000, 10_000, 100_000) if q else (1_000, 10_000, 100_000, 1_000_000)
        ),
        "fig6a_range": lambda: bench_range.run(n_keys=100_000 if q else 400_000),
        "fig6b_memory": lambda: bench_memory.run(
            n_keys=100_000 if q else 400_000, seconds=1.0 if q else 2.0
        ),
        "fig6c_scalability": lambda: bench_scalability.run(
            scales=(50_000, 200_000) if q else (100_000, 400_000, 1_000_000),
            seconds=1.0 if q else 2.0,
        ),
        "rl_tuning": lambda: bench_rl_tuning.run(
            n_keys=100_000 if q else 200_000, episodes=20 if q else 80
        ),
        "self_tuning": lambda: bench_self_tuning.run(
            n_keys=100_000 if q else 200_000, waves=45 if q else 90,
            batch=2048 if q else 4096,
        ),
        "gateway": lambda: bench_gateway.run(
            n_keys=50_000 if q else 100_000,
            n_clients=4_000 if q else 10_000,
            loads=(250, 1000, 4000) if q else (250, 1000, 4000, 16000),
            duration=0.8 if q else 1.2,
        ),
        "locate_sweep": lambda: bench_throughput.run_locate_sweep(
            n_keys=100_000 if q else 200_000, n_iters=7 if q else 11
        ),
        "pipeline_index": lambda: bench_pipeline.run(
            n_docs=4096 if q else 16384
        ),
        "kernels": lambda: bench_kernels.run(
            n_keys=50_000 if q else 200_000
        ),
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in sections.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        fn()
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
