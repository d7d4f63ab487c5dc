"""Live-traffic driver for the request gateway (DESIGN.md §9): many
concurrent client threads fire single lookup/insert/delete requests at a
`RequestGateway`, which micro-batches them into §7.5 pow2-padded waves
over a tuned `ShardedUpLIF` — the production front end of the serving
story, end to end.

  PYTHONPATH=src python examples/serve_gateway.py [--keys 200000]
      [--clients 64] [--seconds 5] [--no-tune]

Each client thread runs a closed loop (one request in flight, tiny think
time) with a 70/30 read/upsert mix and occasional deletes; `RetryAfter`
backpressure is honored by sleeping the hinted amount. The summary
prints achieved throughput, the p50/p99/p99.9 tail from the shared
streaming histogram, the flush-trigger and pad-width mix, and the
tuner's maintenance/shed counters.
"""
import argparse
import threading
import time

import numpy as np

from repro import compile_cache
from repro.core import ShardedUpLIF
from repro.data import make_dataset
from repro.serve import GatewayConfig, RequestGateway, RetryAfter
from repro.tuning import SelfTuner

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import LatencyHistogram  # noqa: E402


def client_loop(gw, keys, hist, stop, tid, counts):
    rng = np.random.default_rng(1000 + tid)
    n = len(keys)
    while not stop.is_set():
        k = int(keys[rng.integers(n)])
        try:
            p = rng.random()
            if p < 0.70:
                fut = gw.submit_lookup(k)
            elif p < 0.98:
                fut = gw.submit_insert(k, k * 2 + 1)
            else:
                fut = gw.submit_delete(k + 1)  # miss: exercises the path
        except RetryAfter as e:
            counts["rejected"] += 1
            time.sleep(e.retry_after_s)
            continue
        fut.result(30.0)
        hist.record(fut.total_latency_s)
        counts["done"] += 1
        time.sleep(rng.exponential(0.0005))


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=200_000)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--dataset", default="wikits")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--no-tune", action="store_true")
    args = ap.parse_args()

    print(f"== UpLIF gateway driver: {args.keys:,} {args.dataset} keys, "
          f"{args.clients} client threads, tuning "
          f"{'OFF' if args.no_tune else 'ON/async'} ==")
    keys = np.sort(make_dataset(args.dataset, args.keys))
    index = ShardedUpLIF(keys, keys * 2 + 1, n_shards=args.shards)
    # engine defaults: builds overlap serving, commits drain paced
    tuner = None if args.no_tune else SelfTuner.overlapped(
        max_concurrent_builds=2, commit_replay_cap=4096
    ).attach(index)
    gw = RequestGateway(
        index, tuner=tuner,
        config=GatewayConfig(max_batch=1024, max_delay_s=0.002),
    )
    t0 = time.time()
    primed = gw.warmup()
    print(f"warmup: {time.time()-t0:.2f}s, primed widths {primed}")

    hist = LatencyHistogram()
    stop = threading.Event()
    counts = {"done": 0, "rejected": 0}
    threads = [
        threading.Thread(
            target=client_loop, args=(gw, keys, hist, stop, i, counts),
            daemon=True,
        )
        for i in range(args.clients)
    ]
    t0 = time.time()
    for t in threads:
        t.start()
    time.sleep(args.seconds)
    stop.set()
    for t in threads:
        t.join(30.0)
    dt = time.time() - t0
    st = gw.stats()
    gw.close()

    s = hist.summary_ms()
    print(f"\n{counts['done']:,} requests in {dt:.1f}s "
          f"({counts['done']/dt:,.0f} req/s, {counts['rejected']} rejected)")
    print(f"latency p50={s['p50_ms']:.2f}ms p99={s['p99_ms']:.2f}ms "
          f"p99.9={s['p999_ms']:.2f}ms max={s['max_ms']:.1f}ms")
    print(f"waves={st['waves']} mean_batch="
          f"{st['ops']/max(st['waves'],1):.1f} triggers="
          f"{st['flush_triggers']} pads={st['pad_widths']}")
    if tuner is not None:
        print(f"tuner: {tuner.stats()}")
        tuner.close()


if __name__ == "__main__":
    main()
