"""Pallas kernel microbenchmarks (interpret mode on CPU — correctness-speed
proxy only; TPU timing comes from the roofline terms in §Roofline)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import repro.core  # x64
from benchmarks.common import emit, time_batches
from repro.core import fops
from repro.core.radix_spline import build_radix_spline
from repro.core.uplif import UpLIF, UpLIFConfig
from repro.kernels import ops

LOCATE_STRATEGIES = ("binsearch", "spline", "fused")


def _locate_strategy_rows(n_keys: int, q: int, seed: int):
    """fops-vs-fused locate comparison: ONE index state, three jitted
    lookup programs that differ only in the static locate strategy, so the
    rows measure exactly the search-plan swap (binsearch = B+Tree bisect,
    spline = jnp predict+window bisect, fused = Pallas kernel — interpret
    mode off-TPU, so treat CPU ratios as a wiring proof, not TPU speedup)."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 1 << 48, n_keys).astype(np.int64))
    idx = UpLIF(keys, keys + 1, UpLIFConfig(locate="spline"))
    queries = jnp.asarray(rng.choice(keys, q).astype(np.int64))
    state = idx.fstate
    base_static = idx.fstatic()
    times = {}
    for strat in LOCATE_STRATEGIES:
        static = base_static._replace(locate=strat)
        times[strat] = time_batches(
            lambda s=static: fops.lookup(state, queries, static=s)[
                0
            ].block_until_ready(),
            n_iters=5,
        )
    rows = []
    for strat in LOCATE_STRATEGIES:
        dt = times[strat]
        rows.append({
            "name": f"locate/{strat}",
            "us_per_call": round(dt * 1e6, 1),
            "derived": f"{q/dt/1e6:.3f} Mq/s (interpret)",
            "strategy": strat,
            "n_keys": int(len(keys)),
            "batch": q,
            "speedup_vs_binsearch": round(times["binsearch"] / dt, 3),
            "speedup_vs_spline": round(times["spline"] / dt, 3),
        })
    return rows


def run(n_keys: int = 200_000, q: int = 4096, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows = []
    rows.extend(_locate_strategy_rows(n_keys // 2, q, seed))
    keys = np.unique(rng.integers(0, 1 << 52, n_keys).astype(np.int64))
    pos = np.arange(len(keys), dtype=np.int64) * 2
    model, static = build_radix_spline(keys, pos, max_error=24)
    queries = jnp.asarray(rng.choice(keys, q))

    dt = time_batches(
        lambda: ops.spline_lookup(
            model.table, model.spline_keys, model.spline_pos,
            int(model.shift), queries, static.n_search_iters,
        ).block_until_ready(),
        n_iters=5,
    )
    rows.append({"name": "spline_lookup", "us_per_call": round(dt * 1e6, 1),
                 "derived": f"{q/dt/1e6:.3f} Mq/s (interpret)"})

    slots = jnp.asarray(np.sort(rng.integers(0, 1 << 52, 262144).astype(np.int64)))
    pred = jnp.asarray(
        np.searchsorted(np.asarray(slots), np.asarray(queries)).astype(np.float32)
    )
    dt = time_batches(
        lambda: ops.route_and_search(slots, queries, pred)[0].block_until_ready(),
        n_iters=5,
    )
    rows.append({"name": "tile_search", "us_per_call": round(dt * 1e6, 1),
                 "derived": f"{q/dt/1e6:.3f} Mq/s (interpret)"})

    cap = 65536
    arr = np.full(cap, np.iinfo(np.int64).max, np.int64)
    arr[: cap // 2] = np.sort(rng.integers(0, 1 << 52, cap // 2).astype(np.int64))
    fences = np.concatenate([arr[::16], [np.iinfo(np.int64).max]])
    dt = time_batches(
        lambda: ops.bmat_rank(
            jnp.asarray(arr), jnp.asarray(fences), queries, 16
        ).block_until_ready(),
        n_iters=5,
    )
    rows.append({"name": "bmat_rank", "us_per_call": round(dt * 1e6, 1),
                 "derived": f"{q/dt/1e6:.3f} Mq/s (interpret)"})

    x = jnp.asarray(rng.normal(0, 1, 16384))
    w = jnp.asarray([0.25, 0.5, 0.25])
    mu = jnp.asarray([-1.0, 0.0, 2.0])
    sd = jnp.asarray([0.5, 1.0, 0.7])
    dt = time_batches(
        lambda: ops.gmm_estep(x, w, mu, sd), n_iters=5
    )
    rows.append({"name": "gmm_estep", "us_per_call": round(dt * 1e6, 1),
                 "derived": f"{16384/dt/1e6:.3f} Msamples/s (interpret)"})
    emit(rows, "kernels")
    return rows


if __name__ == "__main__":
    run()
