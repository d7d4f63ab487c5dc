"""Quickstart: build an UpLIF index, serve mixed lookups/inserts, tune it.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import UpLIF
from repro.core.rl_agent import AgentConfig, QLearningAgent
from repro.data import WorkloadRunner, make_dataset


def main():
    compile_cache.enable()
    print("== UpLIF quickstart ==")
    keys = make_dataset("logn", 200_000)
    runner = WorkloadRunner(keys, init_frac=0.5, seed=0)
    index = UpLIF(runner.init_keys, runner.init_keys * 10)
    print(f"bulk-loaded {index.n_keys:,} keys  "
          f"alpha={index.alpha:.2f}  index={index.index_bytes()/2**10:.0f} KiB")

    # point lookups
    q = np.random.default_rng(1).choice(runner.init_keys, 4096)
    found, vals = index.lookup(q)
    assert found.all() and (vals == q * 10).all()
    print(f"lookup batch of {len(q)}: all found")

    # updatable: insert unseen keys (in-place via Nullifier placeholders,
    # overflow to the BMAT delta buffer)
    res = runner.run(index, write_rate=0.5, seconds=3.0)
    m = index.measures()
    print(f"write-heavy 3s: {res.mops:.3f} Mops/s  "
          f"bmat={m['bmat_size']} (height {m['bmat_height']})")

    # range queries over the merged view
    lo = int(keys[len(keys) // 3])
    ks, vs = index.range_query(lo, lo + 10**9, max_out=16)
    print(f"range [{lo}, +1e9): first {len(ks)} keys -> {ks[:4]}")

    # self-tuning (Section 4): one RL step
    agent = QLearningAgent(AgentConfig())
    rec = agent.step(index, lambda ix: (
        ix.lookup(np.random.default_rng(2).choice(runner.init_keys, 4096))[0].size
    ))
    print(f"RL agent: action={rec['action']} reward={rec['reward']:.3f}")
    print("OK")


if __name__ == "__main__":
    main()
