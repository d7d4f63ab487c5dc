"""Serve a small LM with batched requests + the UpLIF prefix-cache index
(the paper's technique in the serving substrate): repeated prompts hit the
cache and skip prefill work.

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np

from repro import compile_cache
import repro.core  # noqa: F401
from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import ServeEngine
from repro.serve.engine import Request


def main():
    compile_cache.enable()
    cfg = smoke_config("deepseek-7b")
    params = init_params(cfg, 0)
    eng = ServeEngine(cfg, params, max_len=256)
    rng = np.random.default_rng(0)

    base_prompt = rng.integers(0, cfg.vocab, 48).astype(np.int32)
    waves = [
        [Request(i, base_prompt, 8) for i in range(2)],          # cold + hit
        [Request(10 + i, np.concatenate(                          # shared prefix
            [base_prompt, rng.integers(0, cfg.vocab, 16).astype(np.int32)]
        ), 8) for i in range(3)],
    ]
    for wi, wave in enumerate(waves):
        t0 = time.time()
        done = eng.generate(wave)
        dt = time.time() - t0
        outs = {r.rid: r.out[:4] for r in done}
        print(f"wave {wi}: {len(wave)} reqs in {dt:.2f}s  "
              f"prefix hits={eng.prefix_index.hits} misses={eng.prefix_index.misses}")
        for rid, o in outs.items():
            print(f"  req {rid}: first tokens {o}")
    print(f"prefix index: {eng.prefix_index.memory_bytes()/2**10:.1f} KiB "
          f"for {eng.prefix_index.index.size:,} fingerprints")


if __name__ == "__main__":
    main()
