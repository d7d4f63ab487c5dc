"""Train a small LM end to end with the full production stack: UpLIF-backed
data pipeline, microbatched AdamW train_step, fault-tolerant loop with atomic
checkpointing (kill it mid-run and re-launch — it resumes exactly).

  PYTHONPATH=src python examples/train_lm.py [--steps 60] [--arch deepseek-7b]
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro import compile_cache
import repro.core  # noqa: F401 — x64 (index subsystem)
from repro.configs import smoke_config
from repro.data.pipeline import PackedCorpus, PipelineConfig
from repro.models import init_params
from repro.train.loop import LoopConfig, run as run_loop
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.step import make_train_step


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    cfg = dataclasses.replace(
        cfg, d_model=args.d_model, n_layers=args.layers,
        d_ff=args.d_model * 3, vocab=2048,
        n_heads=max(cfg.n_heads, 4), head_dim=args.d_model // 4,
        n_kv_heads=min(cfg.n_kv_heads, 4),
    )
    print(f"== training {cfg.name}-smoke ({cfg.n_params()/1e6:.1f}M params) ==")

    corpus = PackedCorpus(
        PipelineConfig(vocab=cfg.vocab, seq_len=256, global_batch=8,
                       n_docs=2048)
    )
    print(f"corpus: {corpus.total_tokens:,} tokens, UpLIF doc index "
          f"({corpus.index.index_bytes()/2**10:.1f} KiB)")

    params = init_params(cfg, 0)
    opt = init_opt_state(params)
    specs = jax.tree_util.tree_map(lambda _: None, params)
    step_fn = jax.jit(make_train_step(
        cfg, lambda t, k: t, specs,
        AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps), nm=1
    ))

    def next_batch(step):
        b = corpus.batch(step)
        import jax.numpy as jnp
        return {"tokens": jnp.asarray(b["tokens"])}

    res = run_loop(
        step_fn, params, opt, next_batch,
        LoopConfig(total_steps=args.steps, ckpt_every=20,
                   ckpt_dir=args.ckpt, async_ckpt=True, log_every=10),
        metadata={"arch": cfg.name},
    )
    print(f"done: loss {res['losses'][0]:.3f} -> {res['final_loss']:.3f}, "
          f"{res['median_step_s']*1e3:.0f} ms/step, "
          f"stragglers flagged: {res['stragglers']}")


if __name__ == "__main__":
    main()
