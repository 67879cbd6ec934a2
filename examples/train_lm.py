"""End-to-end LM training driver: ~45M-param model, a few hundred steps.

Full production loop on CPU scale: deterministic resumable pipeline,
AdamW + clipping + grad accumulation, async checkpoints, supervisor-driven
restart, loss curve report.  (A ~100M+ model trains identically - pass
--d-model 768 --layers 12; CPU wall-clock is the only reason defaults are
smaller.)

    PYTHONPATH=src python examples/train_lm.py --steps 60
"""

import argparse
import sys
sys.path.insert(0, "src")

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import TokenPipeline
from repro.models.model import build_model
from repro.train.loop import make_train_step
from repro.train.optimizer import init_opt_state
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--ckpt", default="/tmp/lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = ModelConfig(
        name=f"lm-{args.d_model}d{args.layers}L", family="dense",
        n_layers=args.layers, d_model=args.d_model,
        n_heads=args.d_model // 64, n_kv_heads=max(args.d_model // 128, 1),
        d_ff=args.d_model * 4, vocab_size=args.vocab, dtype="float32",
        tie_embeddings=True)
    m = build_model(cfg)
    total, _ = cfg.param_count()
    print(f"model {cfg.name}: ~{total/1e6:.1f}M params")

    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, weight_decay=0.01)
    params = m.init(jax.random.key(0))
    opt = init_opt_state(tcfg, params)
    pipe = TokenPipeline(vocab_size=args.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=17)
    mgr = CheckpointManager(args.ckpt, keep=2)
    start = 0
    if args.resume and mgr.latest_step() is not None:
        (params, opt), meta = mgr.restore((params, opt))
        start = meta["step"]
        print(f"resumed from step {start}")

    step_fn = jax.jit(make_train_step(m, tcfg,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1))
    t0 = time.time()
    for i in range(start, args.steps):
        batch = {"tokens": jnp.asarray(pipe.batch(i)["tokens"])}
        params, opt, met = step_fn(params, opt, batch, jnp.asarray(i))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(met['loss']):.4f} "
                  f"gnorm={float(met['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(i-start+1):.2f}s/step)")
        if (i + 1) % 25 == 0:
            mgr.save(i + 1, (params, opt), blocking=False,
                     metadata={"step": i + 1})
    mgr.wait()
    print("ok")


if __name__ == "__main__":
    main()
