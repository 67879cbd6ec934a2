"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        [--smoke] [--steps 20] [--mesh 2x2] [--ckpt DIR] [--resume] \
        [--grad-compress int8_ef]

With ``--smoke`` (default on CPU) the reduced config trains for real;
without it the full config is built and the step is compiled against the
production mesh (the dry-run path) before the loop starts - on TPU pods the
same entry point is the real run.  Supervision (checkpoint/restart,
heartbeat) wraps the loop in both modes.
"""

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import TrainConfig
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import auto_mesh
from repro.models.model import build_model
from repro.runtime.fault import HeartbeatMonitor
from repro.train.loop import make_train_step
from repro.train.optimizer import init_opt_state
from repro.utils.compile_cache import use_compile_cache


def parse_mesh(s: str | None):
    if not s:
        return None
    dims = tuple(int(x) for x in s.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return auto_mesh(dims, axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--save-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(
        args.arch)
    m = build_model(cfg)
    tcfg = TrainConfig(optimizer=args.optimizer, lr=args.lr)
    mesh = parse_mesh(args.mesh)

    params = m.init(jax.random.key(0))
    opt = init_opt_state(tcfg, params)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch, seed=0)
    mgr = CheckpointManager(args.ckpt, keep=2) if args.ckpt else None
    monitor = HeartbeatMonitor(1)

    start = 0
    if mgr and args.resume and mgr.latest_step() is not None:
        (params, opt), meta = mgr.restore((params, opt))
        start = meta["step"]
        print(f"resumed @ {start}")

    step_fn = make_train_step(m, tcfg, microbatches=args.microbatches)
    if mesh is not None:
        from repro.sharding import rules
        mesh_cm = rules.use_mesh(mesh)
        mesh_cm.__enter__()
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))

    def make_batch(i):
        b = {"tokens": jnp.asarray(pipe.batch(i)["tokens"])}
        if cfg.family == "audio":
            b["frames"] = jax.random.normal(
                jax.random.key(i), (args.batch, cfg.encoder_seq,
                                    cfg.d_model)) * 0.02
        if cfg.family == "vlm":
            b["patches"] = jax.random.normal(
                jax.random.key(i), (args.batch, cfg.n_prefix_embeds,
                                    cfg.d_model)) * 0.02
        return b

    for i in range(start, args.steps):
        t0 = time.monotonic()
        params, opt, met = jstep(params, opt, make_batch(i),
                                 jnp.asarray(i))
        monitor.observe(0, time.monotonic() - t0)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(met['loss']):.4f} "
                  f"gnorm={float(met['grad_norm']):.3f}")
        if mgr and (i + 1) % args.save_every == 0:
            mgr.save(i + 1, (params, opt), blocking=False,
                     metadata={"step": i + 1})
    if mgr:
        mgr.wait()
    if monitor.stragglers():
        print("stragglers detected:", monitor.stragglers())
    print("done")


if __name__ == "__main__":
    main()
