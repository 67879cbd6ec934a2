"""Quickstart: the two faces of the framework in ~a minute.

1. CORTEX SNN engine - build the balanced random network (paper §IV.A),
   simulate 200 ms, print the firing-rate band.
2. LM stack - one training step of a reduced qwen2.5 config on the
   deterministic synthetic pipeline.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import TrainConfig
from repro.core import builder, engine, models, snn
from repro.data.pipeline import TokenPipeline
from repro.models.model import build_model
from repro.train.loop import make_train_step
from repro.train.optimizer import init_opt_state
from repro.utils.compile_cache import use_compile_cache


def snn_demo():
    print("== CORTEX SNN: balanced random network (hpc_benchmark) ==")
    spec, stdp = models.hpc_benchmark(scale=0.04, stdp=True)
    dec = builder.decompose(spec, 1)
    g = builder.build_shards(spec, dec)[0].device_arrays()
    table = snn.make_param_table(list(spec.groups), dt=models.DT_MS)
    cfg = engine.EngineConfig(dt=models.DT_MS, stdp=stdp)
    state = engine.init_state(g, list(spec.groups), jax.random.key(0))
    state, spikes = jax.jit(
        lambda s: engine.run(s, g, table, cfg, 2000))(state)
    rate = models.firing_rate_hz(np.asarray(spikes), spec.n_neurons)
    print(f"  neurons={spec.n_neurons} edges={g.n_edges} "
          f"steps=2000 (200 ms)")
    print(f"  mean rate = {rate:.2f} Hz (paper band: < 10 Hz, "
          f"asynchronous-irregular)")
    w = np.asarray(state.weights)
    print(f"  STDP weights: min={w.min():.1f} max={w.max():.1f} (bounded)")


def lm_demo():
    print("== LM stack: one train step (reduced qwen2.5) ==")
    cfg = configs.get_smoke("qwen2.5-3b")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3)
    opt = init_opt_state(tcfg, params)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=32,
                         global_batch=4)
    step = jax.jit(make_train_step(m, tcfg), donate_argnums=(0, 1))
    for i in range(3):
        batch = {"tokens": jnp.asarray(pipe.batch(i)["tokens"])}
        params, opt, met = step(params, opt, batch, jnp.asarray(i))
        print(f"  step {i}: loss={float(met['loss']):.3f} "
              f"grad_norm={float(met['grad_norm']):.3f}")


if __name__ == "__main__":
    use_compile_cache()
    snn_demo()
    lm_demo()
    print("ok")
