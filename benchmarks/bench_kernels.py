"""Kernel-path microbenchmarks (CPU-executable proxies).

The Pallas kernels themselves only run in interpret mode here (Python-speed,
not meaningful to time); what we CAN measure on CPU is the XLA formulation
they were derived from - the fused flat sweep and LIF chain - across sizes,
plus the blocked-layout conversion cost.  On TPU the same harness times the
compiled kernels (interpret=False).
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# allow `python benchmarks/bench_kernels.py` without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import backends, builder, models, snn
from repro.core.autotune import autotune_report
from repro.core.layout import blocked_layout
from repro.utils.compile_cache import use_compile_cache


def bench_sweep_sizes(out, *, quick=False):
    """Sweep-only step time per execution backend (registry dispatch)."""
    sizes = ((0.02, "small"),) if quick else ((0.02, "small"),
                                              (0.08, "medium"))
    for scale, tag in sizes:
        spec, _ = models.hpc_benchmark(scale=scale, stdp=False)
        g = builder.build_shards(spec, builder.decompose(spec, 1))[0] \
            .device_arrays()
        ring = jnp.zeros((spec.max_delay, g.n_mirror), jnp.float32)
        w = g.weight_init

        for name in ("flat", "bucketed", "pallas"):
            backend = backends.get_backend(name)
            layout = backend.prepare(g)

            @jax.jit
            def sweep(ring, t):
                return backend.sweep(layout, w, ring, t)

            r = sweep(ring, jnp.asarray(5, jnp.int32))
            jax.block_until_ready(r)
            n = 20 if quick else 200
            t0 = time.perf_counter()
            for i in range(n):
                r = sweep(ring, jnp.asarray(i % spec.max_delay, jnp.int32))
            jax.block_until_ready(r)
            us = (time.perf_counter() - t0) / n * 1e6
            out(f"kernel_proxy/synaptic_sweep/{name}/{tag}", us,
                f"edges={g.n_edges};edges_per_us={g.n_edges/us:.0f}")


def bench_blocked_layout(out, *, quick=False):
    """Build-time flat -> post-block ELL conversion (vectorized scatter)."""
    sizes = ((0.05, "small"),) if quick else ((0.05, "small"),
                                              (0.2, "medium"))
    for scale, tag in sizes:
        spec, _ = models.hpc_benchmark(scale=scale, stdp=False)
        g = builder.build_shards(spec, builder.decompose(spec, 1),
                                 with_blocked=False)[0]
        blocked_layout(g)  # warm numpy caches
        n = 3 if quick else 20
        t0 = time.perf_counter()
        for _ in range(n):
            bg = blocked_layout(g)
        us = (time.perf_counter() - t0) / n * 1e6
        out(f"kernel_proxy/blocked_layout/{tag}", us,
            f"edges={g.n_edges};nb={bg.nb};eb={bg.eb}")


def bench_autotune(out, *, quick=False):
    """Chosen (PB, EB) vs the fixed defaults per shard-degree distribution
    (single-shard and a stacked multi-shard set), with the padded-slot and
    VMEM model terms that drove the choice."""
    sizes = ((0.02, 1, "small-1dev"),) if quick else (
        (0.02, 1, "small-1dev"), (0.1, 1, "medium-1dev"),
        (0.05, 4, "small-4dev"))
    for scale, n_dev, tag in sizes:
        spec, _ = models.hpc_benchmark(scale=scale)
        shards = builder.build_shards(spec, builder.decompose(spec, n_dev),
                                      with_blocked=False)
        rep = autotune_report(shards)
        out(f"kernel_proxy/autotune/{tag}", rep["padded_slots"],
            f"pb={rep['pb']};eb={rep['eb']};"
            f"default=({rep['default_pb']},{rep['default_eb']});"
            f"slots_vs_default={rep['slots_vs_default']};"
            f"pad_ratio={rep['pad_ratio']};"
            f"default_pad_ratio={rep['default_pad_ratio']};"
            f"vmem_kib={rep['vmem_kib']};feasible={rep['feasible']}")


def bench_lif_chain(out, *, quick=False):
    for n in ((4096,) if quick else (4096, 65536)):
        gs = [snn.LIFParams()]
        table = snn.make_param_table(gs, dt=0.1)
        state = snn.init_state(n, np.zeros(n, np.int32), gs)
        zeros = jnp.zeros(n)

        @jax.jit
        def step(s):
            return snn.lif_step(s, table, zeros, zeros)

        s = step(state)
        jax.block_until_ready(s.v_m)
        reps = 20 if quick else 200
        t0 = time.perf_counter()
        for _ in range(reps):
            s = step(s)
        jax.block_until_ready(s.v_m)
        us = (time.perf_counter() - t0) / reps * 1e6
        out(f"kernel_proxy/lif_step/n{n}", us,
            f"neurons_per_us={n/us:.0f}")


def main(out, *, quick: bool = False, autotune: bool = False):
    if autotune:
        # (PB, EB) table only - chosen vs the fixed defaults
        bench_autotune(out, quick=quick)
        return
    bench_sweep_sizes(out, quick=quick)
    bench_lif_chain(out, quick=quick)
    bench_blocked_layout(out, quick=quick)
    bench_autotune(out, quick=quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="kernel-path microbenchmarks (CPU-executable proxies)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny config: smallest sizes, few reps (CI smoke)")
    ap.add_argument("--autotune", action="store_true",
                    help="print only the chosen (PB, EB) table vs the "
                         "fixed defaults (repro.core.autotune)")
    args = ap.parse_args()
    use_compile_cache()
    print("name,us_per_call,derived")
    main(lambda name, us, derived="": print(f"{name},{us:.2f},{derived}",
                                            flush=True),
         quick=args.quick, autotune=args.autotune)
