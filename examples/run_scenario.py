"""Run any scenario-zoo network x neuron model end-to-end.

The ``simulate_marmoset``-style driver for the rest of the zoo
(DESIGN.md §12): pick a scenario (``brunel`` with its (g, eta) regime
knobs, the Potjans-Diesmann ``microcircuit``, ``hpc_benchmark``,
``marmoset``) or a NeuronModel demo network (``--model izhikevich`` /
``adex`` / ``poisson``), simulate, and report per-population rates.

    PYTHONPATH=src python examples/run_scenario.py --scenario brunel \
        --scale 0.02 --g 4.5 --eta 2.0 --steps 2000
    PYTHONPATH=src python examples/run_scenario.py --scenario microcircuit \
        --scale 0.02 --steps 1000
    PYTHONPATH=src python examples/run_scenario.py --model izhikevich

With >1 host devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
the same run goes through the distributed shard_map engine on a
(rows, width) mesh - every scenario and model rides the same decomposition,
backends, and spike wires.
"""

import argparse
import sys
sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core import builder, engine, models
from repro.core import distributed as dist
from repro.core import neuron_models
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="brunel",
                    choices=models.available_scenarios())
    ap.add_argument("--model", default=None,
                    help="run the cross-model demo network for this "
                         "NeuronModel instead of --scenario "
                         f"(one of {neuron_models.available_models()})")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--g", type=float, default=None,
                    help="brunel inhibition balance")
    ap.add_argument("--eta", type=float, default=None,
                    help="brunel external drive / threshold rate")
    ap.add_argument("--poisson-input", action="store_true",
                    help="brunel: explicit Poisson emitter population "
                         "(the lif+poisson composite) instead of the "
                         "collapsed per-neuron rate")
    ap.add_argument("--sweep", default="flat",
                    help="execution backend (flat|bucketed|pallas)")
    ap.add_argument("--spike-wire", default="packed")
    args = ap.parse_args()
    use_compile_cache()

    if args.model:
        spec, stdp = models.model_demo(args.model, scale=args.scale)
        tag = f"model_demo({args.model})"
    else:
        kw = {}
        if args.scenario == "brunel":
            if args.g is not None:
                kw["g"] = args.g
            if args.eta is not None:
                kw["eta"] = args.eta
            if args.poisson_input:
                kw["poisson_input"] = True
        spec, stdp = models.get_scenario(args.scenario, scale=args.scale,
                                         **kw)
        tag = args.scenario
    model = neuron_models.get_model(spec.neuron_model)
    table = model.make_param_table(list(spec.groups), dt=models.DT_MS)
    n_dev = jax.device_count()
    print(f"{tag}: {spec.n_neurons} neurons, "
          f"{len(spec.populations)} population(s), "
          f"neuron_model={spec.neuron_model}, {n_dev} device(s)")

    if n_dev > 1:
        width = 2 if n_dev % 2 == 0 else 1
        rows = n_dev // width
        mesh = jax.make_mesh((rows, width), ("data", "model"))
        dec = dist.mesh_decompose(spec, rows, width)
        net = dist.prepare_stacked(spec, dec, rows, width)
        dcfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=models.DT_MS, stdp=stdp,
                                       sweep=args.sweep,
                                       neuron_model=spec.neuron_model),
            spike_wire=args.spike_wire)
        step, _ = dist.make_distributed_step(net, mesh, list(spec.groups),
                                             dcfg)
        state = dist.init_stacked_state(net, list(spec.groups),
                                        sweep=args.sweep,
                                        neuron_model=spec.neuron_model)
        jstep = jax.jit(step)
        counts = np.zeros(spec.n_neurons)
        for _ in range(args.steps):
            state, bits = jstep(state)
            b = np.asarray(bits)
            for si, part in enumerate(dec.parts):
                counts[part] += b[si, :part.size]
    else:
        dec = builder.decompose(spec, 1)
        g = builder.build_shards(spec, dec)[0].device_arrays()
        cfg = engine.EngineConfig(dt=models.DT_MS, stdp=stdp,
                                  sweep=args.sweep,
                                  neuron_model=spec.neuron_model)
        state = engine.init_state(g, list(spec.groups), jax.random.key(0),
                                  sweep=args.sweep,
                                  neuron_model=spec.neuron_model)
        step = engine.make_step_fn(g, table, cfg)
        counts = np.zeros(g.n_local)
        for _ in range(args.steps):
            state, bits = step(state)
            counts[:] += np.asarray(bits)
        counts = counts[:spec.n_neurons]

    t_s = args.steps * models.DT_MS * 1e-3
    off = spec.pop_offsets()
    for i, p in enumerate(spec.populations):
        r = counts[off[i]:off[i + 1]].sum() / (p.n * t_s)
        print(f"  {p.name:6s} n={p.n:7d} rate={r:8.2f} Hz")
    print(f"  total: {counts.sum():.0f} spikes over "
          f"{args.steps * models.DT_MS:.0f} ms "
          f"(mean {counts.sum() / (spec.n_neurons * t_s):.2f} Hz)")
    print("ok")


if __name__ == "__main__":
    main()
