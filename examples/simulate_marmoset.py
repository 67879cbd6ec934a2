"""End-to-end driver for the paper's evaluation case (§IV.B).

Builds the multi-area marmoset-style cortical network, decomposes it with
Area-Processes Mapping + Multisection Division onto a (rows x width) layout,
runs a few hundred ms of biological time with checkpoint/restart, and
reports per-area rates + the spike-exchange traffic split (local vs remote)
that the indegree decomposition buys.

    PYTHONPATH=src python examples/simulate_marmoset.py \
        [--scale 0.002] [--areas 4] [--steps 2000] [--ckpt /tmp/marmoset]

With >1 host devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
the same script runs the shard_map engine on a (rows, width) mesh.
"""

import argparse
import sys
sys.path.insert(0, "src")

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import builder, engine, models, snn
from repro.core import distributed as dist
from repro.utils.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--areas", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--ckpt", default="/tmp/marmoset_ckpt")
    ap.add_argument("--save-every", type=int, default=500)
    ap.add_argument("--spike-wire", default="packed",
                    help="spike-exchange codec: f32|u8|packed|sparse|"
                         "sparse:<rate> (multi-device runs only)")
    ap.add_argument("--spike-wire-remote", default=None,
                    help="codec for the cross-row boundary tier (the "
                         "inter-host hop on a host-aligned mesh); "
                         "default: same as --spike-wire")
    args = ap.parse_args()
    use_compile_cache()

    spec = models.marmoset(scale=args.scale, n_areas=args.areas)
    n_dev = jax.device_count()
    table = snn.make_param_table(list(spec.groups), dt=models.DT_MS)
    mgr = CheckpointManager(args.ckpt, keep=2)
    print(f"marmoset: {spec.n_neurons} neurons, {args.areas} areas, "
          f"{n_dev} device(s)")

    if n_dev > 1:
        width = 2 if n_dev % 2 == 0 else 1
        rows = n_dev // width
        mesh = jax.make_mesh((rows, width), ("data", "model"))
        dec = dist.mesh_decompose(spec, rows, width)
        net = dist.prepare_stacked(spec, dec, rows, width)
        dcfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=models.DT_MS),
            spike_wire=args.spike_wire,
            spike_wire_remote=args.spike_wire_remote)
        step, _ = dist.make_distributed_step(net, mesh, list(spec.groups),
                                             dcfg)
        state = dist.init_stacked_state(net, list(spec.groups))
        print(f"  mesh {rows}x{width}; spike traffic/step/shard: "
              f"area={net.comm_bytes_area}B vs "
              f"global={net.comm_bytes_global}B")
        # what each wire codec would ship per step on THIS decomposition
        # (the sparse ID wire wins below the packed crossover firing rate)
        table_b = {w: dist.wire_bytes_per_step(net, "area", w)
                   for w in ("f32", "u8", "packed", "sparse")}
        split = dist.wire_bytes_split(
            "area", args.spike_wire, args.spike_wire_remote,
            n_shards=net.n_shards, row_width=net.row_width,
            n_local=net.n_local, b_pad=net.b_pad)
        run_tag = args.spike_wire + (
            f"+{args.spike_wire_remote}" if args.spike_wire_remote else "")
        print("  wire bytes/step (area): "
              + "  ".join(f"{w}={b}B" for w, b in table_b.items())
              + f"  [running: {run_tag}: intra-host {split['intra']}B + "
              + f"inter-host {split['inter']}B]")
        jstep = jax.jit(step)
        counts = np.zeros(net.n_shards)
        for i in range(args.steps):
            state, bits = jstep(state)
            if i % args.save_every == args.save_every - 1:
                mgr.save(i + 1, state, blocking=False)
            counts += np.asarray(bits).sum(axis=-1)
        mgr.wait()
        overflow = int(np.asarray(state.wire_overflow).sum())
        if overflow:
            print(f"  WARNING: lossy wire saturated {overflow} time(s) - "
                  f"raise the sparse capacity (e.g. sparse:<rate>)")
        total = counts.sum()
        rate = total / (spec.n_neurons * args.steps * models.DT_MS * 1e-3)
    else:
        dec = builder.decompose(spec, 1, method="random")
        g = builder.build_shards(spec, dec)[0].device_arrays()
        cfg = engine.EngineConfig(dt=models.DT_MS)
        state = engine.init_state(g, list(spec.groups), jax.random.key(0))
        step = engine.make_step_fn(g, table, cfg)
        n_spk = 0
        for i in range(args.steps):
            state, bits = step(state)
            n_spk += int(np.asarray(bits).sum())
            if i % args.save_every == args.save_every - 1:
                mgr.save(i + 1, state, blocking=False,
                         metadata={"step": i + 1})
                print(f"  step {i+1}: checkpoint saved")
        mgr.wait()
        rate = n_spk / (spec.n_neurons * args.steps * models.DT_MS * 1e-3)

    print(f"  simulated {args.steps * models.DT_MS:.0f} ms, "
          f"mean rate = {rate:.2f} Hz")
    # restart proof: restore the latest checkpoint
    last = mgr.latest_step()
    if last:
        _, meta = mgr.restore(state)
        print(f"  restored checkpoint @ step {last} ok")
    print("ok")


if __name__ == "__main__":
    main()
