import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Production-scale dry-run + roofline for the CORTEX SNN engine itself.

The LM cells prove the substrate; THIS is the paper's own workload at the
paper's own scale: the marmoset benchmark's "normalized problem size 1"
(1M neurons, 3.8B synapses) and beyond, decomposed onto the production
meshes.  Graphs are never materialized - the step lowers from
ShapeDtypeStructs whose shapes come from the decomposition arithmetic
(edges/shard, mirrors/shard, boundary widths), exactly like the LM dry-run.

Reports per (scale x mesh x wire-encoding): compile ok, per-device memory,
the three roofline terms, and the spike-exchange traffic for f32 vs packed
wires (§Perf iteration on the paper's own bottleneck).

    PYTHONPATH=src python -m repro.launch.dryrun_snn
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import snn
from repro.core.distributed import (DistributedConfig, DistState,
                                    make_raw_distributed_step,
                                    wire_bytes_for_dims, wire_bytes_split)
from repro.core.wire import sparse_packed_crossover_fraction
from repro.core.engine import EngineConfig
from repro.launch.mesh import make_production_mesh
from repro.utils.hlo_analysis import analyze_hlo

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def shard_dims(n_neurons: int, n_edges: int, n_shards: int,
               row_width: int, *, max_delay: int = 64,
               remote_frac: float = 0.25, boundary_frac: float = 0.15):
    """Decomposition arithmetic -> per-shard static shapes (padded)."""
    pad = lambda n, m=128: ((n + m - 1) // m) * m
    n_local = pad(-(-n_neurons // n_shards))
    e = pad(-(-n_edges // n_shards))
    n_mirror = pad(int(n_local * (1.0 + remote_frac)))
    b_pad = pad(max(int(n_local * boundary_frac), 8))
    return dict(n_local=n_local, n_edges=e, n_mirror=n_mirror, b_pad=b_pad,
                max_delay=max_delay)


def state_and_consts_sds(dims, mesh, axes, *, compact: bool = False):
    """SDS stand-ins.  ``compact`` stores the static edge arrays in the
    narrowest dtype their range allows (u16 mirror/post ids, i8 delays and
    channels) - the edge sweep is memory-bound, so edge bytes ARE the step
    time (§Perf iteration)."""
    S = mesh.devices.size
    sh = NamedSharding(mesh, P(axes))
    nl, nm, e, b, D = (dims["n_local"], dims["n_mirror"], dims["n_edges"],
                      dims["b_pad"], dims["max_delay"])
    f32 = jnp.float32
    i32 = jnp.int32
    idx_t = jnp.uint16 if compact and nm <= 65535 else i32
    small_t = jnp.int8 if compact else i32
    sds = lambda shape, dt: jax.ShapeDtypeStruct((S,) + shape, dt,
                                                 sharding=sh)
    state = DistState(
        v_m=sds((nl,), f32), syn_ex=sds((nl,), f32), syn_in=sds((nl,), f32),
        ref_count=sds((nl,), i32), ring=sds((D, nm), f32),
        weights=sds((e,), f32), k_pre=sds((nm,), f32), k_post=sds((nl,), f32),
        prev_bits=sds((nl,), f32), t=sds((), i32),
        key=sds((2,), jnp.uint32), wire_overflow=sds((), i32),
        gate_overflow=sds((), i32))
    consts = dict(
        pre_idx=sds((e,), idx_t), post_idx=sds((e,), idx_t),
        delay=sds((e,), small_t), channel=sds((e,), small_t),
        plastic=sds((e,), jnp.bool_), weight_init=sds((e,), f32),
        group_id=sds((nl,), i32), ext_rate=sds((nl,), f32),
        ext_weight=sds((nl,), f32), ext_thresh=sds((nl,), f32),
        global_id=sds((nl,), i32),
        mirror_src_idx=sds((nm,), idx_t),
        boundary_slots=sds((b,), idx_t), mirror_is_intra=sds((nm,), jnp.bool_),
        mirror_row_gather=sds((nm,), i32),
        mirror_remote_gather=sds((nm,), i32), mirror_src_flat=sds((nm,), i32),
    )
    return state, consts


def run_cell(scale: float, multi_pod: bool, wire: str, *, stdp: bool = True,
             compact: bool = False, overlap: bool = True,
             wire_remote: str | None = None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh.axis_names
    S = mesh.devices.size
    n_neurons = int(1_000_000 * scale)
    n_edges = int(3_800_000_000 * scale)   # paper: 3.8B synapses at size 1
    dims = shard_dims(n_neurons, n_edges, S, mesh.shape["model"])
    from repro.core.models import HPC_STDP
    cfg = DistributedConfig(
        engine=EngineConfig(dt=0.1, stdp=HPC_STDP if stdp else None),
        comm_mode="area", overlap=overlap, axis_names=axes,
        spike_wire=wire, spike_wire_remote=wire_remote)
    groups = [snn.LIFParams(), snn.LIFParams(t_ref=1.0)]
    step = make_raw_distributed_step(mesh, groups, cfg,
                                     max_delay=dims["max_delay"],
                                     n_local=dims["n_local"],
                                     n_mirror=dims["n_mirror"])
    state_sds, consts_sds = state_and_consts_sds(dims, mesh, axes,
                                                 compact=compact)
    t0 = time.time()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state_sds, consts_sds).compile()
    costs = analyze_hlo(compiled.as_text())
    ma = compiled.memory_analysis()
    # analytic per-shard wire traffic from the codec itself (no graph, no
    # HLO needed - the same SpikeWire.bytes_per_step the engine accounts
    # with), vs the packed bitmap on identical dims, split by tier
    # (intra-row/-host vs the cross-row boundary hop)
    split = wire_bytes_split(
        cfg.comm_mode, wire, wire_remote, n_shards=S,
        row_width=mesh.shape["model"], n_local=dims["n_local"],
        b_pad=dims["b_pad"])
    model_bytes = split["intra"] + split["inter"]
    packed_bytes = wire_bytes_for_dims(
        cfg.comm_mode, "packed", n_shards=S, row_width=mesh.shape["model"],
        n_local=dims["n_local"], b_pad=dims["b_pad"])
    rec = dict(
        scale=scale,
        mesh="2x16x16" if multi_pod else "16x16", wire=wire,
        wire_remote=wire_remote or wire,
        compact=compact, overlap=overlap,
        n_neurons=n_neurons, n_edges_global=n_edges, **dims,
        wire_model_bytes=model_bytes,
        wire_bytes_intra=split["intra"], wire_bytes_inter=split["inter"],
        wire_vs_packed=round(model_bytes / packed_bytes, 3),
        crossover_frac=round(
            sparse_packed_crossover_fraction(dims["n_local"]), 5),
        compile_s=round(time.time() - t0, 1),
        peak_gib=round((ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                       / 2**30, 3),
        flops_per_chip=costs.flops,
        traffic_bytes=costs.traffic_bytes,
        collective_bytes=costs.collective_bytes,
        compute_s=costs.flops / PEAK_FLOPS,
        memory_s=costs.traffic_bytes / HBM_BW,
        collective_s=costs.collective_bytes / ICI_BW,
    )
    terms = {k: rec[k] for k in ("compute_s", "memory_s", "collective_s")}
    rec["dominant"] = max(terms, key=terms.get)
    return rec


def measure_firing_rates(*, scale: float = 0.02, steps: int = 400,
                         n_rows: int = 4, row_width: int = 2,
                         seed: int = 0) -> dict:
    """MEASURED per-row firing rates from a small materialized probe run.

    The dry-run cells never materialize a graph, so their sparse-wire
    capacity is a guess; this probe runs the hpc_benchmark verification
    network at a small scale through the single-shard engine, partitions
    the neurons with the SAME mesh decomposition the production cells
    assume, and reports the per-row per-step firing fractions - the
    quantity the sparse ``(count, ids)`` wire must be provisioned for.
    The recommended ``sparse:<rate>`` is the worst row's PEAK fraction
    with 2x headroom (first step of the ROADMAP adaptive-capacity
    follow-on: measure, then provision).
    """
    import jax as _jax

    from repro.core import builder, models
    from repro.core.distributed import mesh_decompose
    from repro.core.engine import EngineConfig as _EngineConfig
    from repro.core import engine as _engine

    spec, _ = models.hpc_benchmark(scale=scale, stdp=False)
    g = builder.build_shards(spec, builder.decompose(spec, 1))[0] \
        .device_arrays()
    table = snn.make_param_table(list(spec.groups), dt=0.1)
    cfg = _EngineConfig(dt=0.1)
    st = _engine.init_state(g, list(spec.groups), _jax.random.key(seed))
    _, spikes = _jax.jit(lambda s: _engine.run(s, g, table, cfg, steps))(st)
    s = np.asarray(spikes)[:, :spec.n_neurons]
    dec = mesh_decompose(spec, n_rows, row_width)
    row_of = np.asarray(dec.owner) // row_width
    rows = []
    for r in range(n_rows):
        sel = s[:, row_of == r]
        frac = sel.mean(axis=1) if sel.shape[1] else np.zeros(s.shape[0])
        rows.append(dict(
            row=r, n=int(sel.shape[1]),
            rate_hz=round(float(sel.mean() / (0.1e-3)), 2),
            frac_mean=round(float(frac.mean()), 6),
            frac_peak=round(float(frac.max()), 6)))
    peak = max(r["frac_peak"] for r in rows)
    recommended = round(min(max(2.0 * peak, 1e-4), 1.0), 5)
    # the same measured peak also provisions the activity-gated sweep
    # (DESIGN.md §13): the gate's worklist capacity follows the identical
    # 2x-headroom policy, reported here in post blocks on THIS probe's
    # geometry so saturation->dense fallback is predictable up front
    from repro.core import autotune
    from repro.core.layout import DEFAULT_PB
    gate_rate = autotune.recommend_gate_rate(peak)
    nb = max(-(-g.n_local // DEFAULT_PB), 1)
    cap = autotune.gate_capacity(nb, g.n_edges, gate_rate)
    return dict(probe_scale=scale, probe_steps=steps, n_rows=n_rows,
                rows=rows, frac_peak=peak,
                recommended_sparse=f"sparse:{recommended}",
                recommended_gate=f"pallas:sparse:{gate_rate:g}",
                gate_rate=gate_rate,
                gate_capacity_blocks=cap, gate_blocks_total=nb)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun_snn.json")
    ap.add_argument("--probe-scale", type=float, default=0.02,
                    help="hpc_benchmark scale of the measured firing probe")
    ap.add_argument("--probe-steps", type=int, default=400)
    args = ap.parse_args()
    results = []
    # (wire, wire_remote, compact, overlap): paper-faithful baseline ->
    # each §Perf iteration -> the final optimized config (overlap OFF once
    # the wire is packed; EXPERIMENTS.md §Perf C3) -> the sparse ID wire
    # (CORTEX's Spikes Broadcast; beats packed below the crossover firing
    # rate) -> the per-tier multi-host split (dense bitmap intra-host,
    # sparse IDs on the inter-host boundary hop; DESIGN.md §11)
    variants = (("f32", None, False, True), ("packed", None, False, True),
                ("packed", None, True, True), ("packed", None, True, False),
                ("sparse", None, True, True),
                ("packed", "sparse", True, True))
    for multi_pod in (False, True):
        for scale in (1.0, 4.0):
            for wire, wire_remote, compact, overlap in variants:
                rec = run_cell(scale, multi_pod, wire, compact=compact,
                               overlap=overlap, wire_remote=wire_remote)
                results.append(rec)
                wtag = (wire if wire_remote is None
                        else f"{wire}+{wire_remote}")
                print(f"[{'2x16x16' if multi_pod else '16x16'}] scale={scale} "
                      f"wire={wtag:13s} compact={int(compact)} "
                      f"overlap={int(overlap)} "
                      f"peak={rec['peak_gib']:.2f}GiB "
                      f"c={rec['compute_s']*1e6:8.1f}us "
                      f"m={rec['memory_s']*1e6:8.1f}us "
                      f"n={rec['collective_s']*1e6:8.1f}us "
                      f"wire_model={rec['wire_model_bytes']}B "
                      f"(intra={rec['wire_bytes_intra']}/"
                      f"inter={rec['wire_bytes_inter']}, "
                      f"{rec['wire_vs_packed']:.2f}x packed) "
                      f"dom={rec['dominant']}", flush=True)
    # packed<->sparse crossover for the marmoset-scale (scale=1) cells: the
    # per-step firing fraction (and Hz at the paper's dt) above which the
    # fixed-capacity ID wire stops beating the 1-bit bitmap
    dt_ms = 0.1
    for rec in results:
        if rec["scale"] == 1.0 and rec["wire"] == "sparse":
            frac = rec["crossover_frac"]
            print(f"[{rec['mesh']}] packed<->sparse crossover @ "
                  f"n_local={rec['n_local']}: firing fraction {frac:.4f}"
                  f"/step = {frac / (dt_ms * 1e-3):.0f} Hz at dt={dt_ms}ms "
                  f"(sparse capacity must stay below this to win)",
                  flush=True)
    # MEASURED firing next to the analytic crossover: a small materialized
    # probe run gives the per-row firing fractions the sparse wire must
    # actually carry, and the recommended "sparse:<rate>" capacity (peak
    # with 2x headroom) - so starved-wire overflow is predictable BEFORE a
    # production run instead of discovered in wire_overflow telemetry.
    probe = measure_firing_rates(scale=args.probe_scale,
                                 steps=args.probe_steps)
    for r in probe["rows"]:
        print(f"[probe] row {r['row']}: n={r['n']} rate={r['rate_hz']}Hz "
              f"frac mean={r['frac_mean']:.5f}/step "
              f"peak={r['frac_peak']:.5f}/step", flush=True)
    from repro.core.wire import get_wire
    print(f"[probe] measured peak firing fraction {probe['frac_peak']:.5f}"
          f"/step -> recommended wire '{probe['recommended_sparse']}' "
          f"(2x headroom; default 'sparse' provisions "
          f"{get_wire('sparse').max_rate:g})", flush=True)
    print(f"[probe] same peak -> recommended sweep backend "
          f"'{probe['recommended_gate']}' (gate worklist "
          f"{probe['gate_capacity_blocks']}/{probe['gate_blocks_total']} "
          f"post blocks on the probe geometry; saturation falls back to "
          f"the dense pass and counts in gate_overflow)", flush=True)
    results.append(dict(name="firing_probe", **probe))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"-> {args.out}")


if __name__ == "__main__":
    main()
