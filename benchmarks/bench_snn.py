"""Fig. 18 analogue: memory + step-time scaling of the SNN engine.

The paper's headline figure compares CORTEX vs NEST on the marmoset
benchmark across normalized problem sizes (memory per node, wall time).
On this CPU container we reproduce the *shape* of that comparison:

* problem-size scaling of step wall-time and per-shard memory for the
  CORTEX engine across every execution backend (``--backend
  {flat,bucketed,pallas}`` restricts the axis; pallas runs in interpret
  mode off-TPU, so its CPU numbers measure the emulated kernel, not the
  TPU lowering);
* the distributed step across every spike-wire codec and comm mode
  (``--spike-wire`` / ``--comm-mode`` restrict the axes;
  ``--spike-wire-remote`` puts a different codec on the cross-row
  boundary tier) - the end-to-end cost of the SpikeWire
  encode/collective/decode path, with the codec's own wire bytes/step
  split intra/inter-host next to the timing;
* the same step across N REAL local processes (``--processes``, via the
  ``repro.launch.multihost`` launcher - gloo collectives on a
  host-aligned mesh), the multi-host scaling axis;
* Area-Processes Mapping vs Random Equivalent Mapping: remote-mirror
  memory and per-step spike-exchange bytes (the Fig. 8/9/10 quantities,
  computed exactly from the built shards - these are the terms that
  dominate at Fugaku scale).

Results also land as JSON (``--json``, default experiments/bench_snn.json)
so the wire bytes/step ride along with the timings.  ``--quick`` shrinks
every axis to a CI-smoke-sized config.  The wire benchmark shards over
however many devices exist (set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a real mesh).
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# allow `python benchmarks/bench_snn.py --backend ...` without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import backends as backends_mod
from repro.core import builder, engine, models, snn, stdp as stdp_mod
from repro.core import neuron_models as neuron_models_mod
from repro.core.backends import available_backends
from repro.core.distributed import (DistributedConfig, init_stacked_state,
                                    make_distributed_step, mesh_decompose,
                                    prepare_stacked, wire_bytes_per_step,
                                    wire_bytes_split)
from repro.utils.compile_cache import use_compile_cache

DEFAULT_BACKENDS = available_backends()
DEFAULT_WIRES = ("f32", "u8", "packed", "sparse")
DEFAULT_COMM_MODES = ("area", "global")


def _bytes_of_shard(g) -> int:
    tot = 0
    for f in ("pre_idx", "post_idx", "delay", "channel", "weight_init"):
        tot += np.asarray(getattr(g, f)).nbytes
    tot += np.asarray(g.mirror_src_shard).nbytes * 2
    return tot


def _scenario_net(scale, *, model="lif", scenario=None):
    """(spec, stdp, tag) for the step-scaling axes: the hpc verification
    net by default, a named scenario-zoo entry, or the cross-model demo
    network for a NeuronModel (ISSUE: the --model / --scenario axes)."""
    if scenario:
        spec, stdp = models.get_scenario(scenario, scale=scale)
        return spec, stdp, scenario
    if model != "lif":
        spec, stdp = models.model_demo(model, scale=scale,
                                       stdp=(model != "poisson"))
        return spec, stdp, f"demo-{model}"
    spec, stdp = models.hpc_benchmark(scale=scale, stdp=True)
    return spec, stdp, "hpc_benchmark"


def bench_step_scaling(out, backends=DEFAULT_BACKENDS, *, quick=False,
                       model="lif", scenario=None):
    scales = (0.02,) if quick else (0.02, 0.05, 0.1)
    reps = 20 if quick else 100
    for scale in scales:
        spec, stdp, tag = _scenario_net(scale, model=model,
                                        scenario=scenario)
        nmodel = neuron_models_mod.get_model(spec.neuron_model)
        dec = builder.decompose(spec, 1)
        g = builder.build_shards(spec, dec)[0].device_arrays()
        table = nmodel.make_param_table(list(spec.groups), dt=0.1)
        for sweep in backends:
            cfg = engine.EngineConfig(dt=0.1, stdp=stdp, sweep=sweep,
                                      neuron_model=spec.neuron_model)
            # native-layout weights: the measured loop is the resident hot
            # path, not the flat-state compatibility conversion
            st = engine.init_state(g, list(spec.groups), jax.random.key(0),
                                   sweep=sweep,
                                   neuron_model=spec.neuron_model)
            step = engine.make_step_fn(g, table, cfg)
            st, _ = step(st)  # compile+warm
            t0 = time.perf_counter()
            for _ in range(reps):
                st, _ = step(st)
            jax.block_until_ready(st.v_m if hasattr(st, "v_m")
                                  else st.neurons.v_m)
            us = (time.perf_counter() - t0) / reps * 1e6
            out(f"snn_step/{sweep}/{tag}/scale{scale}", us,
                dict(edges=g.n_edges, model=spec.neuron_model,
                     scenario=tag))


def _time(fn, args, reps):
    r = fn(*args)
    jax.block_until_ready(jax.tree.leaves(r)[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(jax.tree.leaves(r)[0])
    return (time.perf_counter() - t0) / reps * 1e6


def bench_profile(out, backends=DEFAULT_BACKENDS, *, quick=False,
                  model="lif", scenario=None):
    """Per-phase hot-path breakdown: sweep / neuron_update / stdp per
    execution backend on one shard (weights in the backend's NATIVE layout,
    as the engine carries them - the loop pays no ``edge_perm``
    conversion), plus the spike-exchange phase through the real shard_map
    collective path.  The ``sweep_plus_stdp`` record is the ISSUE's
    acceptance metric for the fused blocked hot path.  ``model`` /
    ``scenario`` swap the network and the neuron_update dynamics (the
    NeuronModel registry axis); every record carries the model name."""
    scale = 0.02 if quick else 0.1
    reps = 5 if quick else 30
    spec, stdp_params, tag = _scenario_net(scale, model=model,
                                           scenario=scenario)
    if stdp_params is None:
        stdp_params = models.HPC_STDP   # profile the plasticity phase too
    nmodel = neuron_models_mod.get_model(spec.neuron_model)
    g = builder.build_shards(spec, builder.decompose(spec, 1))[0] \
        .device_arrays()
    table = jnp.asarray(nmodel.make_param_table(list(spec.groups), dt=0.1))
    rng = np.random.default_rng(0)
    ring = jnp.asarray((rng.uniform(size=(spec.max_delay, g.n_mirror))
                        < 0.02).astype(np.float32))
    spk = jnp.asarray((rng.uniform(size=g.n_local) < 0.05)
                      .astype(np.float32))
    neurons = nmodel.init_state(g.n_local, np.asarray(g.group_id),
                                list(spec.groups))
    mkey = jax.random.key(0) if nmodel.stochastic else None
    t0j = jnp.asarray(0, jnp.int32)
    traces = stdp_mod.init_traces(g.n_mirror, g.n_local, jnp.float32)
    iex = jnp.asarray(rng.uniform(0, 50, g.n_local).astype(np.float32))
    iin = jnp.asarray(rng.uniform(-50, 0, g.n_local).astype(np.float32))
    for name in backends:
        backend = backends_mod.get_backend(name)
        layout = backend.prepare(g)
        w = backend.to_native_weights(layout, g.weight_init)
        meta = dict(edges=g.n_edges, scale=scale, phase=None,
                    model=spec.neuron_model)

        sweep = jax.jit(lambda w, ring, t: backend.sweep(layout, w, ring, t))
        t5 = jnp.asarray(5, jnp.int32)
        sweep_us = _time(sweep, (w, ring, t5), reps)
        out(f"snn_profile/{name}/sweep", sweep_us,
            dict(meta, phase="sweep"))

        nup = jax.jit(lambda n, iex, iin: backend.neuron_update(
            layout, n, table, iex, iin, model=nmodel, key=mkey, t=t0j))
        out(f"snn_profile/{name}/neuron_update",
            _time(nup, (neurons, iex, iin), reps),
            dict(meta, phase="neuron_update"))

        _, _, arrived = sweep(w, ring, t5)
        supd = jax.jit(lambda w, a, s: backend.stdp_update(
            layout, w, a, s, traces, stdp_params))
        stdp_us = _time(supd, (w, arrived, spk), reps)
        out(f"snn_profile/{name}/stdp", stdp_us,
            dict(meta, phase="stdp"))
        out(f"snn_profile/{name}/sweep_plus_stdp", sweep_us + stdp_us,
            dict(meta, phase="sweep_plus_stdp"))
    _bench_profile_exchange(out, reps)


def _bench_profile_exchange(out, reps):
    """The exchange phase, isolated: encode -> collective(s) -> decode of
    the two-level spike exchange on whatever host mesh exists."""
    from jax.sharding import PartitionSpec as P
    from repro.core.distributed import _exchange

    n_dev = jax.device_count()
    width = 2 if n_dev % 2 == 0 else 1
    rows = n_dev // width
    mesh = jax.make_mesh((rows, width), ("data", "model"))
    spec = models.marmoset(scale=0.004, n_areas=4)
    dec = mesh_decompose(spec, rows, width)
    net = prepare_stacked(spec, dec, rows, width, with_blocked=False)
    consts = dict(
        boundary_slots=jnp.asarray(net.boundary_slots),
        mirror_is_intra=jnp.asarray(net.mirror_is_intra),
        mirror_row_gather=jnp.asarray(net.mirror_row_gather),
        mirror_remote_gather=jnp.asarray(net.mirror_remote_gather),
        mirror_src_flat=jnp.asarray(net.mirror_src_flat),
        mirror_src_idx=jnp.asarray(net.graph["mirror_src_idx"]),
    )
    rng = np.random.default_rng(1)
    bits = jnp.asarray((rng.uniform(size=(net.n_shards, net.n_local))
                        < 0.01).astype(np.float32))
    for mode in ("area", "global"):
        cfg = DistributedConfig(engine=engine.EngineConfig(dt=0.1),
                                comm_mode=mode, spike_wire="packed")
        wire = cfg.wire

        def local(b, g):
            mirror, _ = _exchange(b[0], {k: v[0] for k, v in g.items()},
                                  cfg, wire)
            return mirror[None]

        spec_p = P(("data", "model"))
        ex = jax.jit(jax.shard_map(local, mesh=mesh,
                                   in_specs=(spec_p, spec_p),
                                   out_specs=spec_p, check_vma=False))
        out(f"snn_profile/exchange/{mode}-packed",
            _time(ex, (bits, consts), reps),
            dict(phase="exchange", comm_mode=mode, mesh=f"{rows}x{width}",
                 wire_bytes_step=wire_bytes_per_step(net, mode, "packed")))


def _area_localized_layout(nb, pb, eb, *, max_delay=8, pres_per_block=32,
                           seed=0):
    """Synthetic area-localized blocked layout: block b's edges draw ONLY
    from its own mirror slice (the Area-Processes Mapping premise - a post
    block's indegree sub-graph is its own area's projection).  This is the
    geometry where activity gating has leverage: a quiet area leaves its
    blocks with zero arrivals.  Random dense connectivity (the hpc net)
    de-gates at any realistic rate - every block sees every spike."""
    from repro.core.layout import BlockedGraph
    rng = np.random.default_rng(seed)
    n_local = nb * pb - pb // 2              # ragged tail block, like prod
    n_mirror = nb * pres_per_block
    pre = np.zeros((nb, eb), np.int32)
    post_rel = np.zeros((nb, eb), np.int32)
    delay = np.zeros((nb, eb), np.int32)
    channel = np.zeros((nb, eb), np.int32)
    plastic = np.zeros((nb, eb), bool)
    weight = np.zeros((nb, eb), np.float32)
    for b in range(nb):
        ne = eb - 16
        pre[b, :ne] = rng.integers(b * pres_per_block,
                                   (b + 1) * pres_per_block, ne)
        hi = pb if (b + 1) * pb <= n_local else n_local - b * pb
        post_rel[b, :ne] = rng.integers(0, hi, ne)
        delay[b, :ne] = rng.integers(1, max_delay + 1, ne)
        channel[b, :ne] = rng.integers(0, 2, ne)
        plastic[b, :ne] = rng.uniform(size=ne) < 0.7
        weight[b, :ne] = rng.uniform(1.0, 50.0, ne)  # inside [w_min, w_max]
    bg = BlockedGraph(nb=nb, eb=eb, pb=pb, n_local=n_local,
                      pre_idx=jnp.asarray(pre),
                      post_rel=jnp.asarray(post_rel),
                      delay=jnp.asarray(delay), channel=jnp.asarray(channel),
                      plastic=jnp.asarray(plastic),
                      edge_perm=jnp.asarray(
                          np.arange(nb * eb, dtype=np.int32).reshape(nb, eb)),
                      weight=None)
    flat = lambda a: jnp.asarray(a.reshape(-1))
    layout = backends_mod.EdgeLayout(
        n_local=n_local, n_mirror=n_mirror, max_delay=max_delay,
        pre_idx=flat(pre), post_idx=flat(post_rel), delay=flat(delay),
        channel=flat(channel), plastic=flat(plastic), blocked=bg)
    return layout, jnp.asarray(weight.reshape(-1))


def bench_gate_activity(out, *, quick=False):
    """The pallas:sparse acceptance axis: dense vs activity-gated
    sweep+stdp across active-area fractions on the area-localized layout.

    ``active_fraction`` is the fraction of post blocks whose pre-area is
    firing this step (within an active area neurons fire at a biological
    few-percent-per-step rate; quiet areas are exactly silent).  The gate
    is provisioned per regime the way ``dryrun_snn`` recommends: capacity
    sized to ~1.5x the expected active blocks, floor 2.  At fraction 1.0
    capacity clamps to nb and the backend degenerates to the plain dense
    reduce - the graceful-degradation end of the curve; the prepass cost
    it still pays is the gate's overhead ceiling."""
    if quick:
        nb, pb, eb, reps = 12, 128, 512, 5
        fracs = (1.0, 0.0625)
    else:
        nb, pb, eb, reps = 64, 256, 2048, 10
        fracs = (1.0, 0.25, 0.0625, 0.03125)
    layout, w = _area_localized_layout(nb, pb, eb)
    bg = layout.blocked
    dense = backends_mod.get_backend("pallas")
    params = models.HPC_STDP
    rng = np.random.default_rng(3)
    D, M = layout.max_delay, layout.n_mirror
    traces = stdp_mod.init_traces(M, layout.n_local, jnp.float32)
    t5 = jnp.asarray(5, jnp.int32)
    ppb = M // nb
    for frac in fracs:
        # activity localized to ceil(frac*nb) areas; ~3%/step inside them
        n_act = max(int(np.ceil(frac * nb)), 1)
        act_blocks = rng.choice(nb, size=n_act, replace=False)
        pre_mask = np.zeros(M, np.float32)
        for b in act_blocks:
            pre_mask[b * ppb:(b + 1) * ppb] = 1.0
        ring = jnp.asarray((rng.uniform(size=(D, M)) < 0.03)
                           .astype(np.float32) * pre_mask)
        post_mask = np.zeros(layout.n_local, np.float32)
        for b in act_blocks:
            post_mask[b * pb:min((b + 1) * pb, layout.n_local)] = 1.0
        spk = jnp.asarray((rng.uniform(size=layout.n_local) < 0.05)
                          .astype(np.float32) * post_mask)
        # provision the gate for the regime: capacity ~ 1.5x expected
        # active blocks (solve the gate_capacity policy backwards)
        cap_target = min(max(int(np.ceil(1.5 * frac * nb)), 2), nb)
        k = (bg.nb * bg.eb) / nb
        rate = float(1.0 - (1.0 - min(cap_target / nb, 1.0 - 1e-9))
                     ** (1.0 / k))
        sp = backends_mod.SparsePallasBackend(gate_rate=max(rate, 1e-9),
                                              min_capacity=2)
        cap = sp.gate_capacity(layout)
        for name, be in (("dense", dense), ("sparse", sp)):
            meta = dict(nb=nb, eb=eb, pb=pb, active_fraction=frac,
                        capacity=(cap if name == "sparse" else nb),
                        phase=None)
            if name == "sparse":
                sweep = jax.jit(lambda w, r, t, b=be: b.sweep_with_stats(
                    layout, w, r, t))
                *_, ovf = sweep(w, ring, t5)
                meta["overflow"] = int(ovf)
                _, n_active, _ = be.gate_stats(layout, ring, t5)
                meta["n_active"] = int(n_active)
            else:
                sweep = jax.jit(lambda w, r, t, b=be: b.sweep(
                    layout, w, r, t))
            sweep_us = _time(sweep, (w, ring, t5), reps)
            out(f"snn_gate/{name}/act{frac:g}/sweep", sweep_us,
                dict(meta, phase="sweep"))
            arrived = sweep(w, ring, t5)[2]
            supd = jax.jit(lambda w, a, s, b=be: b.stdp_update(
                layout, w, a, s, traces, params))
            stdp_us = _time(supd, (w, arrived, spk), reps)
            out(f"snn_gate/{name}/act{frac:g}/stdp", stdp_us,
                dict(meta, phase="stdp"))
            out(f"snn_gate/{name}/act{frac:g}/sweep_plus_stdp",
                sweep_us + stdp_us, dict(meta, phase="sweep_plus_stdp"))


def bench_wire_exchange(out, wires=DEFAULT_WIRES,
                        comm_modes=DEFAULT_COMM_MODES, *,
                        remote_wire=None, quick=False, model="lif",
                        scenario=None, backend=None):
    """Distributed step time per (spike-wire codec x comm mode).

    Uses whatever devices this process has (1 is fine: the encode/decode
    work and the payload shapes are identical; only the collective hop is
    degenerate), so the codecs are measured end-to-end through the real
    shard_map step.  ``remote_wire`` puts a different codec on the
    cross-row boundary tier (the inter-host hop under a host-aligned
    mesh); the JSON records split the wire bytes intra/inter either way.
    ``scenario``/``model`` swap the network (default: the multi-area
    marmoset case) - e.g. ``--scenario brunel --backend pallas
    --spike-wire sparse`` runs the zoo end-to-end through the sharded
    step; ``backend`` selects the execution backend (default flat).
    """
    n_dev = jax.device_count()
    width = 2 if n_dev % 2 == 0 else 1
    rows = n_dev // width
    mesh = jax.make_mesh((rows, width), ("data", "model"))
    if scenario or model != "lif":
        spec, _, tag = _scenario_net(0.02, model=model, scenario=scenario)
    else:
        spec, tag = models.marmoset(scale=0.004, n_areas=4), "marmoset"
    sweep = backend or "flat"
    needs_blocked = backends_mod.get_backend(sweep).needs_blocked
    dec = mesh_decompose(spec, rows, width)
    net = prepare_stacked(spec, dec, rows, width,
                          with_blocked=needs_blocked)
    reps = 10 if quick else 50
    for mode in comm_modes:
        for wire in wires:
            cfg = DistributedConfig(
                engine=engine.EngineConfig(dt=models.DT_MS, sweep=sweep,
                                           neuron_model=spec.neuron_model),
                comm_mode=mode, spike_wire=wire,
                spike_wire_remote=remote_wire)
            step, _ = make_distributed_step(net, mesh, list(spec.groups),
                                            cfg)
            state = init_stacked_state(net, list(spec.groups), sweep=sweep,
                                       neuron_model=spec.neuron_model)
            jstep = jax.jit(step)
            state, _ = jstep(state)  # compile+warm
            jax.block_until_ready(state.v_m)
            t0 = time.perf_counter()
            for _ in range(reps):
                # block EVERY rep: keeps one step's collectives in flight
                # at a time - async pile-up of N steps x M collectives can
                # deadlock the forced-host-device CPU rendezvous (sync cost
                # is noise against a ~100ms sharded step)
                state, _ = jstep(state)
                jax.block_until_ready(state.v_m)
            us = (time.perf_counter() - t0) / reps * 1e6
            overflow = int(np.asarray(state.wire_overflow).sum())
            split = wire_bytes_split(
                mode, wire, remote_wire, n_shards=net.n_shards,
                row_width=net.row_width, n_local=net.n_local,
                b_pad=net.b_pad)
            wtag = wire if remote_wire is None else f"{wire}+{remote_wire}"
            out(f"snn_wire/{mode}/{wtag}", us,
                dict(wire_bytes_step=split["intra"] + split["inter"],
                     wire_bytes_intra=split["intra"],
                     wire_bytes_inter=split["inter"],
                     mesh=f"{rows}x{width}", overflow=overflow,
                     model=spec.neuron_model, scenario=tag, sweep=sweep))


def bench_multiprocess(out, *, processes: int, devices_per_process: int,
                       backend=None, wires=("packed",),
                       comm_modes=("area",), remote_wire=None, quick=False):
    """Real multi-process step timing through the
    ``repro.launch.multihost`` launcher (N local CPU processes, gloo
    collectives, host-aligned mesh): process 0's per-step timing with the
    intra/inter-host wire-byte split.  The launcher owns all spawn/env
    mechanics (per-child XLA_FLAGS, PYTHONPATH, coordinator)."""
    import tempfile

    import repro.launch.multihost as mh_launch

    steps = 10 if quick else 40
    for mode in comm_modes:
        for wire in wires:
            with tempfile.NamedTemporaryFile(suffix=".json") as f:
                argv = ["--processes", str(processes),
                        "--devices-per-process", str(devices_per_process),
                        "--steps", str(steps), "--bench",
                        "--comm-mode", mode, "--wire", wire,
                        "--out", f.name]
                if backend:
                    argv += ["--sweep", backend]
                if remote_wire:
                    argv += ["--wire-remote", remote_wire]
                rec = mh_launch.run_launcher(
                    mh_launch.build_parser().parse_args(argv))
            tag = wire if remote_wire is None else f"{wire}+{remote_wire}"
            out(f"snn_mp/{mode}/{tag}/p{processes}", rec["us_per_step"],
                dict(processes=processes,
                     devices_per_process=devices_per_process,
                     sweep=rec["sweep"],
                     wire_bytes_intra=rec["wire_bytes_intra"],
                     wire_bytes_inter=rec["wire_bytes_inter"],
                     overflow=rec["overflow"]))


def bench_checkpoint(out, *, quick=False):
    """Checkpoint save/restore overhead (fault-tolerant runtime,
    DESIGN.md §15): a blocking save is D2H + fsync'd atomic commit, a
    restore is read + device_put + prng re-wrap.  ``ckpt_bytes`` /
    ``ckpt_leaves`` are structural (exact across machines - any drift
    means the state schema changed); ``us_per_call`` is the measured
    per-save overhead a supervised run pays every ``--save-every`` steps.
    """
    import tempfile

    from repro.checkpoint.manager import CheckpointManager, network_metadata

    scale = 0.02 if quick else 0.05
    reps = 3 if quick else 10
    spec, stdp, tag = _scenario_net(scale)
    dec = builder.decompose(spec, 1)
    g = builder.build_shards(spec, dec)[0].device_arrays()
    st = engine.init_state(g, list(spec.groups), jax.random.key(0))
    md = network_metadata(spec, seed=0, extra={"step": 0})
    with tempfile.TemporaryDirectory() as tmp:
        mgr = CheckpointManager(tmp, keep=2)
        mgr.save(0, st, metadata=md)          # warm: dirs, fs caches
        t0 = time.perf_counter()
        for i in range(reps):
            mgr.save(i + 1, st, metadata=md)
        save_us = (time.perf_counter() - t0) / reps * 1e6
        d = os.path.join(tmp, f"step_{reps:09d}")
        ckpt_bytes = sum(os.path.getsize(os.path.join(d, n))
                         for n in os.listdir(d) if n.endswith(".npy"))
        ckpt_leaves = len(jax.tree.leaves(st))
        t0 = time.perf_counter()
        for _ in range(reps):
            restored, _ = mgr.restore(st)
        jax.block_until_ready(jax.tree.leaves(restored)[0])
        rest_us = (time.perf_counter() - t0) / reps * 1e6
    shared = dict(ckpt_bytes=ckpt_bytes, ckpt_leaves=ckpt_leaves,
                  model=spec.neuron_model)
    out(f"snn_ckpt/save/{tag}/scale{scale}", save_us, shared)
    out(f"snn_ckpt/restore/{tag}/scale{scale}", rest_us, shared)


def bench_mapping_comparison(out, *, quick=False):
    """Area vs Random mapping: mirrors + spike traffic (paper Fig. 8-10)."""
    scales = (0.004,) if quick else (0.004, 0.008)
    for scale in scales:
        spec = models.marmoset(scale=scale, n_areas=4)
        for method, tag in (("area", "cortex_area"),
                            ("random", "random_equiv")):
            dec = mesh_decompose(spec, n_rows=4, row_width=2, method=method)
            net = prepare_stacked(spec, dec, 4, 2)
            shards = builder.build_shards(spec, dec)
            mem = sum(_bytes_of_shard(g) for g in shards) / len(shards)
            remote = sum(int(g.n_mirror) - int(dec.parts[i].size)
                         for i, g in enumerate(shards))
            comm = (net.comm_bytes_area if method == "area"
                    else net.comm_bytes_global)
            out(f"snn_map/{tag}/scale{scale}", mem,
                dict(remote_mirrors=remote, comm_bytes_step=comm))


_BUILD_SCALING_CODE = """
import dataclasses, json, resource, sys, time
from repro.core import builder, models

def peak_rss_mb():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024

mode, scale = sys.argv[1], float(sys.argv[2])
spec, _ = models.hpc_benchmark(scale=scale, stdp=True)
spec = dataclasses.replace(spec, connectivity="procedural")
dec = builder.decompose(spec, 1)
# a forked child momentarily shares the parent's address space, so the
# kernel's RSS high-water mark starts at the HARNESS's peak, not ours -
# reset it (clear_refs code 5) so VmHWM measures this build alone
try:
    with open("/proc/self/clear_refs", "w") as f:
        f.write("5")
except OSError:
    pass
t0 = time.perf_counter()
shards = builder.build_shards(spec, dec, with_blocked=False,
                              force_materialized=(mode == "materialized"))
us = (time.perf_counter() - t0) * 1e6
print(json.dumps(dict(us=us, peak_rss_mb=round(peak_rss_mb(), 1),
                      edges=shards[0].n_edges, n_neurons=spec.n_neurons)))
"""


def bench_build_scaling(out, *, quick=False):
    """Tentpole axis (DESIGN.md §14): wall-clock + peak RSS of building the
    SAME fixed-indegree network through the materialize-then-route
    pipeline vs the procedural O(owned rows) shard-local build.  Each
    (mode, scale) runs in a fresh subprocess so ``ru_maxrss`` is that
    build's own peak, not the harness's; edge counts are identical across
    modes by construction (analytic fixed indegree), so ``edges`` is an
    exact-diffable field while the RSS/time numbers drift per machine."""
    scales = (0.1, 0.3) if quick else (0.1, 0.3, 0.6)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    # the builds are host-only: the children stay off the accelerator,
    # which this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH")) if p))
    import subprocess
    for scale in scales:
        for mode in ("materialized", "procedural"):
            r = subprocess.run(
                [sys.executable, "-c", _BUILD_SCALING_CODE, mode,
                 str(scale)], env=env, capture_output=True, text=True,
                timeout=600)
            if r.returncode != 0:
                raise RuntimeError(f"build-scaling subprocess failed "
                                   f"({mode}, {scale}): {r.stderr[-2000:]}")
            rec = json.loads(r.stdout.strip().splitlines()[-1])
            out(f"snn_build/{mode}/scale{scale}", rec["us"],
                dict(edges=rec["edges"], n_neurons=rec["n_neurons"],
                     peak_rss_mb=rec["peak_rss_mb"], scale=scale))


def bench_shape_tune(out, *, quick=False):
    """Measured (PB, EB) timings for the autotuner (DESIGN.md §14): time
    the blocked sweep at each feasible candidate shape on the profile
    network, keyed by the shard's degree-distribution signature.  The
    records feed ``autotune.load_measured_timings`` /
    ``block_shapes="measured:<BENCH json>"`` - committed benchmarks become
    the tie-breaker for future builds with the same degree profile."""
    from repro.core import autotune, layout as layout_mod

    scale = 0.02 if quick else 0.1
    reps = 5 if quick else 30
    spec, _, tag = _scenario_net(scale)
    dec = builder.decompose(spec, 1)
    base = builder.build_shards(spec, dec, with_blocked=False)[0]
    sig = autotune.degree_signature(autotune.degrees_from_graphs([base]))
    rng = np.random.default_rng(0)
    ring = (rng.uniform(size=(spec.max_delay, base.n_mirror)) < 0.02) \
        .astype(np.float32)
    for pb in autotune.DEFAULT_PB_CANDIDATES:
        if pb > 4 * base.n_local:
            continue   # degenerate: whole shard in a fraction of a block
        eb = layout_mod.blocked_eb(base, pb=pb)
        g = builder.build_shards(spec, dec, block_shapes=(pb, eb))[0] \
            .device_arrays()
        backend = backends_mod.get_backend("pallas")
        lay = backend.prepare(g)
        w = backend.to_native_weights(lay, g.weight_init)
        sweep = jax.jit(lambda w, r, t: backend.sweep(lay, w, r, t))
        us = _time(sweep, (w, jnp.asarray(ring), jnp.asarray(5, jnp.int32)),
                   reps)
        out(f"shape_tune/{sig}/pb{pb}xeb{eb}", us,
            dict(pb=pb, eb=eb, edges=g.n_edges, scenario=tag, scale=scale))


def bench_gate_tune(out, *, quick=False):
    """Measured gate-capacity data for the pallas:sparse worklist
    (DESIGN.md §13): run the profile network and record, per candidate
    capacity K, the measured saturation (overflow) rate and occupancy of
    the activity gate - ``gate_tune/<signature>/cap{K}`` records keyed
    like ``shape_tune/``.  The committed records feed
    ``autotune.load_measured_gate`` / ``gate_rate="measured:<BENCH json>"``
    so future runs of a same-signature network provision the worklist from
    DATA instead of the firing-rate byte model.  The simulation is
    deterministic (fixed seed), so overflow_rate/occupancy are exact
    perf-trajectory invariants.

    Two networks are measured: the hpc verification net (the profile
    network every other tuned axis keys on) and the area-localized
    marmoset net at quick geometry - the paper's benchmark topology,
    whose exponential-distance connectivity gives the gate a very
    different indegree signature than the uniform hpc net.
    """
    from repro.core import autotune

    # LIF time-to-first-spike under the Poisson drive is ~25 ms (~250
    # steps at dt=0.1): measure the gate over a post-warmup window or
    # every record degenerates to peak_active=0
    scale, n_steps, warm = (0.05, 500, 250) if quick else (0.1, 700, 300)
    m_scale = 0.001 if quick else 0.002
    nets = [_scenario_net(scale) + (scale, "area"),
            models.get_scenario("marmoset", scale=m_scale, n_areas=4)
            + ("marmoset", m_scale, "random")]
    for spec, stdp, tag, net_scale, method in nets:
        # the marmoset net keeps its multi-area structure but lands on
        # ONE shard here (random mapping; area mapping needs >= 1 device
        # per area) - the gate only sees the merged indegree profile
        dec = builder.decompose(spec, 1, method=method)
        g = builder.build_shards(spec, dec)[0].device_arrays()
        nmodel = neuron_models_mod.get_model(spec.neuron_model)
        table = jnp.asarray(
            nmodel.make_param_table(list(spec.groups), dt=0.1))
        cfg = engine.EngineConfig(dt=0.1, stdp=stdp, sweep="flat",
                                  neuron_model=spec.neuron_model)
        sp = backends_mod.get_backend("pallas:sparse")
        lay = sp.prepare(g)
        # signature over the LAYOUT's degrees - exactly what the
        # measured-spec backend computes at gate_capacity time, so
        # records always match
        sig = autotune.degree_signature(
            autotune.degrees_from_graphs([lay]))
        nb = lay.blocked.nb
        step = engine.make_step_fn(g, table, cfg)
        n_active_fn = jax.jit(lambda r, t: sp.gate_stats(lay, r, t)[1])
        st = engine.init_state(g, list(spec.groups), jax.random.key(0),
                               sweep="flat")
        n_act = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            st, _ = step(st)
            n_act.append(int(n_active_fn(st.ring, st.t)))
        us = (time.perf_counter() - t0) * 1e6 / n_steps
        n_act = np.asarray(n_act)[warm:]
        peak = int(n_act.max())
        model_cap = autotune.gate_capacity(nb, lay.n_edges,
                                           autotune.DEFAULT_GATE_RATE)
        # candidate ladder around the observed peak (plus the model's
        # pick): below-peak points measure the overflow cost curve,
        # at/above-peak points are the zero-overflow provisioning
        # candidates
        caps = sorted({max(peak // 2, 1), max(peak, 1),
                       min(max(int(np.ceil(peak * 1.25)), peak + 1), nb),
                       model_cap})
        for cap in caps:
            out(f"gate_tune/{sig}/cap{cap}", us,
                dict(capacity=cap, nb=nb,
                     overflow_rate=round(float((n_act > cap).mean()), 4),
                     occupancy=round(float(n_act.mean() / max(cap, 1)),
                                     4),
                     peak_active=peak, n_steps=n_steps, warmup=warm,
                     scenario=tag, scale=net_scale))


def bench_surrogate(out, *, quick=False):
    """Differentiable-mode cost axes (DESIGN.md §17).

    Two questions the training subsystem's overhead story rests on:

    * **Step overhead** - surrogate mode's forward trajectory is
      bit-identical to inference, so any step-time gap is the float
      spike ring + custom-JVP dispatch, not different dynamics
      (``snn_surrogate/step/{inference,surrogate}``).
    * **Remat win** - compiled peak TEMP memory of a reverse-mode
      rollout gradient at T=200, naive scan vs chunked
      ``jax.checkpoint`` (``repro.diff.rollout``); the ``us_per_call``
      is the compiled grad's wall time, so the memory/compute trade
      rides along.  ``benchmarks/diff.py`` guards checkpointed < naive
      from the fresh run alone.
    """
    import dataclasses as dataclasses_mod

    from repro.diff import rollout as rollout_mod

    scale = 0.02 if quick else 0.05
    reps = 30 if quick else 100
    spec, _ = models.get_scenario("brunel", scale=scale)
    g = builder.build_shards(spec, builder.decompose(spec, 1))[0] \
        .device_arrays()
    nmodel = neuron_models_mod.get_model(spec.neuron_model)
    table = jnp.asarray(nmodel.make_param_table(list(spec.groups), dt=0.1))
    st0 = engine.init_state(g, list(spec.groups), jax.random.key(0))
    for mode, spike in (("inference", None),
                        ("surrogate", "fast_sigmoid")):
        cfg = engine.EngineConfig(dt=0.1, surrogate=spike,
                                  neuron_model=spec.neuron_model)
        step = engine.make_step_fn(g, table, cfg)
        us = _time(step, (st0,), reps)
        out(f"snn_surrogate/step/{mode}", us,
            dict(n_neurons=g.n_local, edges=g.n_edges, scale=scale,
                 surrogate=spike or "none"))

    n_steps = 200
    cfg = engine.EngineConfig(dt=0.1, surrogate="fast_sigmoid",
                              neuron_model=spec.neuron_model)

    def make_loss(ck):
        def loss(w):
            st = dataclasses_mod.replace(st0, weights=w)
            _, spikes = rollout_mod.rollout(st, g, table, cfg, n_steps,
                                            checkpoint_every=ck)
            return jnp.mean(spikes)
        return loss

    for label, ck in (("naive", None), ("ckpt25", 25)):
        loss = make_loss(ck)
        temp = rollout_mod.grad_peak_memory_bytes(loss, st0.weights)
        us = _time(jax.jit(jax.grad(loss)), (st0.weights,),
                   max(reps // 10, 3))
        out(f"snn_surrogate/rollout_mem/{label}", us,
            dict(temp_bytes=int(temp), n_steps=n_steps,
                 checkpoint_every=ck or 0, n_neurons=g.n_local,
                 scale=scale))


_SESSION_SOLO_CODE = """
import json, sys, time
import jax
from repro.core import builder, engine, models
from repro.core import neuron_models

seed, scale, n_steps = int(sys.argv[1]), float(sys.argv[2]), int(sys.argv[3])
t0 = time.perf_counter()
spec, stdp = models.get_scenario("brunel", scale=scale)
g = builder.build_shards(spec, builder.decompose(spec, 1))[0] \\
    .device_arrays()
nmodel = neuron_models.get_model(spec.neuron_model)
table = jax.numpy.asarray(nmodel.make_param_table(list(spec.groups),
                                                  dt=0.1))
cfg = engine.EngineConfig(dt=0.1, stdp=stdp, sweep="flat",
                          neuron_model=spec.neuron_model)
st = engine.init_state(g, list(spec.groups), jax.random.key(seed),
                       sweep="flat")
run1 = jax.jit(lambda s: engine.run(s, g, table, cfg, n_steps))
_, bits = run1(st)
jax.block_until_ready(bits)
print(json.dumps(dict(s=time.perf_counter() - t0)))
"""


def bench_sessions(out, *, quick=False, n_sessions=8):
    """Multi-tenant serving throughput (DESIGN.md §16): N brunel sessions
    resident in ONE vmapped slot batch (one build, one compile, shared
    consts) vs the same N seeds run as sequential one-shot scripts (each
    paying its own build + jit + scan - today's batch-script workflow).
    Each one-shot run is a FRESH subprocess (the bench_build_scaling
    idiom): an in-process loop of fresh ``jax.jit`` closures undercounts
    the baseline because later compiles hit XLA's in-process caches that
    a real batch script never sees.  The sequential cost is the child's
    full wall-clock (interpreter + imports + build + compile + run - what
    ``python run_one.py`` actually costs); the child also reports its
    post-import compute seconds, recorded as ``seq_compute_s`` with the
    compute-only ratio in ``speedup_vs_sequential_compute`` so both
    accountings are visible.  The acceptance bar is the
    ``speedup_vs_sequential`` field of the batched record: >= 4x
    aggregate steps/sec at N = 8."""
    import subprocess

    from repro.serve.snn import SessionEngine

    scale = 0.01 if quick else 0.02
    n_steps = 50 if quick else 100

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH")) if p))
    seq_s = 0.0       # wall-clock of the one-shot processes
    seq_compute = 0.0  # post-import build+compile+run inside the child
    # each one-shot child takes the device in turn, so they all run before
    # this process touches it (an accelerator takes one process at a time)
    for seed in range(n_sessions):
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, "-c", _SESSION_SOLO_CODE, str(seed),
             str(scale), str(n_steps)], env=env, capture_output=True,
            text=True, timeout=600)
        seq_s += time.perf_counter() - t0
        if r.returncode != 0:
            raise RuntimeError(f"solo-session subprocess failed "
                               f"(seed {seed}): {r.stderr[-2000:]}")
        seq_compute += json.loads(r.stdout.strip().splitlines()[-1])["s"]

    t0 = time.perf_counter()
    eng = SessionEngine(max_sessions=n_sessions, sweep="flat")
    for seed in range(n_sessions):
        eng.create("brunel", seed=seed, scale=scale)
    eng.step_wave(n=n_steps)
    ses_s = time.perf_counter() - t0

    total = n_sessions * n_steps
    seq_sps, ses_sps = total / seq_s, total / ses_s
    out(f"snn_sessions/sequential/s{n_sessions}", seq_s * 1e6 / total,
        dict(n_sessions=n_sessions, n_steps=n_steps,
             agg_steps_per_sec=round(seq_sps, 1),
             seq_compute_s=round(seq_compute, 2), scenario="brunel",
             scale=scale))
    out(f"snn_sessions/batched/s{n_sessions}", ses_s * 1e6 / total,
        dict(n_sessions=n_sessions, n_steps=n_steps,
             agg_steps_per_sec=round(ses_sps, 1),
             speedup_vs_sequential=round(ses_sps / seq_sps, 2),
             speedup_vs_sequential_compute=round(seq_compute / ses_s, 2),
             scenario="brunel", scale=scale))


def main(out, backend: str | None = None, *, wires=DEFAULT_WIRES,
         comm_modes=DEFAULT_COMM_MODES, remote_wire=None,
         processes: int | None = None, devices_per_process: int = 2,
         quick: bool = False, profile: bool = False, model: str = "lif",
         scenario: str | None = None, ckpt: bool = False,
         sessions: int | None = None, gate_tune: bool = False,
         surrogate: bool = False):
    if sessions:
        # multi-tenant serving axis only: batched vs sequential throughput
        bench_sessions(out, quick=quick, n_sessions=sessions)
        return
    if gate_tune:
        # measured gate-capacity records only (pallas:sparse provisioning)
        bench_gate_tune(out, quick=quick)
        return
    if surrogate:
        # differentiable-mode axis only: surrogate step overhead + the
        # checkpointed-rollout gradient memory trade (DESIGN.md §17)
        bench_surrogate(out, quick=quick)
        return
    if ckpt:
        # checkpoint save/restore overhead only (fault-tolerance axis)
        bench_checkpoint(out, quick=quick)
        return
    if profile:
        # per-phase breakdown mode (sweep / neuron_update / stdp /
        # exchange) - the hot-path drill-down, instead of the scaling axes,
        # plus the dense-vs-gated activity sweep (the pallas:sparse axis)
        bench_profile(out, (backend,) if backend else DEFAULT_BACKENDS,
                      quick=quick, model=model, scenario=scenario)
        bench_gate_activity(out, quick=quick)
        return
    if processes:
        # multi-process axis only: real cross-process collectives through
        # the repro.launch.multihost launcher
        bench_multiprocess(out, processes=processes,
                           devices_per_process=devices_per_process,
                           backend=backend, wires=wires,
                           comm_modes=comm_modes,
                           remote_wire=remote_wire, quick=quick)
        return
    bench_step_scaling(out, (backend,) if backend else DEFAULT_BACKENDS,
                       quick=quick, model=model, scenario=scenario)
    bench_wire_exchange(out, wires, comm_modes, remote_wire=remote_wire,
                        quick=quick, model=model, scenario=scenario,
                        backend=backend)
    bench_mapping_comparison(out, quick=quick)
    bench_build_scaling(out, quick=quick)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="SNN engine scaling benchmark with backend, spike-wire "
                    "and comm-mode axes")
    ap.add_argument("--backend", default=None,
                    help="restrict the step benchmark to one execution "
                         "backend (any registered name or variant: flat|"
                         "bucketed|pallas|pallas:auto|pallas:sparse|"
                         "pallas:sparse:<rate>; default: all registered)")
    ap.add_argument("--model", default="lif",
                    help="NeuronModel registry axis (lif|izhikevich|adex|"
                         "poisson): run the cross-model demo network with "
                         "these dynamics; records carry the model name")
    ap.add_argument("--scenario", default=None,
                    help="scenario-zoo network for the step/wire benches "
                         "(hpc_benchmark|brunel|microcircuit|marmoset); "
                         "overrides --model's demo net")
    ap.add_argument("--spike-wire", default=None,
                    help="restrict the wire benchmark to one codec "
                         "(f32|u8|packed|sparse|sparse:<rate>; default: "
                         "all registered)")
    ap.add_argument("--spike-wire-remote", default=None,
                    help="codec for the cross-row boundary tier (the "
                         "inter-host hop) - e.g. packed intra + sparse "
                         "inter; default: same as --spike-wire")
    ap.add_argument("--comm-mode", default=None,
                    choices=DEFAULT_COMM_MODES,
                    help="restrict the wire benchmark to one comm mode "
                         "(default: area and global)")
    ap.add_argument("--processes", type=int, default=None,
                    help="run the wire benchmark across N REAL local "
                         "processes via the repro.launch.multihost "
                         "launcher (skips the in-process axes)")
    ap.add_argument("--devices-per-process", type=int, default=2,
                    help="forced host devices per process for --processes")
    ap.add_argument("--ckpt", action="store_true",
                    help="checkpoint save/restore overhead only "
                         "(fault-tolerant runtime axis, DESIGN.md §15)")
    ap.add_argument("--sessions", type=int, default=None, metavar="N",
                    help="multi-tenant serving axis only: N resident "
                         "sessions through ONE vmapped slot batch vs N "
                         "sequential one-shot runs (DESIGN.md §16)")
    ap.add_argument("--gate-tune", action="store_true",
                    help="measured gate-capacity records only "
                         "(gate_tune/<sig>/cap{K}: overflow rate + "
                         "occupancy per candidate worklist capacity)")
    ap.add_argument("--surrogate", action="store_true",
                    help="differentiable-mode axis only: surrogate vs "
                         "inference step overhead + naive vs checkpointed "
                         "rollout gradient peak memory at T=200")
    ap.add_argument("--quick", action="store_true",
                    help="tiny config: smallest scales, few reps (CI smoke)")
    ap.add_argument("--profile", action="store_true",
                    help="per-phase hot-path breakdown (sweep / "
                         "neuron_update / stdp / exchange) instead of the "
                         "scaling axes; JSON records carry a 'phase' field")
    ap.add_argument("--json", default="experiments/bench_snn.json",
                    help="write records (incl. wire bytes/step) as JSON; "
                         "'' disables")
    args = ap.parse_args()
    use_compile_cache()
    # fail fast, before the step-scaling phase runs
    neuron_models_mod.get_model(args.model)
    if args.backend:
        backends_mod.get_backend(args.backend)
    if args.scenario and args.scenario not in models.available_scenarios():
        ap.error(f"unknown --scenario {args.scenario!r}; available: "
                 f"{models.available_scenarios()}")
    if args.spike_wire:
        from repro.core.wire import get_wire
        get_wire(args.spike_wire)
    if args.spike_wire_remote:
        from repro.core.wire import get_wire
        get_wire(args.spike_wire_remote)

    records = []

    def _out(name, us, derived=None):
        derived = derived or {}
        records.append(dict(name=name, us_per_call=round(us, 2), **derived))
        extra = ";".join(f"{k}={v}" for k, v in derived.items())
        print(f"{name},{us:.2f},{extra}", flush=True)

    print("name,us_per_call,derived")
    main(_out, args.backend,
         wires=(args.spike_wire,) if args.spike_wire
         else (("packed",) if args.processes else DEFAULT_WIRES),
         comm_modes=(args.comm_mode,) if args.comm_mode
         else (("area",) if args.processes else DEFAULT_COMM_MODES),
         remote_wire=args.spike_wire_remote,
         processes=args.processes,
         devices_per_process=args.devices_per_process,
         quick=args.quick, profile=args.profile,
         model=args.model, scenario=args.scenario, ckpt=args.ckpt,
         sessions=args.sessions, gate_tune=args.gate_tune,
         surrogate=args.surrogate)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"-> {args.json}")
