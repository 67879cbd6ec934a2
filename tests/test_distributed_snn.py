"""Distributed SNN engine: 1-shard vs N-shard bitwise equivalence, all
communication modes, overlap schedule, traffic accounting (paper §III.C).

The shard_map tests need >1 host device, so they run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import builder, models
from repro.core.distributed import (mesh_decompose, prepare_stacked,
                                    wire_bytes_for_dims, wire_bytes_per_step)
from repro.core.wire import get_wire

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_sub(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


EQUIV_CODE = textwrap.dedent("""
    import dataclasses, json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import models, builder, engine, snn
    from repro.core import distributed as dist

    spec, _ = models.hpc_benchmark(scale=0.02, stdp=True)
    groups = [dataclasses.replace(spec.groups[0], i_e=800.0)]
    spec = dataclasses.replace(spec, groups=groups)
    stdp = models.HPC_STDP
    N = 200
    mesh = jax.make_mesh((4, 2), ("data", "model"))

    dec1 = builder.decompose(spec, 1)
    g1 = builder.build_shards(spec, dec1)[0].device_arrays()
    table = snn.make_param_table(list(spec.groups), dt=0.1)
    cfg1 = engine.EngineConfig(dt=0.1, stdp=stdp, external_drive=False)
    st1 = engine.init_state(g1, list(spec.groups), jax.random.key(0))
    _, ref = jax.jit(lambda s: engine.run(s, g1, table, cfg1, N))(st1)
    ref = np.asarray(ref)[:, :spec.n_neurons].astype(bool)

    results = {}
    dec = dist.mesh_decompose(spec, n_rows=4, row_width=2)
    net = dist.prepare_stacked(spec, dec, 4, 2)
    # backend axis: flat across every comm x overlap combo; the pallas and
    # bucketed backends through the SAME distributed code path (registry
    # dispatch) on representative combos.  The pallas rows cover BOTH
    # weight residencies: native blocked state (init with sweep=) and the
    # flat-state compatibility path (per-step edge_perm conversion).
    combos = ([("flat", m, o, True) for m in ("global", "area")
               for o in (False, True)]
              + [("pallas", "area", True, True),
                 ("pallas", "global", False, False),
                 ("bucketed", "area", True, True)])
    for sweep, mode, overlap, native in combos:
        dcfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=0.1, stdp=stdp, sweep=sweep,
                                       external_drive=False),
            comm_mode=mode, overlap=overlap)
        step, _ = dist.make_distributed_step(net, mesh,
                                             list(spec.groups), dcfg)
        state = dist.init_stacked_state(net, list(spec.groups),
                                        sweep=sweep if native else None)
        @jax.jit
        def run(s):
            return jax.lax.scan(lambda s, _: step(s), s, None, length=N)
        _, bits = run(state)
        bits = np.asarray(bits)
        glob = np.zeros((N, spec.n_neurons), bool)
        for si, part in enumerate(dec.parts):
            glob[:, part] = bits[:, si, :part.size]
        results[f"{sweep}-{mode}-{overlap}"] = bool((glob == ref).all())
    results["spiked"] = int(ref.sum())
    print(json.dumps(results))
""")


@pytest.mark.slow
def test_distributed_equivalence_all_modes():
    out = run_sub(EQUIV_CODE)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["spiked"] > 100, "vacuous test - nothing spiked"
    for k, v in res.items():
        if k != "spiked":
            assert v, f"mode {k} diverged from single-shard reference"


DRIVE_CODE = textwrap.dedent("""
    import dataclasses, json
    import numpy as np
    import jax
    from repro.core import builder, engine, models, snn
    from repro.core import distributed as dist

    # the scenario's own Poisson drive (boosted 2x so the short run fires)
    spec, _ = models.hpc_benchmark(scale=0.02, stdp=False)
    pops = [dataclasses.replace(p, ext_rate_hz=p.ext_rate_hz * 2.0)
            for p in spec.populations]
    spec = dataclasses.replace(spec, populations=pops)
    N = 120
    groups = list(spec.groups)
    cfg = engine.EngineConfig(dt=0.1)

    g1 = builder.build_shards(spec, builder.decompose(spec, 1))[0] \\
        .device_arrays()
    table = snn.make_param_table(groups, dt=0.1)
    st1 = engine.init_state(g1, groups, jax.random.key(0))
    _, ref = jax.jit(lambda s: engine.run(s, g1, table, cfg, N))(st1)
    ref = np.asarray(ref)[:, :spec.n_neurons]

    res = {"spiked": int(ref.sum())}
    for rows, width in ((4, 2), (1, 1)):
        mesh = jax.make_mesh((rows, width), ("data", "model"),
                             devices=jax.devices()[:rows * width])
        dec = dist.mesh_decompose(spec, rows, width)
        net = dist.prepare_stacked(spec, dec, rows, width,
                                   with_blocked=False)
        step, _ = dist.make_distributed_step(
            net, mesh, groups, dist.DistributedConfig(engine=cfg))
        state = dist.init_stacked_state(net, groups, mesh=mesh)
        shards = [d.id for a in state.v_m.addressable_shards
                  for d in [a.device]]
        _, bits = jax.jit(lambda s: jax.lax.scan(
            lambda c, _: step(c), s, None, length=N))(state)
        bits = np.asarray(bits)
        glob = np.zeros((N, spec.n_neurons), bool)
        for si, part in enumerate(dec.parts):
            glob[:, part] = bits[:, si, :part.size] > 0
        res[f"{rows}x{width}"] = bool((glob == ref).all())
        res[f"{rows}x{width}_devices"] = len(set(shards))
    print(json.dumps(res))
""")


@pytest.mark.slow
def test_poisson_drive_is_decomposition_invariant():
    """With the scenario's own Poisson drive (not a constant current), a
    4x2 run, a 1x1 run and the single-shard engine spike identically:
    every shard draws one stream per GLOBAL neuron id from a shared key.
    ``init_stacked_state(mesh=)`` places the shards on distinct devices."""
    res = json.loads(run_sub(DRIVE_CODE).strip().splitlines()[-1])
    assert res["spiked"] > 20, "vacuous test - nothing spiked"
    assert res["4x2"] and res["1x1"], res
    assert res["4x2_devices"] == 8 and res["1x1_devices"] == 1, res


WIRE_CODE = textwrap.dedent("""
    import dataclasses, json
    import numpy as np
    import jax
    from repro.core import models, engine
    from repro.core import distributed as dist
    from repro.core.wire import SparseWire, register_wire

    # desynchronized, actually-firing net: Poisson drive boosted 2x keeps
    # per-shard per-step spike counts comfortably below the default sparse
    # capacity while staying in the asynchronous regime (no i_e sync)
    spec, stdp = models.hpc_benchmark(scale=0.02, stdp=True)
    pops = [dataclasses.replace(p, ext_rate_hz=p.ext_rate_hz * 2.0)
            for p in spec.populations]
    spec = dataclasses.replace(spec, populations=pops)
    N = 150
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    dec = dist.mesh_decompose(spec, n_rows=4, row_width=2)
    net = dist.prepare_stacked(spec, dec, 4, 2, with_blocked=False)
    # a deliberately starved sparse wire for the overflow-telemetry leg
    register_wire("tiny", SparseWire(max_rate=0.0, min_capacity=1,
                                     name="tiny"))

    def run(mode, wire, rwire=None):
        cfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=0.1, stdp=stdp),
            comm_mode=mode, spike_wire=wire, spike_wire_remote=rwire)
        step, _ = dist.make_distributed_step(net, mesh, list(spec.groups),
                                             cfg)
        state = dist.init_stacked_state(net, list(spec.groups))
        @jax.jit
        def scan(s):
            return jax.lax.scan(lambda s, _: step(s), s, None, length=N)
        fin, bits = scan(state)
        return np.asarray(bits), int(np.asarray(fin.wire_overflow).sum())

    results = {}
    for mode in ("area", "global"):
        ref, ref_ov = run(mode, "packed")
        results[f"{mode}-spiked"] = int(ref.sum())
        results[f"{mode}-packed-overflow"] = ref_ov
        for wire in ("f32", "u8", "sparse", "sparse:0.5"):
            bits, ov = run(mode, wire)
            results[f"{mode}-{wire}"] = bool((bits == ref).all())
            results[f"{mode}-{wire}-overflow"] = ov
        # per-tier wires: dense bitmap on the intra-row tier, sparse IDs
        # on the cross-row boundary tier (the multi-host default split)
        bits, ov = run(mode, "packed", "sparse")
        results[f"{mode}-packed+sparse"] = bool((bits == ref).all())
        results[f"{mode}-packed+sparse-overflow"] = ov
    # starved capacity: trajectories may legitimately diverge (lossy), but
    # the saturation MUST surface in telemetry
    _, tiny_ov = run("area", "tiny")
    results["tiny-overflow"] = tiny_ov
    print(json.dumps(results))
""")


@pytest.mark.slow
def test_cross_wire_trajectories_and_overflow_telemetry():
    """Every wire codec (dense and sparse ID-based) produces bit-identical
    spike trajectories in both comm modes when capacity holds, with zero
    overflow; a starved sparse wire surfaces its saturation in
    ``DistState.wire_overflow`` instead of failing silently."""
    out = run_sub(WIRE_CODE)
    res = json.loads(out.strip().splitlines()[-1])
    for mode in ("area", "global"):
        assert res[f"{mode}-spiked"] > 100, "vacuous test - nothing spiked"
        assert res[f"{mode}-packed-overflow"] == 0
        for wire in ("f32", "u8", "sparse", "sparse:0.5"):
            assert res[f"{mode}-{wire}"], \
                f"wire {wire} diverged from packed under {mode}"
            assert res[f"{mode}-{wire}-overflow"] == 0
        assert res[f"{mode}-packed+sparse"], \
            f"per-tier packed+sparse diverged from packed under {mode}"
        assert res[f"{mode}-packed+sparse-overflow"] == 0
    assert res["tiny-overflow"] > 0, \
        "starved sparse wire saturated without telemetry"


DRYRUN_CODE = textwrap.dedent("""
    import json
    import jax
    from repro.core import snn
    from repro.core.distributed import (DistributedConfig,
                                        make_raw_distributed_step,
                                        wire_bytes_for_dims)
    from repro.core.engine import EngineConfig
    from repro.core.wire import sparse_packed_crossover_fraction
    from repro.launch.dryrun_snn import shard_dims, state_and_consts_sds

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    axes = mesh.axis_names
    dims = shard_dims(20_000, 400_000, 8, 2, max_delay=16)
    res = {}
    for wire in ("packed", "sparse"):
        cfg = DistributedConfig(engine=EngineConfig(dt=0.1),
                                comm_mode="area", axis_names=axes,
                                spike_wire=wire)
        step = make_raw_distributed_step(mesh, [snn.LIFParams()], cfg,
                                         max_delay=dims["max_delay"],
                                         n_local=dims["n_local"],
                                         n_mirror=dims["n_mirror"])
        state, consts = state_and_consts_sds(dims, mesh, axes)
        jax.jit(step).lower(state, consts).compile()
        res[wire] = wire_bytes_for_dims(
            "area", wire, n_shards=8, row_width=2,
            n_local=dims["n_local"], b_pad=dims["b_pad"])
    res["crossover"] = sparse_packed_crossover_fraction(dims["n_local"])
    print(json.dumps(res))
""")


@pytest.mark.slow
def test_raw_dryrun_step_compiles_for_sparse_wire():
    """The graph-free dry-run path (ShapeDtypeStruct consts only) lowers
    and compiles with the sparse ID wire, and its codec-based traffic
    model reports sparse < packed below the crossover fraction."""
    out = run_sub(DRYRUN_CODE)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["sparse"] < res["packed"], res
    assert 0.02 < res["crossover"] < 1 / 32


def test_comm_accounting_area_beats_global():
    """Multi-area nets: area-mode spike traffic << global gather (the
    paper's Fig. 8 claim, computed from the exchange metadata)."""
    spec = models.marmoset(scale=0.004, n_areas=4)
    dec = mesh_decompose(spec, n_rows=4, row_width=2)
    net = prepare_stacked(spec, dec, 4, 2)
    assert net.comm_bytes_area < net.comm_bytes_global * 0.8, (
        net.comm_bytes_area, net.comm_bytes_global)


def test_boundary_sets_are_small():
    """Area-Processes Mapping keeps per-shard boundary (inter-row) sets far
    below the local neuron count - n(inV^r) << n(V_i)."""
    spec = models.marmoset(scale=0.004, n_areas=4)
    dec = mesh_decompose(spec, n_rows=4, row_width=2)
    net = prepare_stacked(spec, dec, 4, 2)
    assert net.b_pad < net.n_local * 0.7, (net.b_pad, net.n_local)


def test_boundary_pad_slots_do_not_alias_neuron_zero():
    """Boundary padding uses the out-of-range sentinel n_local (read back
    as 0 via the exchange's fill-mode take), so a pad slot never mirrors a
    real neuron's bit - that would inflate the sparse wire's spike count
    and raise phantom overflow whenever neuron 0 fires."""
    spec = models.marmoset(scale=0.004, n_areas=4)
    dec = mesh_decompose(spec, n_rows=4, row_width=2)
    net = prepare_stacked(spec, dec, 4, 2, with_blocked=False)
    bs = np.asarray(net.boundary_slots)
    assert (bs <= net.n_local).all()
    assert (bs == net.n_local).any(), "config has no padding - vacuous"
    for s in range(net.n_shards):
        pads = bs[s] == net.n_local
        if pads.any():  # pads form a suffix after the real boundary prefix
            assert pads[int(np.argmax(pads)):].all()


def test_wire_bytes_through_codec():
    """Per-wire traffic accounting goes through the SpikeWire codec: the
    StackedNetwork figures, the dims-only dry-run model, and the codecs'
    own bytes_per_step must all agree."""
    spec = models.marmoset(scale=0.004, n_areas=4)
    dec = mesh_decompose(spec, n_rows=4, row_width=2)
    net = prepare_stacked(spec, dec, 4, 2, with_blocked=False)
    for mode in ("area", "global"):
        for wire in ("f32", "u8", "packed", "sparse"):
            got = wire_bytes_per_step(net, mode, wire)
            assert got == wire_bytes_for_dims(
                mode, wire, n_shards=net.n_shards, row_width=net.row_width,
                n_local=net.n_local, b_pad=net.b_pad)
        w = get_wire("packed")
        if mode == "global":
            expect = net.n_shards * w.bytes_per_step(net.n_local)
        else:
            expect = (net.row_width * w.bytes_per_step(net.n_local)
                      + net.n_shards * w.bytes_per_step(net.b_pad))
        assert wire_bytes_per_step(net, mode, "packed") == expect
    # the legacy fp32 mapping metric is the f32 wire through the same codec
    assert net.comm_bytes_area == wire_bytes_per_step(net, "area", "f32")
    assert net.comm_bytes_global == wire_bytes_per_step(net, "global", "f32")


def test_sparse_wire_traffic_beats_packed_at_marmoset_dims():
    """At production dims (marmoset scale 1 on a 16x16 mesh) a 2%-capacity
    sparse wire ships less than the packed bitmap in both comm modes - the
    ISSUE's acceptance number, computed without materializing a graph."""
    dims = dict(n_shards=256, row_width=16, n_local=4096, b_pad=640)
    for mode in ("area", "global"):
        sparse = wire_bytes_for_dims(mode, "sparse", **dims)
        packed = wire_bytes_for_dims(mode, "packed", **dims)
        assert sparse < packed, (mode, sparse, packed)
    # and the f32->packed->sparse progression is monotone
    area = [wire_bytes_for_dims("area", w, **dims)
            for w in ("f32", "u8", "packed", "sparse")]
    assert area == sorted(area, reverse=True)


OVERFLOW_CODE = textwrap.dedent("""
    import dataclasses, functools, json, tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.checkpoint.manager import CheckpointManager
    from repro.core import engine, models
    from repro.core import distributed as dist
    from repro.core.wire import SparseWire, register_wire

    spec, stdp = models.hpc_benchmark(scale=0.02, stdp=True)
    pops = [dataclasses.replace(p, ext_rate_hz=p.ext_rate_hz * 3.0)
            for p in spec.populations]
    spec = dataclasses.replace(spec, populations=pops)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    dec = dist.mesh_decompose(spec, 4, 2)
    net = dist.prepare_stacked(spec, dec, 4, 2, with_blocked=False)
    register_wire("tiny", SparseWire(max_rate=0.0, min_capacity=1,
                                     name="tiny"))
    results = {}

    # ---- part A: exact per-tier counting through _exchange -------------
    consts = dict(
        boundary_slots=jnp.asarray(net.boundary_slots),
        mirror_is_intra=jnp.asarray(net.mirror_is_intra),
        mirror_row_gather=jnp.asarray(net.mirror_row_gather),
        mirror_remote_gather=jnp.asarray(net.mirror_remote_gather),
        mirror_src_flat=jnp.asarray(net.mirror_src_flat),
        mirror_src_idx=jnp.asarray(net.graph["mirror_src_idx"]),
    )
    bs = np.asarray(net.boundary_slots)
    real_b = (bs < net.n_local).sum(axis=1)        # live boundary slots
    sp = P(("data", "model"))

    def overflow_of(bits_np, mode):
        cfg = dist.DistributedConfig(engine=engine.EngineConfig(dt=0.1),
                                     comm_mode=mode, spike_wire="tiny")
        def local(b, g):
            _, ov = dist._exchange(b[0], {k: v[0] for k, v in g.items()},
                                   cfg, cfg.wire, cfg.remote_wire)
            return ov[None]
        ex = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(sp, sp),
                                   out_specs=sp, check_vma=False))
        return np.asarray(ex(jnp.asarray(bits_np), consts)).tolist()

    ones = np.ones((net.n_shards, net.n_local), np.float32)
    single = np.zeros_like(ones); single[:, 0] = 1.0
    results["ones-area"] = overflow_of(ones, "area")
    results["ones-global"] = overflow_of(ones, "global")
    results["single-area"] = overflow_of(single, "area")
    # every local bitmap saturates (capacity 1 < n_local); the boundary
    # tier saturates exactly where >1 live boundary neuron fired
    results["expect-area"] = (1 + (real_b > 1)).astype(int).tolist()
    results["real_b"] = real_b.astype(int).tolist()

    # ---- part B: accumulation across a checkpoint/restore boundary -----
    def make_run(mode):
        cfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=0.1, stdp=stdp),
            comm_mode=mode, spike_wire="tiny")
        step, _ = dist.make_distributed_step(net, mesh, list(spec.groups),
                                             cfg)
        @functools.partial(jax.jit, static_argnums=1)
        def scan(s, n):
            return jax.lax.scan(lambda s, _: step(s), s, None, length=n)
        return scan

    for mode in ("area", "global"):
        scan = make_run(mode)
        s0 = dist.init_stacked_state(net, list(spec.groups))
        mid, _ = scan(s0, 100)
        mgr = CheckpointManager(tempfile.mkdtemp(), keep=1)
        mgr.save(100, mid)
        restored, _ = mgr.restore(dist.init_stacked_state(
            net, list(spec.groups)))
        fin_r, bits_r = scan(restored, 80)
        fin_u, bits_u = scan(mid, 80)
        results[f"{mode}-mid-overflow"] = int(
            np.asarray(mid.wire_overflow).sum())
        results[f"{mode}-restored-overflow-equal"] = bool(
            (np.asarray(fin_r.wire_overflow)
             == np.asarray(fin_u.wire_overflow)).all())
        results[f"{mode}-restored-bits-equal"] = bool(
            (np.asarray(bits_r) == np.asarray(bits_u)).all())
        results[f"{mode}-final-overflow"] = int(
            np.asarray(fin_u.wire_overflow).sum())
    print(json.dumps(results))
""")


@pytest.mark.slow
def test_wire_overflow_tier_accounting_and_checkpoint():
    """DistState.wire_overflow telemetry contract: in "area" mode each of
    the two tiers (intra-row local payload, cross-row boundary payload) is
    counted EXACTLY once per step, "global" mode counts its single gather
    once, a sub-capacity step counts nothing - and the counter is ordinary
    restorable state: a run resumed from a checkpoint accumulates to the
    same totals (and trajectory) as the uninterrupted run."""
    out = run_sub(OVERFLOW_CODE)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ones-area"] == res["expect-area"], res
    assert res["ones-global"] == [1] * len(res["ones-global"])
    assert res["single-area"] == [0] * len(res["single-area"]), \
        "sub-capacity payloads must not raise phantom overflow"
    assert max(res["real_b"]) > 1, "vacuous fixture: no boundary tier fires"
    for mode in ("area", "global"):
        assert res[f"{mode}-mid-overflow"] > 0, \
            f"starved wire never saturated under {mode} - vacuous"
        assert res[f"{mode}-final-overflow"] >= res[f"{mode}-mid-overflow"]
        assert res[f"{mode}-restored-overflow-equal"], \
            f"overflow lost across checkpoint/restore under {mode}"
        assert res[f"{mode}-restored-bits-equal"]


def test_wire_bytes_split_tiers():
    """Intra/inter tier accounting: the split sums to the total, "global"
    mode is all-inter, and swapping only the REMOTE wire moves only the
    inter-host term (the per-tier wire contract)."""
    dims = dict(n_shards=8, row_width=2, n_local=4096, b_pad=640)
    from repro.core.distributed import wire_bytes_split
    for mode in ("area", "global"):
        s = wire_bytes_split(mode, "packed", **dims)
        assert s["intra"] + s["inter"] == wire_bytes_for_dims(
            mode, "packed", **dims)
    assert wire_bytes_split("global", "packed", **dims)["intra"] == 0
    a = wire_bytes_split("area", "packed", **dims)
    b = wire_bytes_split("area", "packed", "sparse", **dims)
    assert b["intra"] == a["intra"] and b["inter"] != a["inter"]
    assert b["inter"] == 8 * get_wire("sparse").bytes_per_step(640)
    # global mode's single gather rides the remote-tier wire
    g = wire_bytes_split("global", "f32", "packed", **dims)
    assert g["inter"] == 8 * get_wire("packed").bytes_per_step(4096)
