"""Smoke run of the simulator's main path on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips (2x2 mesh) only

One chip: builds ``hpc_benchmark(scale=1.0, stdp=True)`` - 11,250 neurons,
12.66M edges, plastic E->E synapses - with ``builder.build_shards`` and
runs it through ``engine.run``, first with the default ``flat`` sweep, then
with ``sweep="pallas"``.  Each phase checks that every v_m and weight is
finite, that plastic weights stay in [w_min, w_max], that the mean rate
after the first 100 steps lies in the asynchronous-irregular band
(0, 10) Hz, and that over a 20-step window from the same warmed-up state
the chip matches a float32 run of the network on the host CPU (max|dv_m|
<= 1e-3 mV, spike totals within 10%).  ``pallas`` must match ``flat`` to
the same bar, and its compiled program must hold Pallas TPU kernels
(``tpu_custom_call``): no kernel may run in interpret mode.  hpc_benchmark
has one parameter row, so a small ``marmoset`` (two neuron groups) is
then held to the same TPU-vs-CPU bar, on both backends, over a 20-step
window after its first spikes.

Four chips: builds ``marmoset`` with procedural connectivity on a 2x2 mesh
through ``mesh_decompose`` -> ``prepare_stacked`` ->
``make_distributed_step``, driven by the scenario's own Poisson drive, and
compares it over the run (onset and first bursts) with the same network on
a 1x1 mesh (one of those chips): spikes must agree exactly and v_m to the
bar above, and spikes must have crossed shard boundaries.  It prints
``bytes_in_use`` per device and fails if the shards are not spread over
all four devices.

Wall times printed on the way are set-up and run times of this smoke, not
benchmark results.  The last line of standard output is
``{"ok": true, "device": {...}}``; a failed check, or a platform other than
``tpu``, exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

# one-chip phase
HPC_SCALE = 1.0
HPC_STEPS = 500          # steps per backend run, a multiple of WINDOW
RATE_SKIP = 100          # steps left out of the rate (network onset)
WINDOW = 20              # steps of the TPU-vs-CPU agreement window
RATE_BAND_HZ = (0.0, 10.0)
MULTI_SCALE = 0.005      # marmoset, two neuron groups: 4,996 neurons
MULTI_WARM = 300         # steps to the network's first spikes
# four-chip phase
MARMOSET_SCALE = 0.01   # 9,996 neurons, 1.74M edges
DIST_STEPS = 450         # onset (~250 steps) and the first bursts
# agreement bars
DV_MAX_MV = 1e-3
SPIKE_REL = 0.10


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def wall(label: str, seconds: float) -> None:
    print(f"  wall {label}: {seconds:.3f} s (not a benchmark result)",
          flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (platform {dev.platform!r})")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} chips needed, found "
                 f"{len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    return devices


def agree(tag: str, a, b) -> None:
    """``a`` and ``b`` are (v_m, spike total) at the end of a window."""
    (v_a, n_a), (v_b, n_b) = a, b
    dv = float(np.max(np.abs(np.asarray(v_a, np.float64)
                             - np.asarray(v_b, np.float64))))
    print(f"  {tag}: max|dv_m| over {WINDOW} steps = {dv!r} mV; "
          f"spike totals {n_a} vs {n_b}", flush=True)
    check(dv <= DV_MAX_MV, f"{tag}: max|dv_m| <= {DV_MAX_MV} mV")
    check(abs(n_a - n_b) <= SPIKE_REL * max(n_a, n_b),
          f"{tag}: spike totals within {SPIKE_REL:.0%}")


# --------------------------------------------------------------------------
# one chip: hpc_benchmark with STDP, flat then pallas
# --------------------------------------------------------------------------

class OnChip:
    """One network built with ``builder.build_shards``, placed on the TPU
    and on the host CPU, with one compiled ``engine.run`` of WINDOW steps
    per backend: longer runs chain it, so each backend compiles once."""

    def __init__(self, spec, stdp=None, method=None):
        from repro.core import builder, models, snn
        self.cpu = jax.devices("cpu")[0]
        self.groups, self.stdp = list(spec.groups), stdp
        dec = (builder.decompose(spec, 1) if method is None
               else builder.decompose(spec, 1, method=method))
        t0 = time.perf_counter()
        self.g_host = builder.build_shards(spec, dec)[0]
        wall("build", time.perf_counter() - t0)
        self.table_host = np.asarray(
            snn.make_param_table(self.groups, dt=models.DT_MS))
        self.placed = {False: (self.g_host.device_arrays(),
                               jnp.asarray(self.table_host))}
        with jax.default_device(self.cpu):
            self.placed[True] = (self.g_host.device_arrays(),
                                 jnp.asarray(self.table_host))

    def init(self):
        from repro.core import engine
        return engine.init_state(self.placed[False][0], self.groups,
                                 jax.random.key(0))

    def runner(self, sweep, on_cpu=False):
        from repro.core import engine, models
        cfg = engine.EngineConfig(dt=models.DT_MS, stdp=self.stdp,
                                  sweep=sweep)
        g, table = self.placed[on_cpu]
        return jax.jit(lambda s: engine.run(s, g, table, cfg, WINDOW))

    def cpu_window(self, state):
        """WINDOW steps of ``flat`` on the host CPU from ``state``."""
        t0 = time.perf_counter()
        s, bits = self.runner("flat", on_cpu=True)(
            jax.device_put(state, self.cpu))
        wall(f"cpu reference {WINDOW} steps", time.perf_counter() - t0)
        return np.asarray(s.neurons.v_m), int(np.asarray(bits).sum())


def hpc_phase() -> None:
    from repro.core import models

    spec, stdp = models.hpc_benchmark(scale=HPC_SCALE, stdp=True)
    n = spec.n_neurons
    print(f"== hpc_benchmark(scale={HPC_SCALE}, stdp=True)", flush=True)
    net = OnChip(spec, stdp)
    g_host = net.g_host
    print(f"  neurons={n} edges={int((np.asarray(g_host.delay) > 0).sum())} "
          f"max_delay={g_host.max_delay}", flush=True)
    plastic = np.asarray(g_host.plastic) & (np.asarray(g_host.delay) > 0)

    state0 = net.init()
    warmed = None
    windows = {}
    for sweep in ("flat", "pallas"):
        print(f"== {sweep}: {HPC_STEPS} steps through engine.run", flush=True)
        t0 = time.perf_counter()
        run = net.runner(sweep).lower(state0).compile()
        wall(f"{sweep} compile", time.perf_counter() - t0)
        if sweep == "pallas":
            check("tpu_custom_call" in run.as_text(),
                  "pallas: compiled program holds tpu_custom_call")
        t0 = time.perf_counter()
        final, rasters = state0, []
        for _ in range(HPC_STEPS // WINDOW):
            final, bits = run(final)
            rasters.append(bits)
        jax.block_until_ready(final)
        wall(f"{sweep} run {HPC_STEPS} steps", time.perf_counter() - t0)

        v = np.asarray(final.neurons.v_m)
        w = np.asarray(final.weights)
        check(bool(np.isfinite(v).all()), f"{sweep}: every v_m finite")
        check(bool(np.isfinite(w).all()), f"{sweep}: every weight finite")
        wp = w[plastic]
        print(f"  {sweep}: plastic weights min={wp.min()!r} "
              f"max={wp.max()!r} over {wp.size} edges", flush=True)
        check(stdp.w_min <= wp.min() and wp.max() <= stdp.w_max,
              f"{sweep}: plastic weights inside "
              f"[{stdp.w_min}, {stdp.w_max}]")
        spikes = np.concatenate([np.asarray(b) for b in rasters])
        rate = models.firing_rate_hz(spikes[RATE_SKIP:], n)
        print(f"  {sweep}: mean rate {rate!r} Hz over steps "
              f"{RATE_SKIP}..{HPC_STEPS}", flush=True)
        lo, hi = RATE_BAND_HZ
        check(lo < rate < hi,
              f"{sweep}: rate in the asynchronous-irregular band "
              f"({lo:g}, {hi:g}) Hz")
        if warmed is None:
            warmed = final   # the flat run's end state seeds every window
            windows["cpu"] = net.cpu_window(warmed)
            check(windows["cpu"][1] > 0, "cpu reference window spiked")

        s, bits = run(warmed)
        windows[sweep] = (np.asarray(s.neurons.v_m),
                          int(np.asarray(bits).sum()))
        agree(f"{sweep} tpu vs cpu", windows[sweep], windows["cpu"])
    agree("pallas vs flat (tpu)", windows["pallas"], windows["flat"])


def multi_group_phase() -> None:
    """TPU against CPU on a network whose neurons use two parameter rows,
    so the per-neuron parameter lookup (``table[group_id]`` in ``flat``,
    the per-group select in the ``pallas`` neuron kernel) really chooses
    between groups: hpc_benchmark has one row."""
    from repro.core import models

    spec = models.marmoset(scale=MULTI_SCALE)
    print(f"== marmoset(scale={MULTI_SCALE}): {spec.n_neurons} neurons, "
          f"{len(spec.groups)} groups, {MULTI_WARM} warm-up steps, then "
          f"{WINDOW} steps TPU vs CPU", flush=True)
    net = OnChip(spec, method="random")
    used = np.unique(np.asarray(net.g_host.group_id)[
        np.asarray(net.g_host.global_id) >= 0])
    rows = np.unique(net.table_host, axis=0).shape[0]
    check(used.size >= 2 and rows >= 2,
          f"multi-group: {used.size} groups in use, {rows} distinct "
          f"parameter rows")
    run = net.runner("flat")
    warmed = net.init()
    for _ in range(MULTI_WARM // WINDOW):
        warmed, _ = run(warmed)
    ref = net.cpu_window(warmed)
    check(ref[1] > 0, "multi-group: cpu reference window spiked")
    for sweep in ("flat", "pallas"):
        s, bits = (run if sweep == "flat" else net.runner(sweep))(warmed)
        agree(f"multi-group {sweep} tpu vs cpu",
              (np.asarray(s.neurons.v_m), int(np.asarray(bits).sum())), ref)


def one_chip() -> None:
    hpc_phase()
    multi_group_phase()


# --------------------------------------------------------------------------
# four chips: marmoset on a 2x2 mesh against a 1x1 mesh
# --------------------------------------------------------------------------

def used_mirrors(net, s: int) -> np.ndarray:
    """Mirror rows of shard ``s`` that some edge reads (the rest pad)."""
    used = np.zeros(net.n_mirror, bool)
    used[net.graph["pre_idx"][s][net.graph["delay"][s] > 0]] = True
    return used


def four_chips(devices) -> None:
    from repro.core import engine, models
    from repro.core import distributed as dist

    spec = dataclasses.replace(models.marmoset(scale=MARMOSET_SCALE),
                               connectivity="procedural")
    groups = list(spec.groups)
    print(f"== marmoset(scale={MARMOSET_SCALE}) procedural: "
          f"{spec.n_neurons} neurons, {DIST_STEPS} steps, scenario drive",
          flush=True)
    cfg = dist.DistributedConfig(
        engine=engine.EngineConfig(dt=models.DT_MS))
    runs = {}
    for rows, width in ((2, 2), (1, 1)):
        tag = f"{rows}x{width}"
        mesh = jax.make_mesh((rows, width), cfg.axis_names,
                             devices=devices[:rows * width])
        t0 = time.perf_counter()
        dec = dist.mesh_decompose(spec, rows, width)
        net = dist.prepare_stacked(spec, dec, rows, width,
                                   with_blocked=False)
        wall(f"{tag} build", time.perf_counter() - t0)
        print(f"  {tag}: shards={net.n_shards} n_local={net.n_local} "
              f"n_mirror={net.n_mirror} edges/shard={net.n_edges}",
              flush=True)
        step, _ = dist.make_distributed_step(net, mesh, groups, cfg)
        state = dist.init_stacked_state(net, groups, mesh=mesh,
                                        axis_names=cfg.axis_names)
        run = jax.jit(lambda s: jax.lax.scan(lambda c, _: step(c), s, None,
                                             length=DIST_STEPS))
        t0 = time.perf_counter()
        compiled = run.lower(state).compile()
        wall(f"{tag} compile", time.perf_counter() - t0)
        used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in devices[:rows * width]]
        for i, b in enumerate(used):
            print(f"  {tag}: device {i} bytes_in_use={b}", flush=True)
        if rows * width > 1:
            rest = used[1:]
            check(min(rest) > 0 and used[0] < 2 * float(np.median(rest)),
                  f"{tag}: state and consts spread over all "
                  f"{rows * width} devices")
        t0 = time.perf_counter()
        final, bits = compiled(state)
        jax.block_until_ready((final, bits))
        wall(f"{tag} run {DIST_STEPS} steps", time.perf_counter() - t0)
        bits, v = np.asarray(bits), np.asarray(final.v_m)
        if rows * width > 1:
            # spikes the exchange delivered from other shards, as they sit
            # in each shard's ring (the last max_delay steps)
            ring = np.asarray(final.ring)
            remote = sum(int(ring[s][:, (net.mirror_src_flat[s] != s)
                                     & used_mirrors(net, s)].sum())
                         for s in range(net.n_shards))
            print(f"  {tag}: {remote} spikes from other shards in the "
                  f"rings", flush=True)
            check(remote > 0, f"{tag}: spikes crossed shard boundaries")
        glob_bits = np.zeros((DIST_STEPS, spec.n_neurons), bool)
        glob_v = np.zeros(spec.n_neurons, np.float32)
        for si, part in enumerate(dec.parts):
            glob_bits[:, part] = bits[:, si, :part.size] > 0
            glob_v[part] = v[si, :part.size]
        check(bool(np.isfinite(glob_v).all()), f"{tag}: every v_m finite")
        check(int(np.asarray(final.wire_overflow).sum()) == 0,
              f"{tag}: spike wire never overflowed")
        rate = models.firing_rate_hz(glob_bits)
        print(f"  {tag}: mean rate {rate!r} Hz, {int(glob_bits.sum())} "
              f"spikes", flush=True)
        runs[tag] = (glob_bits, glob_v)
        del state, final, compiled, run, step, net
    (b4, v4), (b1, v1) = runs["2x2"], runs["1x1"]
    check(int(b1.sum()) > 0, "1x1: the network spiked")
    dv = float(np.max(np.abs(v4.astype(np.float64) - v1)))
    print(f"  2x2 vs 1x1: max|dv_m| after {DIST_STEPS} steps = {dv!r} mV; "
          f"spikes {int(b4.sum())} vs {int(b1.sum())}, "
          f"{int((b4 != b1).sum())} differing", flush=True)
    check(bool((b4 == b1).all()), "2x2 vs 1x1: every spike agrees")
    check(dv <= DV_MAX_MV, f"2x2 vs 1x1: max|dv_m| <= {DV_MAX_MV} mV")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.utils.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    try:
        if args.chips == 4:
            four_chips(devices)
        else:
            one_chip()
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
