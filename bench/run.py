"""One run of one cell of the chip benchmark.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run:

1. points JAX's persistent compilation cache at the checkout's directory;
2. refuses any platform but ``tpu``, and fewer chips than the cell asks;
3. builds the cell's network from the seed (connectivity where the
   configuration lets the seed choose it, the drive's key, the initial
   membrane potentials) through the program's builder;
4. places it on the chips and compiles the call of the cell's shape;
5. warms up with one call (everything up to here is ``setup_s``);
6. calls back to back for ``--seconds``, reading each call's spike raster
   back to the host as a recording user would;
7. replays the whole run with the plain reference and compares
   (``bench.check``);
8. prints one JSON object as the last line of standard output.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, read from the trace and the set-up times by the
readers in ``bench/metrics/``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, reference, sim as sim_mod  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.registry import Registry  # noqa: E402

GIB = float(1 << 30)


class NoChip(Exception):
    pass


def require_chips(n: int):
    """The devices of the run: TPUs, at least ``n`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, found {len(devices)}")
    return devices


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader may read."""

    times: dict            # host-clock set-up times: build_s, compile_s
    trace: object          # bench.trace.TraceSummary of the window
    steps: int             # steps simulated in the traced window
    window_s: float        # length of the traced window


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Measured:
    """A run's window: the final state (host copy), the whole raster from
    step 0, and the host-clock readings."""

    final: dict
    raster: np.ndarray
    calls: int
    window_s: float
    setup_s: float
    peak_bytes: int


def measure(sim, seconds: float, trace_dir: Path | None,
            t_start: float) -> Measured:
    """Set up ``sim``, warm up with one call, then call back to back for
    ``seconds``, reading each raster back; traced when ``trace_dir``."""
    import jax

    state = sim.setup()
    state, bits = sim.call(state)
    rasters = [sim.raster(bits)]
    setup_s = time.perf_counter() - t_start

    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
    # the next call is dispatched before the last one's raster is read;
    # the window stops dispatching once the call in flight would end it
    t0 = time.perf_counter()
    calls, pending = 0, None
    while True:
        state, bits = sim.call(state)
        if pending is not None:
            rasters.append(sim.raster(pending))
        pending, calls = bits, calls + 1
        elapsed = time.perf_counter() - t0
        per_call = elapsed / (calls - 1) if calls > 1 else 0.0
        if elapsed + per_call >= seconds:
            break
    rasters.append(sim.raster(pending))
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    peak = _peak_bytes(sim.devices)
    final = sim.final(state)
    return Measured(final=final, raster=np.concatenate(rasters), calls=calls,
                    window_s=window_s, setup_s=setup_s, peak_bytes=peak)


@dataclasses.dataclass
class Compared:
    """The reference's replay of a run and the numbers it gave."""

    net: object
    edges: dict
    events: np.ndarray
    ref: object
    numbers: dict
    gaps: np.ndarray


def compare(sim, config, m: Measured) -> Compared:
    """Replay the run with the float32 reference and read the numbers."""
    net = reference.net_from_config(config.data, sim.net_seed, sim.plastic)
    ref_edges = reference.edges(net)
    differ, index = check.match_edges(sim.program_edges(), ref_edges,
                                      net.n, net.max_delay)
    events = reference.drive_events(sim.seeds.key,
                                    reference.drive_thresholds(net),
                                    m.raster.shape[0])
    rep = reference.replay(net, ref_edges, m.raster, sim.v0, sim.seeds.key,
                           events=events)
    consts = reference.lif_constants(net)
    plastic = ref_edges["plastic"] if sim.plastic else None
    cand = dict(served=m.raster, v=m.final["v"], syn_ex=m.final["syn_ex"],
                syn_in=m.final["syn_in"],
                w=(np.full(ref_edges["w"].shape, np.inf, np.float32)
                   if index is None else m.final["w"][index]))
    numbers, gaps = check.readings(rep, cand, consts, plastic)
    numbers["edges_differ"] = differ
    return Compared(net=net, edges=ref_edges, events=events, ref=rep,
                    numbers=numbers, gaps=gaps)


def run(cell: str, seed: int, seconds: float, traced: bool, devices,
        root: Path = ROOT, t_start: float = _T_START) -> dict:
    """One run on ``devices``; returns the result object."""
    reg = Registry(root)
    wl = reg.workload(cell)
    config = reg.config(wl["config"])
    sim = sim_mod.make_sim(config, reg.traffic(wl["traffic"]),
                           sim_mod.Seeds.from_seed(seed),
                           devices[:wl["chips"]])
    trace_dir = root / ".bench_trace" / cell if traced else None
    m = measure(sim, seconds, trace_dir, t_start)
    c = compare(sim, config, m)
    correct, compared = check.judge(c.numbers, wl["limits"])
    step_fail = c.gaps.reshape(-1, sim.steps).max(axis=1)[1:] > \
        wl["limits"].get("spike_gap_mV", np.inf)
    failed = int(step_fail.sum())
    if not correct and failed == 0:
        failed = 1      # an end-state number or the network itself is off
    print(f"bench: {cell} seed {seed}: {m.raster.shape[0]} steps, "
          f"{int(m.raster.sum())} spikes, {m.calls} calls in "
          f"{m.window_s:.3f} s", file=sys.stderr)

    if traced:
        hlo = sim.hlo_text()
        (trace_dir / "hlo.txt").write_text(hlo)
        summary = trace_mod.load(str(trace_dir), hlo)
        ctx = ReadContext(times=dict(sim.times), trace=summary,
                          steps=m.calls * sim.steps, window_s=m.window_s)
        metrics = {}
        for e in reg.per_layer(cell):
            v = reg.reader(e["name"])(ctx)
            if v is not None:
                metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    else:
        values = {"bio_s_per_s": m.calls * sim.steps * sim.dt * 1e-3
                  / m.window_s,
                  "peak_hbm_gib": m.peak_bytes / GIB,
                  "setup_s": m.setup_s}
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in reg.end_to_end(cell)}

    dev = devices[0]
    result = {"correct": bool(correct), "attempted": m.calls,
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": m.peak_bytes}}
    if traced:
        result["device"].update(busy_s=summary.busy_s(),
                                window_s=m.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    chips = Registry(ROOT).workload(args.workload)["chips"]
    try:
        devices = require_chips(chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 devices)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
