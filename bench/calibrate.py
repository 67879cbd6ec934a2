"""Readings that the limits of ``correct`` are set from.

    python -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one run of the cell as ``bench.run`` makes it (set-up, a
warm-up call and a window of ``--seconds``), then two comparisons with
the float32 reference:

- ``program``: the program's served raster and end state (what a run
  compares);
- ``control``: the reference itself put in the program's place and
  computed in bfloat16, the next precision below the configuration's
  float32, over the same initial state, drive and served raster: its own
  spike decisions and end state.

Each seed is set up anew as a run is (build, placement, compile, which
the persistent cache serves after the first seed); one process reads all
seeds, since starting a process on a chip costs more than a seed.  Each
seed prints one JSON line; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import check, reference
from bench import run as run_mod
from bench import sim as sim_mod
from bench.registry import Registry


def control_readings(c, m, sim) -> dict:
    import ml_dtypes
    rep = reference.replay(c.net, c.edges, m.raster, sim.v0, sim.seeds.key,
                           dtype=ml_dtypes.bfloat16, events=c.events)
    consts = reference.lif_constants(c.net)
    cand = dict(served=rep.fires, v=rep.v, syn_ex=rep.syn_ex,
                syn_in=rep.syn_in, w=rep.w)
    numbers, _ = check.readings(c.ref, cand, consts,
                                c.edges["plastic"] if sim.plastic else None)
    return numbers


def calibrate(reg: Registry, cell: str, seeds: list, seconds: float,
              devices):
    """Yield one record of readings per seed."""
    wl = reg.workload(cell)
    config = reg.config(wl["config"])
    for seed in seeds:
        t_start = time.perf_counter()
        sim = sim_mod.make_sim(config, reg.traffic(wl["traffic"]),
                               sim_mod.Seeds.from_seed(seed),
                               devices[:wl["chips"]])
        m = run_mod.measure(sim, seconds, None, t_start)
        t_ref = time.perf_counter()
        c = run_mod.compare(sim, config, m)
        t_ctrl = time.perf_counter()
        ctrl = control_readings(c, m, sim)
        moved = (int((c.ref.w != c.edges["w"])[c.edges["plastic"]].sum())
                 if sim.plastic else 0)
        yield {"workload": cell, "seed": seed,
               "steps": int(m.raster.shape[0]),
               "spikes": int(m.raster.sum()),
               "spikes_per_call": m.raster.reshape(
                   -1, sim.steps * m.raster.shape[1]).sum(axis=1).tolist(),
               "bio_s_per_s": m.calls * sim.steps * sim.dt * 1e-3
               / m.window_s,
               "setup_s": m.setup_s,
               "times": dict(sim.times), "reference_s": t_ctrl - t_ref,
               "control_s": time.perf_counter() - t_ctrl,
               "plastic_weights_moved": moved,
               "program": c.numbers, "control": ctrl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(run_mod.ROOT / "src"))
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    reg = Registry()
    devices = run_mod.require_chips(reg.workload(args.workload)["chips"])
    for rec in calibrate(reg, args.workload,
                         [int(s) for s in args.seeds.split(",")],
                         args.seconds, devices):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
