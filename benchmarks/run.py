"""Benchmark harness entry point - one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus the roofline table if
experiments/roofline.json exists).

    PYTHONPATH=src python -m benchmarks.run [--only snn|kernels|models]

``--json`` switches to the committed perf-trajectory mode: it runs the
curated baseline suite (per-phase profile + dense-vs-gated activity sweep
+ step scaling + wire exchange, backend x wire x model incl.
pallas:sparse) and writes ``BENCH_<scale>.json`` - the file CI diffs
fresh runs against (``benchmarks/diff.py``).  ``--scale full`` is the
committed-numbers configuration (largest feasible single-shard geometry
on this CPU interpret proxy); ``--scale quick`` is the CI-sized one.
"""

import argparse
import sys


def _out(name: str, us: float, derived="") -> None:
    if isinstance(derived, dict):
        derived = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us:.2f},{derived}", flush=True)


def _bench_json(path: str, scale: str) -> None:
    import json
    import os
    import platform

    import jax

    from benchmarks import bench_snn

    quick = scale == "quick"
    records = []

    def out(name, us, derived=None):
        rec = dict(name=name, us_per_call=round(us, 2), **(derived or {}))
        if name.startswith("snn_step/") or name.startswith("snn_gate/"):
            rec["steps_per_sec"] = round(1e6 / us, 2) if us > 0 else None
        records.append(rec)
        _out(name, us, derived or {})

    print("name,us_per_call,derived")
    # multi-tenant serving throughput: N resident sessions in ONE vmapped
    # slot batch vs N sequential one-shot runs (DESIGN.md §16);
    # diff.py holds the batched speedup_vs_sequential above its floor.
    # First, because each one-shot run is a child process that needs the
    # device, and the device serves one process: this one must not hold it
    # yet.
    bench_snn.bench_sessions(out, quick=quick)
    # per-phase hot path (every backend incl. pallas:sparse) + the
    # dense-vs-gated activity axis (the pallas:sparse acceptance metric)
    bench_snn.bench_profile(out, quick=quick)
    bench_snn.bench_gate_activity(out, quick=quick)
    # steps/sec scaling, backend axis
    bench_snn.bench_step_scaling(out, quick=quick)
    # one cross-model leg (backend x model)
    bench_snn.bench_step_scaling(out, ("pallas", "pallas:sparse"),
                                 quick=True, model="izhikevich")
    # wire codecs with the intra/inter byte split (backend x wire)
    bench_snn.bench_wire_exchange(out, comm_modes=("area",), quick=quick)
    bench_snn.bench_mapping_comparison(out, quick=quick)
    # build scaling: materialized vs procedural wall-clock + peak RSS
    # (fresh subprocess per point); diff.py holds procedural's peak
    # strictly below materialized at the largest common scale
    bench_snn.bench_build_scaling(out, quick=quick)
    # measured (PB, EB) sweep timings keyed by degree signature - the
    # committed records ARE the autotuner's measured tie-breaker
    # (block_shapes="measured:BENCH_full.json")
    bench_snn.bench_shape_tune(out, quick=quick)
    # checkpoint save/restore overhead (fault-tolerant runtime,
    # DESIGN.md §15); ckpt_bytes/ckpt_leaves are structural guards
    bench_snn.bench_checkpoint(out, quick=quick)
    # measured gate-capacity records (pallas:sparse worklist provisioning
    # from data: gate_rate="measured:BENCH_full.json"); the deterministic
    # overflow_rate/occupancy fields are exact invariants
    bench_snn.bench_gate_tune(out, quick=quick)
    # differentiable-mode costs (DESIGN.md §17): surrogate vs inference
    # step overhead + naive vs checkpointed rollout gradient peak memory;
    # diff.py holds checkpointed temp bytes strictly below naive at T=200
    bench_snn.bench_surrogate(out, quick=quick)

    payload = {
        "meta": {
            "scale": scale,
            "jax": jax.__version__,
            "backend_platform": jax.default_backend(),
            "python": platform.python_version(),
            "machine": platform.machine(),
        },
        "records": records,
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"-> {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "snn", "kernels", "models", "roofline"])
    ap.add_argument("--json", default=None, nargs="?", const="",
                    metavar="PATH",
                    help="perf-trajectory mode: run the curated baseline "
                         "suite and write BENCH_<scale>.json (or PATH)")
    ap.add_argument("--scale", default="quick", choices=["quick", "full"],
                    help="baseline suite size for --json (quick: CI-sized; "
                         "full: committed-numbers geometry)")
    args = ap.parse_args()
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()

    if args.json is not None:
        _bench_json(args.json or f"BENCH_{args.scale}.json", args.scale)
        return

    print("name,us_per_call,derived")
    if args.only in (None, "snn"):
        from benchmarks import bench_snn
        bench_snn.main(_out)
    if args.only in (None, "kernels"):
        from benchmarks import bench_kernels
        bench_kernels.main(_out)
    if args.only in (None, "models"):
        from benchmarks import bench_models
        bench_models.main(_out)
    if args.only in (None, "roofline"):
        from benchmarks import roofline_table
        roofline_table.main(_out)


if __name__ == "__main__":
    main()
