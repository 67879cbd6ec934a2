"""Local multi-process launcher + worker for the multi-host SNN backend.

One file, two roles:

* **Launcher** (no ``--process-id``): spawns N copies of itself as local
  CPU processes - each child gets
  ``XLA_FLAGS=--xla_force_host_platform_device_count=<devices>`` and a
  shared gloo coordinator - then waits and surfaces the result JSON
  written by process 0.  This is the CI-facing proof that the multi-host
  backend works without a cluster: 2 processes x N host devices on one
  box exercise the exact same code path a Fugaku-style deployment would
  (only the launcher differs).
* **Worker** (``--process-id`` set): joins the mesh via
  :func:`repro.core.multihost.initialize`, builds the SAME spec/
  decomposition/net as every peer (deterministic from the seed), runs the
  distributed step for ``--steps``, and reports sha256 hashes of the full
  spike and voltage trajectories plus overflow telemetry and the
  intra/inter-host wire-byte split - so a 2-process run can be diffed
  bit-for-bit against a 1-process run.  ``--bench`` adds a timed
  per-step loop (the ``bench_snn --processes`` axis shells out to this).

The workload is any scenario-zoo network (``--scenario brunel`` /
``microcircuit`` / ``marmoset``; default the hpc verification case) or
the cross-model demo net for any NeuronModel (``--model izhikevich``,
DESIGN.md §12) - the record carries the scenario/model so per-model
multi-process trajectories can be pinned.

On a REAL cluster no CLI plumbing is needed: when ``--process-id`` is
absent and SLURM (``SLURM_PROCID``/``SLURM_NTASKS``/
``SLURM_STEP_NODELIST``) or k8s-style (``REPRO_COORD_ADDR``/
``REPRO_NUM_PROC``/``REPRO_PROC_ID``) env vars are present with >1
ranks, every rank runs THIS same command line and picks up its identity
from the environment (:func:`repro.core.multihost.detect_cluster_env`).

Examples::

    PYTHONPATH=src python -m repro.launch.multihost \
        --processes 2 --devices-per-process 4 --steps 40 --out /tmp/mh.json
    PYTHONPATH=src python -m repro.launch.multihost \
        --processes 2 --devices-per-process 2 --wire packed \
        --wire-remote sparse --bench --out /tmp/mh_bench.json
    PYTHONPATH=src python -m repro.launch.multihost \
        --processes 2 --devices-per-process 2 --scenario brunel
    srun -n 16 python -m repro.launch.multihost --scenario microcircuit
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import time

__all__ = ["run_launcher", "main"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="multi-host SNN backend: local multi-process launcher")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-process", type=int, default=4,
                    help="forced host CPU devices per process")
    ap.add_argument("--row-width", type=int, default=2,
                    help="multisection cells per Area-Processes row; must "
                         "divide devices-per-process (host alignment)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--scale", type=float, default=0.02,
                    help="scenario scale")
    ap.add_argument("--scenario", default="hpc_benchmark",
                    help="scenario-zoo network (hpc_benchmark|brunel|"
                         "microcircuit|marmoset; repro.core.models)")
    ap.add_argument("--model", default=None,
                    help="run the cross-model demo network for this "
                         "NeuronModel (lif|izhikevich|adex|poisson) "
                         "instead of --scenario")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--drive-boost", type=float, default=None,
                    help="multiplier on the external Poisson rates; "
                         "default 3.0 for the hpc_benchmark smoke (keeps "
                         "tiny CI-scale nets actually firing) and 1.0 for "
                         "every other scenario/model - a zoo network's "
                         "(g, eta)-style operating point must not be "
                         "silently rescaled")
    ap.add_argument("--sweep", default="flat",
                    help="execution backend (flat|bucketed|pallas|pallas:auto)")
    ap.add_argument("--wire", default="packed",
                    help="intra-host spike wire codec")
    ap.add_argument("--wire-remote", default=None,
                    help="inter-host (boundary) wire codec; default = --wire")
    ap.add_argument("--connectivity", default=None,
                    choices=("materialized", "procedural"),
                    help="override the spec's connectivity mode; "
                         "'procedural' makes every worker build ONLY its "
                         "own rows' consts (no full-network broadcast)")
    ap.add_argument("--comm-mode", default="area", choices=("area", "global"))
    ap.add_argument("--no-stdp", action="store_true")
    ap.add_argument("--no-overlap", action="store_true")
    ap.add_argument("--bench", action="store_true",
                    help="also time a per-step loop after the trajectory run")
    ap.add_argument("--out", default="experiments/multihost.json")
    ap.add_argument("--timeout", type=float, default=900.0)
    # --- fault-tolerant supervised runtime (DESIGN.md §15) ---------------
    ap.add_argument("--save-every", type=int, default=None,
                    help="checkpoint every N steps and run under gang "
                         "supervision: dead/hung workers are detected, the "
                         "gang is torn down and relaunched from the latest "
                         "committed checkpoint (enables the fault-tolerant "
                         "supervised runtime)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: <out>.ckpt)")
    ap.add_argument("--keep-ckpts", type=int, default=3,
                    help="checkpoints retained by the manager's GC")
    ap.add_argument("--fault-inject", default=None,
                    help="deterministic fault specs "
                         "kind@step[:factor][#rank], comma-separated; "
                         "kinds: kill|hang|slow|ckpt-corrupt (e.g. "
                         "'kill@70#1'); $REPRO_FAULT_INJECT works too")
    ap.add_argument("--heartbeat-timeout", type=float, default=300.0,
                    help="seconds without a worker heartbeat before the "
                         "gang is declared hung and restarted")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="gang restarts before the supervisor aborts")
    ap.add_argument("--backoff", type=float, default=0.25,
                    help="initial gang-restart backoff seconds (doubles "
                         "per restart)")
    ap.add_argument("--backoff-cap", type=float, default=30.0,
                    help="ceiling on the exponential restart backoff")
    ap.add_argument("--elastic", action="store_true",
                    help="on worker loss, restart the gang on the "
                         "SURVIVING process count (elastic shrink-restart "
                         "from the same procedural checkpoint)")
    # worker-only (set by the launcher when spawning children)
    ap.add_argument("--process-id", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--heartbeat-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--incarnation", type=int, default=0,
                    help=argparse.SUPPRESS)
    return ap


# --------------------------------------------------------------------------
# launcher role
# --------------------------------------------------------------------------

def _child_env(args) -> dict:
    """Worker environment.  The local gang is a CPU rehearsal of a
    multi-host run (gloo collectives between forced host devices), so
    every worker is pinned to the CPU: an accelerator takes one process
    at a time, and N workers (or the launcher itself) would contend for
    it."""
    src = os.path.join(os.path.dirname(__file__), "..", "..")
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{args.devices_per_process}",
        PYTHONPATH=os.pathsep.join(
            p for p in (os.path.abspath(src),
                        os.environ.get("PYTHONPATH")) if p),
    )


def _spawn_gang(args, coordinator: str, env: dict) -> list:
    base = [sys.executable, "-m", "repro.launch.multihost",
            "--coordinator", coordinator]
    for k, v in vars(args).items():
        if k in ("process_id", "coordinator") or v is None or v is False:
            continue
        flag = "--" + k.replace("_", "-")
        base += [flag] if v is True else [flag, str(v)]
    return [subprocess.Popen(base + ["--process-id", str(i)], env=env)
            for i in range(args.processes)]


def run_launcher(args: argparse.Namespace) -> dict:
    """Spawn the worker processes, wait, return process 0's result dict."""
    if args.devices_per_process % args.row_width:
        raise SystemExit(
            f"--row-width {args.row_width} must divide "
            f"--devices-per-process {args.devices_per_process} so mesh rows "
            "align to hosts")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.save_every:
        return _run_launcher_supervised(args)
    procs = _spawn_gang(args, f"localhost:{_free_port()}", _child_env(args))
    # poll ALL workers: one crashing (e.g. a lost coordinator race) must
    # fail the launch immediately, not after its peers hit the gloo/
    # --timeout ceiling waiting for it
    deadline = time.time() + args.timeout
    pending = dict(enumerate(procs))
    failed: list[tuple[int, object]] = []
    while pending and not failed and time.time() < deadline:
        for i, p in list(pending.items()):
            rc = p.poll()
            if rc is not None:
                del pending[i]
                if rc != 0:
                    failed.append((i, rc))
        if pending and not failed:
            time.sleep(0.2)
    for i, p in pending.items():
        p.kill()
        p.wait()
        failed.append((i, "killed"))
    if failed:
        raise SystemExit(f"worker processes failed: {failed}")
    with open(args.out) as f:
        return json.load(f)


def _run_gang(args, deadline: float) -> list[tuple[int, object]]:
    """One gang incarnation: spawn, watch exits AND heartbeats.

    Returns [] on success or [(rank, why), ...] on failure, with every
    worker reaped - the caller decides restart vs abort.  Heartbeat files
    (written per step by the workers' SimulationSupervisor into this
    incarnation's private --heartbeat-dir) catch the failure mode exit
    codes cannot: a HUNG worker that never dies.
    """
    from repro.runtime.supervisor import HeartbeatFile
    procs = _spawn_gang(args, f"localhost:{_free_port()}", _child_env(args))
    spawn_t = time.time()
    pending = dict(enumerate(procs))
    failed: list[tuple[int, object]] = []
    while pending and not failed and time.time() < deadline:
        for i, p in list(pending.items()):
            rc = p.poll()
            if rc is not None:
                del pending[i]
                if rc != 0:
                    failed.append((i, rc))
        if pending and not failed and args.heartbeat_timeout:
            now = time.time()
            ages = HeartbeatFile.ages(args.heartbeat_dir, now)
            for i in pending:
                # a worker that never beat is aged from gang spawn time
                if ages.get(i, now - spawn_t) > args.heartbeat_timeout:
                    failed.append((i, "hung"))
        if pending and not failed:
            time.sleep(0.2)
    if pending and not failed:   # overall deadline hit
        failed = [(i, "timeout") for i in pending]
    # tear down the REMAINING gang: a half-dead gang cannot make progress
    # (the collectives block), so recovery is all-or-nothing
    for i, p in pending.items():
        p.kill()
        p.wait()
    return failed


def _run_launcher_supervised(args) -> dict:
    """Gang supervision: relaunch from the latest committed checkpoint.

    Detects dead (exit code) and hung (heartbeat timeout) workers, tears
    the gang down, backs off per RestartPolicy (real capped-exponential
    delays) and relaunches; workers resume from the newest readable
    checkpoint on their own.  With ``--elastic`` a lost worker shrinks the
    next incarnation to the surviving process count - the workers re-run
    the Area-Processes decomposition for the smaller mesh and remap the
    checkpoint onto it (repro.runtime.elastic.shrink_remap_state).  The
    result record gains a ``supervision`` block: restart events, per-tier
    retry counts and the actual backoff delays.
    """
    from repro.runtime import elastic
    from repro.runtime.fault import RestartPolicy
    args.ckpt_dir = args.ckpt_dir or args.out + ".ckpt"
    os.makedirs(args.ckpt_dir, exist_ok=True)
    policy = RestartPolicy(max_restarts=args.max_restarts,
                           backoff_s=args.backoff, backoff_mult=2.0,
                           backoff_cap_s=args.backoff_cap)
    events: list[str] = []
    delays: list[float] = []
    tiers = {"same": 0, "shrink": 0}
    deadline = time.time() + args.timeout
    incarnation = 0
    while True:
        args.incarnation = incarnation
        # per-incarnation heartbeat dir: a dead gang's last beats must not
        # read as liveness for the next one
        args.heartbeat_dir = os.path.join(args.ckpt_dir,
                                          f"hb_{incarnation:03d}")
        failed = _run_gang(args, deadline)
        if not failed:
            break
        events.append(
            f"fail@inc{incarnation}:"
            + ",".join(f"{r}={c}" for r, c in sorted(failed)))
        if time.time() >= deadline:
            raise SystemExit(
                f"supervised launch timed out; events={events}")
        action, delay = policy.next_action()
        if action == "abort":
            raise SystemExit(
                f"gang exceeded max restarts ({policy.max_restarts}); "
                f"events={events}")
        delays.append(delay)
        events.append(f"backoff:{delay:.6g}")
        time.sleep(delay)
        lost = {r for r, _ in failed}
        if args.elastic and args.processes > 1:
            new_p = max(args.processes - len(lost), 1)
            plan = elastic.plan_mesh(new_p * args.devices_per_process,
                                     model_width=args.row_width,
                                     prefer_pods=False)
            events.append(f"shrink:{args.processes}->{new_p}"
                          f"(mesh {plan.shape[0]}x{plan.shape[1]})")
            args.processes = new_p
            tiers["shrink"] += 1
        else:
            tiers["same"] += 1
        incarnation += 1
    with open(args.out) as f:
        rec = json.load(f)
    rec["supervision"] = dict(
        restarts=policy.restarts, incarnations=incarnation + 1,
        tiers=tiers, events=events, delays=delays,
        processes_final=args.processes, elastic=bool(args.elastic))
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


# --------------------------------------------------------------------------
# worker role
# --------------------------------------------------------------------------

def _build_spec(args):
    """Deterministic (spec, stdp, drive_boost) every rank agrees on."""
    import dataclasses

    from repro.core import models

    if args.model:
        spec, stdp = models.model_demo(args.model, scale=args.scale,
                                       stdp=True)
    else:
        spec, stdp = models.get_scenario(args.scenario, scale=args.scale)
    drive_boost = args.drive_boost
    if drive_boost is None:
        drive_boost = (3.0 if not args.model
                       and args.scenario == "hpc_benchmark" else 1.0)
    if drive_boost != 1.0:
        pops = [dataclasses.replace(p, ext_rate_hz=p.ext_rate_hz
                                    * drive_boost)
                for p in spec.populations]
        spec = dataclasses.replace(spec, populations=pops)
    if args.connectivity:
        spec = dataclasses.replace(spec, connectivity=args.connectivity)
    return spec, stdp, drive_boost


def run_worker(args: argparse.Namespace) -> dict | None:
    if args.save_every:
        return _run_worker_supervised(args)
    # imports deferred so the LAUNCHER process never touches jax (the
    # children must see XLA_FLAGS before their first jax import)
    import jax
    import numpy as np

    from repro.core import backends as backends_mod
    from repro.core import engine, multihost
    from repro.core import distributed as dist

    multihost.initialize(coordinator_address=args.coordinator,
                         num_processes=args.processes,
                         process_id=args.process_id)
    n_rows = jax.device_count() // args.row_width
    spec, stdp, drive_boost = _build_spec(args)
    backend = backends_mod.get_backend(args.sweep)
    dec = dist.mesh_decompose(spec, n_rows, args.row_width)
    mesh = multihost.make_host_mesh(n_rows, args.row_width)
    if spec.connectivity == "procedural":
        # O(owned rows): each worker generates only its own shards'
        # consts; peers exchange nothing but mirror-gid tables
        net = multihost.prepare_stacked_local(
            spec, dec, n_rows, args.row_width, mesh,
            with_blocked=backend.needs_blocked)
    else:
        net = dist.prepare_stacked(spec, dec, n_rows, args.row_width,
                                   with_blocked=backend.needs_blocked)
    cfg = dist.DistributedConfig(
        engine=engine.EngineConfig(dt=0.1,
                                   stdp=None if args.no_stdp else stdp,
                                   sweep=args.sweep,
                                   neuron_model=spec.neuron_model),
        comm_mode=args.comm_mode, overlap=not args.no_overlap,
        spike_wire=args.wire, spike_wire_remote=args.wire_remote)
    step, consts = multihost.make_multihost_step(net, mesh,
                                                 list(spec.groups), cfg)
    state = multihost.init_multihost_state(net, list(spec.groups), mesh,
                                           seed=args.seed, sweep=args.sweep,
                                           neuron_model=spec.neuron_model)

    t0 = time.time()
    run = jax.jit(lambda s, c: jax.lax.scan(lambda s, _: step(s, c), s,
                                            None, length=args.steps))
    final, bits = run(state, consts)
    bits_np = multihost.replicate_to_host(bits, mesh).astype(np.uint8)
    vm_np = multihost.replicate_to_host(final.v_m, mesh)
    overflow = int(multihost.replicate_to_host(final.wire_overflow,
                                               mesh).sum())
    elapsed = time.time() - t0
    sha = lambda a: hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest()
    split = dist.wire_bytes_split(
        args.comm_mode, args.wire, args.wire_remote, n_shards=net.n_shards,
        row_width=net.row_width, n_local=net.n_local, b_pad=net.b_pad)
    rec = dict(
        processes=args.processes, devices=jax.device_count(),
        n_rows=n_rows, row_width=args.row_width, steps=args.steps,
        scale=args.scale, seed=args.seed, sweep=args.sweep,
        scenario=None if args.model else args.scenario,
        model=spec.neuron_model, drive_boost=drive_boost,
        wire=args.wire, wire_remote=args.wire_remote or args.wire,
        comm_mode=args.comm_mode, overlap=not args.no_overlap,
        stdp=not args.no_stdp, connectivity=spec.connectivity,
        bits_sha256=sha(bits_np), vm_sha256=sha(vm_np),
        spiked=int(bits_np.sum()), overflow=overflow,
        wire_bytes_intra=split["intra"], wire_bytes_inter=split["inter"],
        elapsed_s=round(elapsed, 2),
    )
    if args.bench:
        jstep = jax.jit(step)
        s, _ = jstep(state, consts)
        jax.block_until_ready(s.v_m)
        reps = max(args.steps, 5)
        t0 = time.perf_counter()
        for _ in range(reps):
            s, _ = jstep(s, consts)
        jax.block_until_ready(s.v_m)
        rec["us_per_step"] = round(
            (time.perf_counter() - t0) / reps * 1e6, 2)
    if jax.process_index() == 0:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
        print(json.dumps(rec))
        return rec
    return None


def _run_worker_supervised(args: argparse.Namespace) -> dict | None:
    """Checkpointed, fault-injected worker under gang supervision.

    Differences from the plain worker:

    * mesh comes from :func:`repro.core.multihost.plan_elastic_mesh`
      (whatever THIS incarnation's world holds), so a shrunken gang lands
      on the smaller Area-Processes decomposition automatically;
    * the trajectory runs as a per-step jitted python loop under
      :class:`repro.runtime.supervisor.SimulationSupervisor` - heartbeat
      per step, fault injection per step, async checkpoint (full
      mesh-agnostic host snapshot + network_metadata) every
      ``--save-every`` steps;
    * on restart the worker resumes from the newest readable checkpoint:
      same topology -> overlay the snapshot's owned rows onto a fresh
      baseline; different topology -> ``elastic.shrink_remap_state``;
    * rank 0 flushes the spike-trajectory prefix atomically right before
      each checkpoint commit, so a resumed run can still report the FULL
      trajectory hash;
    * hashes are computed over GLOBAL-order arrays (``hash_order:
      "global"``) - comparable across process counts, which is what the
      shrink-restart bit-exactness contract is pinned against.
    """
    import dataclasses

    import jax
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager, network_metadata
    from repro.core import backends as backends_mod
    from repro.core import engine, multihost
    from repro.core import distributed as dist
    from repro.runtime import elastic, inject
    from repro.runtime.supervisor import HeartbeatFile, SimulationSupervisor

    multihost.initialize(coordinator_address=args.coordinator,
                         num_processes=args.processes,
                         process_id=args.process_id)
    rank = jax.process_index()
    spec, stdp, drive_boost = _build_spec(args)
    backend = backends_mod.get_backend(args.sweep)
    mesh = multihost.plan_elastic_mesh(args.row_width)
    n_rows, row_width = np.asarray(mesh.devices, dtype=object).shape
    dec = dist.mesh_decompose(spec, n_rows, row_width)
    if spec.connectivity == "procedural":
        net = multihost.prepare_stacked_local(
            spec, dec, n_rows, row_width, mesh,
            with_blocked=backend.needs_blocked)
    else:
        net = dist.prepare_stacked(spec, dec, n_rows, row_width,
                                   with_blocked=backend.needs_blocked)
    cfg = dist.DistributedConfig(
        engine=engine.EngineConfig(dt=0.1,
                                   stdp=None if args.no_stdp else stdp,
                                   sweep=args.sweep,
                                   neuron_model=spec.neuron_model),
        comm_mode=args.comm_mode, overlap=not args.no_overlap,
        spike_wire=args.wire, spike_wire_remote=args.wire_remote)
    step, consts = multihost.make_multihost_step(net, mesh,
                                                 list(spec.groups), cfg)

    ckpt_dir = args.ckpt_dir or args.out + ".ckpt"
    mgr = CheckpointManager(ckpt_dir, keep=args.keep_ckpts)
    lo, hi = ((0, net.n_shards) if net.local_slice is None
              else net.local_slice)
    owner, li = dec.owner, dec.local_index()
    meta_fields = ("weights_layout", "neuron_model")

    # fresh baseline: restores OVERLAY onto it, because None/empty-dict
    # fields (drive_key, a model's empty aux) leave no checkpoint leaves
    base = dist.init_stacked_state(net, list(spec.groups), seed=args.seed,
                                   sweep=args.sweep,
                                   neuron_model=spec.neuron_model)
    carried = {"wire_overflow": 0, "gate_overflow": 0}
    resumed_from = None
    start_step = 0
    latest = mgr.latest_step()
    if latest is None:
        fields = {f.name: getattr(base, f.name)
                  for f in dataclasses.fields(base)
                  if f.name not in meta_fields}
    else:
        got, host, md = mgr.load_host()
        if md.get("sweep", args.sweep) != args.sweep:
            raise SystemExit(
                f"checkpoint at step {got} was written by sweep="
                f"{md['sweep']}, cannot resume with {args.sweep}")
        old_rows = int(md.get("n_rows", n_rows))
        old_width = int(md.get("row_width", row_width))
        if (old_rows, old_width) == (n_rows, row_width):
            fields = {}
            for f in dataclasses.fields(base):
                if f.name in meta_fields:
                    continue
                v = getattr(base, f.name)
                hv = host.get(f.name)
                if isinstance(v, dict):
                    fields[f.name] = {
                        k: (np.asarray(hv[k])[lo:hi]
                            if hv is not None and k in hv else np.array(a))
                        for k, a in v.items()}
                elif v is None or hv is None:
                    fields[f.name] = v
                else:
                    fields[f.name] = np.asarray(hv)[lo:hi]
        else:
            fields, carried = elastic.shrink_remap_state(
                spec, args.seed, host, step=got,
                old_n_rows=old_rows, old_row_width=old_width,
                new_dec=dec, new_net=net, groups=list(spec.groups),
                sweep=args.sweep, neuron_model=spec.neuron_model,
                stdp_active=not args.no_stdp)
        start_step = resumed_from = got
    state = multihost.state_from_fields(
        fields, mesh, local_slice=net.local_slice,
        weights_layout=base.weights_layout, neuron_model=base.neuron_model)

    # global-order spike trajectory, one (N,) uint8 row per step; the
    # committed prefix rides next to the checkpoints (atomic replace, not
    # GC'd) so a restarted incarnation reloads exactly the rows matching
    # its restored step
    traj_path = lambda s: os.path.join(ckpt_dir, f"traj_{s:09d}.npy")
    bits_rows: list[np.ndarray] = []
    if resumed_from:
        prefix = np.load(traj_path(resumed_from))
        if prefix.shape[0] != resumed_from:
            raise SystemExit(
                f"trajectory prefix {traj_path(resumed_from)} holds "
                f"{prefix.shape[0]} rows, checkpoint says {resumed_from}")
        bits_rows = [np.asarray(r, np.uint8) for r in prefix]

    hb = (HeartbeatFile(args.heartbeat_dir, rank)
          if args.heartbeat_dir else None)
    injector = inject.FaultInjector.from_args(
        args.fault_inject, rank=rank, mode="process",
        state_dir=os.path.join(ckpt_dir, "faults"), ckpt_dir=ckpt_dir)

    def metadata_fn(s, _state):
        return network_metadata(spec, seed=args.seed, extra=dict(
            step=s, n_rows=n_rows, row_width=row_width, sweep=args.sweep,
            neuron_model=spec.neuron_model, stdp=not args.no_stdp,
            connectivity=spec.connectivity))

    def flush_traj(s, _state):
        if rank != 0:
            return
        tmp = traj_path(s) + ".tmp"
        with open(tmp, "wb") as f:   # file object: no np.save .npy-append
            np.save(f, np.stack(bits_rows[:s]).astype(np.uint8))
        os.replace(tmp, traj_path(s))

    jstep = jax.jit(step)

    def step_fn(s, _i):
        return jstep(s, consts)

    def on_step(sstep, _state, bits):
        # replicate_to_host is a collective: every rank appends in lockstep
        b = np.asarray(multihost.replicate_to_host(bits, mesh), np.uint8)
        bits_rows.append(b[owner, li])

    sup = SimulationSupervisor(
        mgr if rank == 0 else None, save_every=args.save_every,
        heartbeat=hb, injector=injector,
        snapshot_fn=lambda s: multihost.snapshot_host_state(s, mesh),
        metadata_fn=metadata_fn, pre_save=flush_traj, restore_fn=None)
    t0 = time.time()
    final, _ = sup.run(state, step_fn, args.steps, start_step=start_step,
                       on_step=on_step)
    elapsed = time.time() - t0

    bits_all = np.stack(bits_rows).astype(np.uint8)      # (steps, N)
    vm_g = np.asarray(multihost.replicate_to_host(final.v_m, mesh))[
        owner, li]                                       # (N,) global order
    overflow = carried["wire_overflow"] + int(
        multihost.replicate_to_host(final.wire_overflow, mesh).sum())
    gate = carried["gate_overflow"] + int(
        multihost.replicate_to_host(final.gate_overflow, mesh).sum())
    if rank != 0:
        return None
    sha = lambda a: hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest()
    split = dist.wire_bytes_split(
        args.comm_mode, args.wire, args.wire_remote, n_shards=net.n_shards,
        row_width=net.row_width, n_local=net.n_local, b_pad=net.b_pad)
    rec = dict(
        processes=args.processes, devices=jax.device_count(),
        n_rows=n_rows, row_width=row_width, steps=args.steps,
        scale=args.scale, seed=args.seed, sweep=args.sweep,
        scenario=None if args.model else args.scenario,
        model=spec.neuron_model, drive_boost=drive_boost,
        wire=args.wire, wire_remote=args.wire_remote or args.wire,
        comm_mode=args.comm_mode, overlap=not args.no_overlap,
        stdp=not args.no_stdp, connectivity=spec.connectivity,
        bits_sha256=sha(bits_all), vm_sha256=sha(vm_g),
        spiked=int(bits_all.sum()), overflow=overflow,
        gate_overflow=gate,
        wire_bytes_intra=split["intra"], wire_bytes_inter=split["inter"],
        elapsed_s=round(elapsed, 2),
        # supervised-runtime extras
        hash_order="global", supervised=True, save_every=args.save_every,
        resumed_from=resumed_from, incarnation=args.incarnation,
        ckpt_events=sup.events,
    )
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))
    return rec


def _cluster_env():
    """Jax-free peek for cluster launch env vars; the full parse lives in
    repro.core.multihost (whose import pulls in jax - fine, because a hit
    means THIS process is a worker, not the jax-free local launcher)."""
    if not (os.environ.get("REPRO_COORD_ADDR")
            or os.environ.get("SLURM_PROCID")):
        return None
    from repro.core.multihost import detect_cluster_env
    return detect_cluster_env()


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.process_id is None:
        # real-cluster launches (SLURM / k8s-style env vars) need no CLI
        # plumbing: every rank runs the same command line and picks up its
        # identity from the environment (ROADMAP multi-host follow-on)
        env = _cluster_env()
        # single-task allocations (e.g. a batch step with SLURM_PROCID=0)
        # still want the LOCAL launcher role, so only >1 ranks divert
        if env is not None and env["num_processes"] > 1:
            args.process_id = env["process_id"]
            args.processes = env["num_processes"]
            args.coordinator = args.coordinator or env["coordinator_address"]
    if args.process_id is not None:
        run_worker(args)
        return
    rec = run_launcher(args)
    print(f"[multihost] {args.processes} process(es) ok: "
          f"spiked={rec['spiked']} overflow={rec['overflow']} "
          f"bits={rec['bits_sha256'][:12]}... -> {args.out}")


if __name__ == "__main__":
    main()
