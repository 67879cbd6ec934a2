"""Multi-host backend: the two-level decomposition across processes.

This is the layer that makes problem size scale with process count
(ROADMAP "multi-host backend"; the schedule is Du et al. 2022's
low-latency brain-simulation exchange, the scaling reference is
Pastorelli et al. 2015).  It threads BOTH existing registries - every
``SweepBackend`` (flat / bucketed / pallas / pallas:auto) and every
``SpikeWire`` (including per-tier selection) - through a multi-process
device mesh with zero changes to the per-shard hot path: the shard_map'ed
step of :mod:`repro.core.distributed` is reused verbatim; only array
*placement* is multi-host-aware here.

Host-aware mapping (DESIGN.md §11)
----------------------------------
The (rows, row_width) mesh of the two-level decomposition is built
row-aligned to hosts: :func:`make_host_mesh` lays ``jax.devices()`` out
process-major and validates that every mesh row (an Area-Processes group)
lives on ONE process.  Consequences:

* the intra-row spike-bitmap ``all_gather`` (the dense tier) never
  crosses a host - it moves bytes inside one process's devices;
* only the boundary payloads (``n(boundary) << n_local`` under area
  mapping) ride the inter-host fabric - and they can take their own wire
  (``DistributedConfig.spike_wire_remote``, e.g. "sparse" IDs inter-host
  under a "packed" intra-host bitmap);
* the boundary collective is issued before the delay>=2 sweep
  (``_exchange_issue`` ordering) and consumed only by the delay-1 path,
  so the slow inter-host hop overlaps the independent intra-host compute -
  the paper's §III.C communication thread, as dataflow.

Array plumbing: in a multi-process program every jit input must be a
GLOBAL array whose addressable shards live on the calling process.
:func:`shard_stacked` builds those from the (S, ...) host-side arrays via
``jax.make_array_from_process_local_data`` (each process contributes its
own rows); :func:`replicate_to_host` is the inverse for results.  CI runs
this with local CPU processes (``repro.launch.multihost`` spawns them and
forces per-process host devices); on a real cluster the same code runs
under the platform's process launcher with TPU/GPU device sets.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distributed as dist
from repro.core import snn

__all__ = ["initialize", "detect_cluster_env", "HostTopology",
           "make_host_mesh", "host_topology", "local_shard_slice",
           "shard_stacked", "replicate_to_host", "make_multihost_step",
           "init_multihost_state", "prepare_stacked_local",
           "plan_elastic_mesh", "state_from_fields",
           "snapshot_host_state"]

#: default coordinator port when only a nodelist is known (SLURM);
#: override with REPRO_COORD_PORT
DEFAULT_COORD_PORT = 12321


def _first_slurm_host(nodelist: str) -> str:
    """First hostname of a SLURM nodelist expression.

    Handles the common compact forms: ``node[003-008,010],other[1-2]`` ->
    ``node003``, plain comma lists (``login1,nid[001-002]`` -> ``login1``),
    and bare hostnames.  The prefix match excludes commas so a plain first
    element never swallows a later bracketed group.  (Full ``scontrol
    show hostnames`` semantics are not needed - only rank 0's host serves
    as the coordinator.)
    """
    m = re.match(r"^([^\[,]+)\[([^\]\-,]+)", nodelist.strip())
    if m:
        return m.group(1) + m.group(2)
    return nodelist.split(",")[0].strip()


def detect_cluster_env(environ=None) -> dict | None:
    """Cluster launch parameters from the environment, or None.

    Two conventions are recognized (ROADMAP multi-host follow-on), so
    real-cluster launches need no CLI plumbing:

    * **k8s-style explicit vars** (checked first - they are opt-in):
      ``REPRO_COORD_ADDR`` (host:port), ``REPRO_NUM_PROC``,
      ``REPRO_PROC_ID``;
    * **SLURM**: ``SLURM_PROCID`` / ``SLURM_NTASKS`` /
      ``SLURM_STEP_NODELIST`` (falling back to ``SLURM_JOB_NODELIST``);
      the coordinator is the nodelist's first host on
      ``REPRO_COORD_PORT`` (default 12321).

    Returns ``dict(coordinator_address=..., num_processes=...,
    process_id=...)`` ready to splat into :func:`initialize`.
    """
    env = os.environ if environ is None else environ
    if env.get("REPRO_COORD_ADDR"):
        return dict(coordinator_address=env["REPRO_COORD_ADDR"],
                    num_processes=int(env.get("REPRO_NUM_PROC", "1")),
                    process_id=int(env.get("REPRO_PROC_ID", "0")))
    if env.get("SLURM_PROCID") is not None and env.get("SLURM_NTASKS"):
        nodelist = (env.get("SLURM_STEP_NODELIST")
                    or env.get("SLURM_JOB_NODELIST"))
        if not nodelist:
            return None
        port = env.get("REPRO_COORD_PORT", str(DEFAULT_COORD_PORT))
        return dict(
            coordinator_address=f"{_first_slurm_host(nodelist)}:{port}",
            num_processes=int(env["SLURM_NTASKS"]),
            process_id=int(env["SLURM_PROCID"]))
    return None


def initialize(*, coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> bool:
    """Join (or skip) the multi-process jax runtime.

    With no explicit arguments the launch parameters are taken from the
    environment (:func:`detect_cluster_env`: SLURM or k8s-style vars), so
    ``srun python -m repro.launch.multihost`` and a k8s pod template both
    work with zero CLI plumbing; outside any cluster the no-args call is
    a no-op.  ``num_processes <= 1`` is a no-op (the single-process paths
    need no distributed runtime) so callers can be launcher-agnostic.  On
    CPU the cross-process collectives need the gloo implementation; the
    config knob only exists on some jax versions, so it is set best-effort
    (newer versions default to gloo).  Call BEFORE any operation that
    touches devices; returns True iff the distributed runtime was
    initialized.
    """
    if num_processes is None and process_id is None:
        detected = detect_cluster_env()
        if detected is None:
            return False
        if coordinator_address is not None:
            detected["coordinator_address"] = coordinator_address
        coordinator_address = detected["coordinator_address"]
        num_processes = detected["num_processes"]
        process_id = detected["process_id"]
    num_processes = 1 if num_processes is None else num_processes
    process_id = 0 if process_id is None else process_id
    if num_processes <= 1:
        return False
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except AttributeError:  # knob removed: gloo is the default there
        pass
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """How the (rows, row_width) decomposition mesh maps onto processes."""

    num_processes: int
    process_id: int
    n_rows: int
    row_width: int
    row_process: tuple[int, ...]   # owning process per mesh row

    @property
    def rows_per_host(self) -> int:
        return self.n_rows // max(self.num_processes, 1)

    @property
    def n_shards(self) -> int:
        return self.n_rows * self.row_width


def make_host_mesh(n_rows: int, row_width: int,
                   axis_names: tuple[str, ...] = ("data", "model")) -> Mesh:
    """Host-aligned (n_rows, row_width) mesh over ``jax.devices()``.

    Devices are laid out process-major (the order ``jax.devices()``
    guarantees), so consecutive ``row_width`` blocks form the mesh rows;
    the function validates that every row's devices share one process -
    the invariant that keeps the intra-row bitmap gather intra-host.  In a
    multi-process program the mesh must cover every device (a process with
    no addressable mesh shards cannot participate in the jit).
    """
    devs = np.asarray(jax.devices(), dtype=object)
    need = n_rows * row_width
    if need > devs.size:
        raise ValueError(
            f"mesh ({n_rows}x{row_width}) needs {need} devices, have "
            f"{devs.size}")
    if jax.process_count() > 1 and need != devs.size:
        raise ValueError(
            f"multi-process mesh must cover all {devs.size} devices, "
            f"requested {n_rows}x{row_width}={need}")
    grid = devs[:need].reshape(n_rows, row_width)
    for r in range(n_rows):
        procs = {d.process_index for d in grid[r]}
        if len(procs) != 1:
            raise ValueError(
                f"mesh row {r} spans processes {sorted(procs)}; pick a "
                "row_width that divides the per-host device count so "
                "Area-Processes rows align to hosts (intra-row gathers "
                "must stay intra-host)")
    return Mesh(grid, axis_names)


def plan_elastic_mesh(row_width: int,
                      axis_names: tuple[str, ...] = ("data", "model")
                      ) -> Mesh:
    """Host-aligned mesh for WHATEVER devices this incarnation has.

    The elastic-restart entry point: instead of a fixed (n_rows,
    row_width) the caller states only the row width, and the elastic row
    plan (:func:`repro.runtime.elastic.plan_mesh`) re-runs for the
    current world size - so a gang restarted on fewer processes lands on
    the correspondingly smaller Area-Processes decomposition with zero
    extra plumbing.  Degrades the row width (halving) only when fewer
    devices than one row survive.
    """
    from repro.runtime.elastic import plan_mesh
    plan = plan_mesh(jax.device_count(), model_width=row_width,
                     prefer_pods=False)
    n_rows, width = plan.shape
    return make_host_mesh(n_rows, width, axis_names)


def host_topology(mesh: Mesh) -> HostTopology:
    """Topology record for a host-aligned mesh (validates alignment)."""
    grid = np.asarray(mesh.devices, dtype=object)
    if grid.ndim > 2:   # (outer..., inner): rows = all outer axes flattened
        grid = grid.reshape(-1, grid.shape[-1])
    n_rows, row_width = grid.shape
    row_process = []
    for r in range(n_rows):
        procs = {d.process_index for d in grid[r]}
        if len(procs) != 1:
            raise ValueError(f"mesh row {r} spans processes {sorted(procs)}")
        row_process.append(procs.pop())
    return HostTopology(num_processes=jax.process_count(),
                        process_id=jax.process_index(),
                        n_rows=n_rows, row_width=row_width,
                        row_process=tuple(row_process))


def local_shard_slice(mesh: Mesh) -> slice:
    """Contiguous slice of the stacked shard axis this process owns.

    The stacked (S, ...) arrays are sharded over the flattened mesh, so
    shard s lives on flat device s; with the process-major layout of
    :func:`make_host_mesh` each process owns one contiguous block.
    """
    flat = np.asarray(mesh.devices, dtype=object).reshape(-1)
    pid = jax.process_index()
    mine = [i for i, d in enumerate(flat) if d.process_index == pid]
    if not mine:
        return slice(0, 0)
    lo, hi = mine[0], mine[-1] + 1
    if mine != list(range(lo, hi)):
        raise ValueError(
            "this process's mesh devices are not contiguous along the "
            "shard axis; build the mesh with make_host_mesh")
    return slice(lo, hi)


def shard_stacked(tree: Any, mesh: Mesh, *,
                  local_slice: tuple[int, int] | None = None) -> Any:
    """(S, ...) host-side arrays -> GLOBAL arrays sharded on axis 0.

    Default (global) mode: every process passes the full stacked value
    (cheap: build-time numpy) and contributes only its own rows; the
    result is a global jax.Array usable as a jit input from every process.
    Works unchanged in a single-process program (where it is a plain
    sharded device_put).

    ``local_slice=(lo, hi)`` switches to LOCAL mode - the O(owned rows)
    contract of the procedural build (:func:`prepare_stacked_local`): the
    passed arrays hold ONLY this process's rows (leading dim ``hi - lo``)
    and are shipped verbatim; the global shape is reconstructed from the
    mesh size.  No process ever holds another process's consts.
    """
    sh = NamedSharding(mesh, P(mesh.axis_names))
    sl = local_shard_slice(mesh)
    S = int(np.asarray(mesh.devices, dtype=object).size)

    def put(a):
        a = np.asarray(a)
        if local_slice is not None:
            if (sl.start, sl.stop) != tuple(local_slice):
                raise ValueError(
                    f"local arrays cover shards {local_slice} but this "
                    f"process owns {(sl.start, sl.stop)} on the mesh")
            return jax.make_array_from_process_local_data(
                sh, np.ascontiguousarray(a), (S,) + a.shape[1:])
        return jax.make_array_from_process_local_data(
            sh, np.ascontiguousarray(a[sl]), a.shape)

    return jax.tree.map(put, tree)


def replicate_to_host(x, mesh: Mesh) -> np.ndarray:
    """Fetch a (possibly non-addressable) global array as full numpy on
    EVERY process - one replicating collective, then a local read."""
    rep = jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))(x)
    return np.asarray(rep.addressable_data(0))


def _allgather_host(a: np.ndarray) -> np.ndarray:
    """Host-side allgather: (``local...``) -> (P, ``local...``) numpy.

    Single-process programs skip the collective (the degenerate P=1 axis
    is added locally) so the local-build code path is testable without a
    cluster."""
    if jax.process_count() <= 1:
        return np.asarray(a)[None]
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(np.asarray(a)))


def prepare_stacked_local(spec, dec, n_rows: int, row_width: int,
                          mesh: Mesh, *, pad_to_multiple: int = 8,
                          with_blocked: bool = True,
                          block_shapes=None) -> dist.StackedNetwork:
    """O(owned rows) multi-process twin of
    :func:`repro.core.distributed.prepare_stacked` for PROCEDURAL specs.

    Every process builds only the shards its mesh devices own; nothing
    proportional to the global edge count is ever held or broadcast.  The
    processes still have to AGREE on the stacked geometry and the exchange
    metadata, which procedural connectivity makes almost free:

    * per-shard edge counts, row degrees (hence the shared blocked
      (PB, EB) shape) and local sizes are ANALYTIC under the
      fixed-indegree rule - every process derives them for all shards
      with zero RNG and zero communication;
    * only the remote-mirror tables need real draws: each process runs
      the counting pass (pass A) over its own rows and allgathers the
      padded remote gid sets - O(sum of mirror tables), not O(edges);
    * every remote mirror of a procedural shard is referenced by a
      generated edge BY CONSTRUCTION, so the boundary lists derived from
      those tables match the materialized ``used``-filtered computation
      bit-exactly (pinned by tests/test_multihost.py).

    Returns a StackedNetwork whose (S, ...) arrays hold only this
    process's rows, with ``local_slice`` recording the owned range; feed
    it to :func:`make_multihost_step` / :func:`init_multihost_state`,
    which ship the local rows via ``shard_stacked(local_slice=...)``.
    """
    from repro.core import builder as builder_mod
    if spec.connectivity != "procedural":
        raise ValueError(
            "prepare_stacked_local needs connectivity='procedural' - a "
            "materialized spec has a global edge list anyway, use "
            "prepare_stacked")
    S = n_rows * row_width
    assert S == dec.n_devices
    sl = local_shard_slice(mesh)
    lo, hi = sl.start, sl.stop
    row_of = np.arange(S) // row_width

    # --- analytic dims for ALL shards (no RNG, no comms) -------------------
    e_all = builder_mod.shard_edge_counts(spec, dec)
    degrees = [builder_mod.shard_row_degrees(spec, dec, s)
               for s in range(S)]
    n_local_all = [int(p.size) for p in dec.parts]

    # --- pass A on OWNED shards: remote-mirror gid sets --------------------
    own_remotes = []
    for s in range(lo, hi):
        d = builder_mod.procedural_shard_raw(spec, dec, s, dims_only=True)
        own_remotes.append(d["mirror_gids"][d["owned"].size:])
        if d["e"] != int(e_all[s]) or not np.array_equal(
                d["row_degree"], degrees[s]):
            raise AssertionError(
                f"shard {s}: generated dims disagree with the analytic "
                "fixed-indegree counts")

    # --- two small allgather rounds: counts, then padded gid tables --------
    counts_local = np.asarray([r.size for r in own_remotes], np.int64)
    counts_all = _allgather_host(counts_local).reshape(-1)
    if counts_all.size != S:
        raise ValueError(
            f"processes own unequal shard counts ({counts_all.size} "
            f"gathered entries for {S} shards); align the mesh to hosts "
            "with make_host_mesh")
    r_pad = max(int(counts_all.max()), 1)
    table_local = np.full((hi - lo, r_pad), -1, np.int64)
    for i, r in enumerate(own_remotes):
        table_local[i, :r.size] = r
    tables = _allgather_host(table_local).reshape(S, r_pad)

    # --- agreed pads + boundary lists (identical on every process) ---------
    plan = dict(e=[int(e) for e in e_all],
                n_local=n_local_all,
                n_mirror=[n_local_all[s] + int(counts_all[s])
                          for s in range(S)],
                row_degree=degrees)
    pads = dist.resolve_stack_pads(plan, spec,
                                   pad_to_multiple=pad_to_multiple,
                                   with_blocked=with_blocked,
                                   block_shapes=block_shapes)
    consumers: list[list[np.ndarray]] = [[] for _ in range(S)]
    for s in range(S):
        rg = tables[s, :int(counts_all[s])]
        src = dec.owner[rg]
        for src_shard in np.unique(src):
            if row_of[src_shard] != row_of[s]:
                sel = src == src_shard
                consumers[int(src_shard)].append(np.unique(
                    np.searchsorted(dec.parts[int(src_shard)], rg[sel])))
    boundary = [np.unique(np.concatenate(c)) if c else np.zeros(0, np.int64)
                for c in consumers]
    b_pad, boundary_slots = dist._boundary_slots_from_lists(
        boundary, pads["n_local_pad"], pad_to_multiple)

    # --- full build of OWNED shards, streamed into local stacked arrays ---
    Sl = hi - lo
    nm = pads["n_mirror_pad"]
    graph = dist._alloc_stacked_graph(Sl, pads["e_pad"],
                                      pads["n_local_pad"], nm,
                                      pads["blocked_meta"])
    src_all = np.zeros((Sl, nm), np.int32)
    idx_all = np.zeros((Sl, nm), np.int32)
    mirror_is_intra = np.zeros((Sl, nm), dtype=bool)
    mirror_row_gather = np.zeros((Sl, nm), dtype=np.int32)
    mirror_remote_gather = np.zeros((Sl, nm), dtype=np.int32)
    shard_iter = dist.procedural_shard_graphs(
        spec, dec, range(lo, hi), pads, pad_to_multiple=pad_to_multiple,
        with_blocked=with_blocked)
    for i, g in enumerate(shard_iter):
        dist._fill_stacked_row(graph, i, g, pads["blocked_meta"])
        src_all[i] = np.asarray(g.mirror_src_shard)
        idx_all[i] = np.asarray(g.mirror_src_idx)
        (mirror_is_intra[i], mirror_row_gather[i],
         mirror_remote_gather[i]) = dist._mirror_meta_row(
            src_all[i], idx_all[i], lo + i, row_of, boundary, b_pad,
            pads["n_local_pad"], row_width)

    return dist.StackedNetwork(
        n_shards=S, row_width=row_width, n_local=pads["n_local_pad"],
        n_mirror=nm, n_edges=pads["e_pad"], b_pad=b_pad,
        max_delay=spec.max_delay, graph=graph,
        blocked_meta=pads["blocked_meta"], block_shapes_spec=block_shapes,
        local_slice=(lo, hi),
        boundary_slots=boundary_slots[lo:hi],
        mirror_is_intra=mirror_is_intra,
        mirror_row_gather=mirror_row_gather,
        mirror_remote_gather=mirror_remote_gather,
        mirror_src_flat=src_all)


def make_multihost_step(net: dist.StackedNetwork, mesh: Mesh,
                        groups: Sequence[snn.LIFParams],
                        cfg: dist.DistributedConfig):
    """Multi-process twin of :func:`repro.core.distributed.make_distributed_step`.

    The shard_map'ed step program is IDENTICAL (same `_build_step`, same
    backend registry dispatch, same two-tier exchange); the difference is
    purely placement - the stacked consts become global arrays with each
    process contributing its own rows.  Returns ``(step, consts)`` where
    ``step(state, consts) -> (state, bits)``: unlike the single-process
    entry point the consts are an explicit OPERAND, because jit forbids
    closing over arrays that span non-addressable devices - pass them
    through every jit/scan boundary.  ``state`` comes from
    :func:`init_multihost_state` (or any state of global arrays).
    """
    host_topology(mesh)   # validate row/host alignment up front
    backend = dist.check_net_backend(net, cfg)
    smapped = dist._build_step(
        mesh, groups, cfg, net.max_delay, net.n_local, net.n_mirror,
        net.blocked_meta if backend.needs_blocked else None)
    consts = shard_stacked(
        dist.stacked_consts(net, needs_blocked=backend.needs_blocked,
                            dt=cfg.engine.dt),
        mesh, local_slice=net.local_slice)
    return smapped, consts


def init_multihost_state(net: dist.StackedNetwork, groups, mesh: Mesh,
                         seed: int = 0, dtype=jnp.float32,
                         weight_dtype=None, sweep: str | None = None,
                         neuron_model: str = "lif") -> dist.DistState:
    """Globally sharded :class:`DistState` for a multi-process mesh.

    Every process computes the identical full stacked state (deterministic
    from ``seed``; the per-shard PRNG keys are derived from shard index,
    not process index) and ships only its own rows - so a 2-process x
    4-device run and a 1-process x 8-device run start from bit-identical
    state, which is what the trajectory-equivalence contract rests on.
    For a locally built net (``net.local_slice``, the procedural O(owned
    rows) path) the state leaves are computed local-rows-only up front -
    same trajectory, no full-network staging.  ``neuron_model`` selects
    the dynamics (DESIGN.md §12); the model's ``aux`` arrays shard like
    every other (S, ...) leaf.
    """
    full = dist.init_stacked_state(net, list(groups), seed=seed, dtype=dtype,
                                   weight_dtype=weight_dtype, sweep=sweep,
                                   neuron_model=neuron_model)
    meta = {"weights_layout", "neuron_model"}   # static markers, not leaves
    return state_from_fields(
        {f.name: getattr(full, f.name)
         for f in dataclasses.fields(full) if f.name not in meta},
        mesh, local_slice=net.local_slice,
        weights_layout=full.weights_layout,
        neuron_model=full.neuron_model)


def state_from_fields(fields: dict, mesh: Mesh, *,
                      local_slice: tuple[int, int] | None = None,
                      weights_layout: str = "flat",
                      neuron_model: str = "lif") -> dist.DistState:
    """Shard a host-side DistState field dict onto the mesh.

    The one place (S, ...) state arrays become global arrays: fresh init
    (:func:`init_multihost_state`), same-topology checkpoint restore
    (slice the :func:`snapshot_host_state` dict to the owned rows) and
    elastic shrink-restart (:func:`repro.runtime.elastic.
    shrink_remap_state` output) all feed through here, so placement rules
    can never diverge between the three.  With ``local_slice`` the arrays
    hold only this process's rows (shipped verbatim); otherwise each
    process contributes its slice of the full value.
    """
    sharded = shard_stacked(fields, mesh, local_slice=local_slice)
    return dist.DistState(weights_layout=weights_layout,
                          neuron_model=neuron_model, **sharded)


def snapshot_host_state(state: dist.DistState, mesh: Mesh) -> dict:
    """Full host-side field dict of a (possibly multi-process) DistState.

    One replicating collective per leaf, so EVERY process must call this
    at the same point in its step loop (the SimulationSupervisor's
    ``snapshot_fn`` contract) and every process gets the full (S, ...)
    value - which is what makes the written checkpoint mesh-agnostic and
    hence restorable onto a DIFFERENT process count.  Static markers
    (weights_layout, neuron_model) are NOT captured: they are re-derived
    from the restoring run's config, which must request the same layout.
    """
    meta = {"weights_layout", "neuron_model"}
    out = {}
    for f in dataclasses.fields(state):
        if f.name in meta:
            continue
        v = getattr(state, f.name)
        out[f.name] = jax.tree.map(lambda a: replicate_to_host(a, mesh), v)
    return out
