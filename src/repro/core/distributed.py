"""Distributed SNN engine: indegree sub-graphs on a TPU mesh via shard_map.

The mesh mapping of the paper's two-level decomposition (DESIGN.md §2):

* the OUTER mesh axes ("pod", "data") index *rows* of devices; each row is an
  Area-Processes group (one or more atlas areas packed by estimated edge
  memory - the paper's Area-Processes Mapping at row granularity);
* the INNER axis ("model") indexes the Multisection Division of each row's
  post-neurons - ``row_width`` spatial cells per row.

Each device owns one indegree sub-graph.  Its mirror table splits into

* **intra-row** mirrors (the paper's *local* sub-graph ``inS^l``): served by a
  dense spike-bitmap ``all_gather`` along "model" only - cheap, dense,
  intra-area traffic; and
* **remote** mirrors (``inS^r``): served by gathering only the *boundary*
  neurons (those with inter-row consumers) across the whole mesh - the
  fixed-width analogue of CORTEX's Spikes Broadcast of IDs.  Because
  ``n(boundary) << n(local)`` under area mapping, total traffic collapses
  from S*n_local (Random Equivalent Mapping) to M*n_local + S*B.

Overlap (paper §III.C): spikes fired at step t-1 are carried RAW in the scan
state and exchanged at the START of step t, while the synaptic sweep for
delays >= 2 (which only needs older ring slots) proceeds independently; the
delay-1 sweep and the ring write consume the collective's result.  On TPU,
XLA's async collectives overlap the exchange with that independent compute -
the dataflow twin of CORTEX's dedicated communication thread.

The per-shard hot path (sweep, neuron update, STDP) is NOT reimplemented
here: it dispatches through the execution-backend registry of
:mod:`repro.core.backends` (``cfg.engine.sweep`` selects flat / bucketed /
pallas), so the distributed step and the single-shard engine share one code
path; only the exchange and the overlap schedule are distributed-specific.
For the pallas backend the stacked ``blk_*`` consts carry each shard's
post-block ELL arrays (DESIGN.md §2/§9).

The exchange payload itself goes through the SpikeWire codec registry of
:mod:`repro.core.wire` (``cfg.spike_wire`` selects f32 / u8 / packed /
sparse, DESIGN.md §10): both gathers - the intra-row local bitmap and the
cross-row boundary payload - encode before and decode after the
collective, so CORTEX's ID-based Spikes Broadcast ("sparse") and the dense
bitmap wires are one config switch apart, and per-wire traffic accounting
(:func:`wire_bytes_per_step` / :func:`wire_bytes_split`) comes from the
same codec that runs on the wire.  The two tiers may ride DIFFERENT wires
(``cfg.spike_wire_remote``): under the host-aligned mesh of
:mod:`repro.core.multihost` the intra-row tier never leaves a host while
the boundary tier is the inter-host hop, so e.g. "packed" intra-host +
"sparse" inter-host puts the ID wire exactly where small messages matter.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backends as backends_mod
from repro.core import neuron_models as neuron_models_mod
from repro.core import snn, stdp as stdp_mod
from repro.core import wire as wire_mod
from repro.core.builder import NetworkSpec, build_shards
from repro.core.decomposition import (Decomposition, apportion_devices,
                                      multisection_divide)
from repro.core.engine import (DRIVE_SALT, EngineConfig, ShardGraph,
                               drive_threshold, phase, poisson_events)
from repro.core.layout import BlockedGraph, DEFAULT_PB

__all__ = ["mesh_decompose", "StackedNetwork", "prepare_stacked",
           "DistributedConfig", "make_distributed_step", "init_stacked_state",
           "wire_bytes_per_step", "wire_bytes_for_dims", "wire_bytes_split",
           "stacked_consts", "check_net_backend", "procedural_stack_plan",
           "resolve_stack_pads", "procedural_shard_graphs",
           "advance_key_data"]


# --------------------------------------------------------------------------
# mesh-aligned decomposition
# --------------------------------------------------------------------------

def mesh_decompose(spec: NetworkSpec, n_rows: int, row_width: int, *,
                   method: str = "area") -> Decomposition:
    """Two-level decomposition aligned to a (rows=pod*data, model) mesh.

    Level 1: pack areas onto rows proportionally to estimated edge memory
    (greedy largest-first into emptiest row - Area-Processes Mapping).
    Level 2: multisection-divide each row's neurons into ``row_width`` cells.

    ``method='random'`` is the Random Equivalent Mapping baseline on the same
    mesh layout (areas ignored), for the Fig. 9-vs-10 comparison.
    """
    rng = np.random.default_rng(spec.seed)
    n_devices = n_rows * row_width
    off = spec.pop_offsets()
    sizes = spec.area_sizes()
    n_areas = len(spec.areas)

    # per-area edge-memory weights
    edge_w = np.zeros(n_areas)
    for pr in spec.projections:
        dst = spec.populations[pr.dst_pop]
        edge_w[dst.area] += pr.indegree * dst.n
    edge_w = np.maximum(edge_w, 1.0)

    area_starts = np.zeros(n_areas + 1, dtype=np.int64)
    for i, p in enumerate(spec.populations):
        area_starts[p.area + 1] = off[i + 1]
    for a in range(1, n_areas + 1):  # forward-fill empty areas
        area_starts[a] = max(area_starts[a], area_starts[a - 1])

    if method == "random":
        # equal random split across rows (Random Equivalent Mapping):
        # array_split keeps row sizes within 1 of each other even when
        # n_neurons % n_rows != 0
        perm = rng.permutation(spec.n_neurons)
        row_of_neuron = np.empty(spec.n_neurons, dtype=np.int64)
        for r, s in enumerate(np.array_split(perm, n_rows)):
            row_of_neuron[s] = r
    else:
        if n_areas >= n_rows:
            # pack areas into rows: largest weight first, into lightest row
            row_load = np.zeros(n_rows)
            area_row = np.zeros(n_areas, dtype=np.int64)
            for a in np.argsort(-edge_w, kind="stable"):
                r = int(np.argmin(row_load))
                area_row[a] = r
                row_load[r] += edge_w[a]
            row_of_neuron = np.empty(spec.n_neurons, dtype=np.int64)
            for a in range(n_areas):
                row_of_neuron[area_starts[a]:area_starts[a + 1]] = area_row[a]
        else:
            # more rows than areas: apportion rows to areas, then split each
            # area across its rows by multisection on positions
            counts = apportion_devices(edge_w, n_rows)
            row_of_neuron = np.empty(spec.n_neurons, dtype=np.int64)
            row0 = 0
            for a in range(n_areas):
                ga = np.arange(area_starts[a], area_starts[a + 1])
                pos = spec.areas[a].positions
                if pos is None:
                    pos = rng.uniform(size=(ga.size, 3))
                part = multisection_divide(pos, int(counts[a]), rng=rng)
                row_of_neuron[ga] = row0 + part
                row0 += int(counts[a])

    # level 2: multisection within each row
    owner = np.full(spec.n_neurons, -1, dtype=np.int32)
    parts: list[np.ndarray] = []
    all_pos = np.concatenate([
        (a.positions if a.positions is not None
         else rng.uniform(size=(sizes[i], 3)))
        for i, a in enumerate(spec.areas)], axis=0)
    for r in range(n_rows):
        gids = np.nonzero(row_of_neuron == r)[0].astype(np.int64)
        if gids.size < row_width:
            raise ValueError(f"row {r} has {gids.size} < {row_width} neurons")
        cell = multisection_divide(all_pos[gids], row_width, rng=rng)
        for m in range(row_width):
            d = r * row_width + m
            sel = np.sort(gids[cell == m])
            parts.append(sel)
            owner[sel] = d

    dec = Decomposition(n_neurons=spec.n_neurons, parts=parts, owner=owner,
                        device_area=np.full(n_devices, -1, dtype=np.int32))
    dec.validate()
    return dec


# --------------------------------------------------------------------------
# stacked (device-major) network arrays + exchange metadata
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackedNetwork:
    """All shard graphs stacked on a leading device axis, plus exchange
    metadata. Every array field has shape (S, ...) and is sharded on axis 0."""

    n_shards: int
    row_width: int
    n_local: int
    n_mirror: int
    n_edges: int
    b_pad: int                 # boundary slots per shard
    max_delay: int
    graph: dict[str, Any]      # stacked ShardGraph arrays (incl. mirror_src_*)
    # exchange metadata (stacked, device-major)
    boundary_slots: Any        # (S, B) int32 local idx published per slot
    mirror_is_intra: Any       # (S, n_mirror) bool
    mirror_row_gather: Any     # (S, n_mirror) int32 -> row-gathered flat idx
    mirror_remote_gather: Any  # (S, n_mirror) int32 -> remote-gathered flat idx
    mirror_src_flat: Any       # (S, n_mirror) int32 (global mode)
    # static blocked-layout geometry (nb, eb, pb) when graph carries the
    # stacked ELL arrays blk_* for the pallas backend; None otherwise
    blocked_meta: tuple[int, int, int] | None = None
    # how the baked shapes were chosen (the prepare_stacked block_shapes
    # arg: None = fixed defaults, "auto" = autotuned, or a pinned spec) -
    # lets make_distributed_step warn ONLY when a shape-tuning backend is
    # paired with an untuned net
    block_shapes_spec: Any = None
    # multi-process builds hold only their own shards: every (S, ...) array
    # here then has leading dim ``hi - lo`` and this records the owned
    # ``(lo, hi)`` range of the global shard axis.  None = all shards
    # present (the single-process case).  See multihost.prepare_stacked_local.
    local_slice: tuple[int, int] | None = None

    # per-shard per-step spike traffic (DESIGN.md §2/§10).  The fp32-bitmap
    # figures are kept as the mapping-quality metric (they count exchanged
    # NEURON SLOTS x 4, independent of wire choice); per-wire bytes go
    # through the SpikeWire codec via :func:`wire_bytes_per_step`.
    @property
    def comm_bytes_global(self) -> int:
        return int(wire_bytes_per_step(self, "global", "f32"))

    @property
    def comm_bytes_area(self) -> int:
        return int(wire_bytes_per_step(self, "area", "f32"))


def _alloc_stacked_graph(S: int, e_pad: int, n_local: int, n_mirror: int,
                         blocked_meta) -> dict[str, np.ndarray]:
    """Preallocate the (S, ...) stacked const arrays so shard graphs can be
    filled (and freed) one at a time - the streaming half of the procedural
    build's O(owned rows) peak-RSS contract."""
    graph = dict(
        pre_idx=np.zeros((S, e_pad), np.int32),
        post_idx=np.zeros((S, e_pad), np.int32),
        delay=np.zeros((S, e_pad), np.int32),
        channel=np.zeros((S, e_pad), np.int32),
        plastic=np.zeros((S, e_pad), bool),
        weight_init=np.zeros((S, e_pad), np.float32),
        group_id=np.zeros((S, n_local), np.int32),
        ext_rate=np.zeros((S, n_local), np.float32),
        ext_weight=np.zeros((S, n_local), np.float32),
        global_id=np.full((S, n_local), -1, np.int32),
        mirror_src_idx=np.zeros((S, n_mirror), np.int32),
    )
    if blocked_meta is not None:
        nb, eb, _pb = blocked_meta
        graph.update(
            blk_pre_idx=np.zeros((S, nb, eb), np.int32),
            blk_post_rel=np.zeros((S, nb, eb), np.int32),
            blk_delay=np.zeros((S, nb, eb), np.int32),
            blk_channel=np.zeros((S, nb, eb), np.int32),
            blk_plastic=np.zeros((S, nb, eb), bool),
            blk_edge_perm=np.zeros((S, nb, eb), np.int32),
        )
    return graph


def _fill_stacked_row(graph: dict, i: int, g: ShardGraph,
                      blocked_meta) -> None:
    """Write one ShardGraph into row ``i`` of the stacked const arrays."""
    for field in ("pre_idx", "post_idx", "delay", "channel", "plastic",
                  "weight_init", "group_id", "ext_rate", "ext_weight",
                  "global_id", "mirror_src_idx"):
        graph[field][i] = np.asarray(getattr(g, field))
    if blocked_meta is not None:
        bg = g.blocked
        if (bg.nb, bg.eb, bg.pb) != blocked_meta:
            raise AssertionError(
                f"shard {i} blocked shape {(bg.nb, bg.eb, bg.pb)} != agreed "
                f"{blocked_meta}")
        graph["blk_pre_idx"][i] = np.asarray(bg.pre_idx)
        graph["blk_post_rel"][i] = np.asarray(bg.post_rel)
        graph["blk_delay"][i] = np.asarray(bg.delay)
        graph["blk_channel"][i] = np.asarray(bg.channel)
        graph["blk_plastic"][i] = np.asarray(bg.plastic)
        graph["blk_edge_perm"][i] = np.asarray(bg.edge_perm)


def _boundary_slots_from_lists(boundary: list[np.ndarray], n_local: int,
                               pad_to_multiple: int):
    """Pad per-shard boundary index lists to one (S, b_pad) table.

    Pad slots carry the out-of-range sentinel n_local: the exchange reads
    them with a zero fill, so a pad slot never aliases a real neuron's
    bit (it would inflate the sparse wire's spike count otherwise).
    """
    b_pad = max(max((b.size for b in boundary), default=1), 1)
    b_pad = ((b_pad + pad_to_multiple - 1) // pad_to_multiple) * pad_to_multiple
    slots = np.full((len(boundary), b_pad), n_local, dtype=np.int32)
    for s, b in enumerate(boundary):
        slots[s, :b.size] = b
    return b_pad, slots


def _mirror_meta_row(src: np.ndarray, idx: np.ndarray, s: int,
                     row_of: np.ndarray, boundary: list[np.ndarray],
                     b_pad: int, n_local: int, row_width: int):
    """Exchange gather indices for ONE shard's mirror table.

    Returns ``(intra, row_gather, remote_gather)``:

    - row gather: (model_idx_within_row, local_idx) -> flat;
    - remote gather: (src_flat, slot) -> flat; slot via searchsorted into
      the source's sorted boundary list (only meaningful where ~intra and
      the source actually publishes that neuron).
    """
    intra = row_of[src] == row_of[s]
    row_gather = ((src % row_width) * n_local + idx).astype(np.int32)
    slot = np.zeros(src.size, dtype=np.int64)
    for src_shard in np.unique(src[~intra]):
        m = (~intra) & (src == src_shard)
        b = boundary[int(src_shard)]
        pos = np.searchsorted(b, idx[m])
        pos = np.clip(pos, 0, max(b.size - 1, 0))
        slot[m] = pos
    remote_gather = (src * b_pad + slot).astype(np.int32)
    return intra, row_gather, remote_gather


def _stack_and_index(spec: NetworkSpec, shard_iter, *, S: int,
                     row_width: int, e_pad: int, n_local: int,
                     n_mirror: int, blocked_meta,
                     pad_to_multiple: int,
                     block_shapes_spec) -> StackedNetwork:
    """Consume shard graphs one at a time into the stacked const arrays and
    derive the exchange metadata.  Peak host memory = the stacked arrays
    plus ONE shard graph (the materialized path holds all shards anyway;
    the procedural path streams them)."""
    row_of = np.arange(S) // row_width
    graph = _alloc_stacked_graph(S, e_pad, n_local, n_mirror, blocked_meta)
    src_all = np.zeros((S, n_mirror), np.int32)
    idx_all = np.zeros((S, n_mirror), np.int32)

    # boundary sets: local indices consumed by shards in OTHER rows
    consumers: list[list[np.ndarray]] = [[] for _ in range(S)]
    n_seen = 0
    for s, g in enumerate(shard_iter):
        _fill_stacked_row(graph, s, g, blocked_meta)
        src = np.asarray(g.mirror_src_shard)
        idx = np.asarray(g.mirror_src_idx)
        src_all[s] = src
        idx_all[s] = idx
        used = np.zeros(n_mirror, dtype=bool)
        used[np.asarray(g.pre_idx)[np.asarray(g.delay) > 0]] = True
        for src_shard in np.unique(src[used]):
            if row_of[src_shard] != row_of[s]:
                sel = used & (src == src_shard)
                consumers[int(src_shard)].append(np.unique(idx[sel]))
        n_seen += 1
    assert n_seen == S

    boundary = [np.unique(np.concatenate(c)) if c else np.zeros(0, np.int64)
                for c in consumers]
    b_pad, boundary_slots = _boundary_slots_from_lists(
        boundary, n_local, pad_to_multiple)

    mirror_is_intra = np.zeros((S, n_mirror), dtype=bool)
    mirror_row_gather = np.zeros((S, n_mirror), dtype=np.int32)
    mirror_remote_gather = np.zeros((S, n_mirror), dtype=np.int32)
    for s in range(S):
        (mirror_is_intra[s], mirror_row_gather[s],
         mirror_remote_gather[s]) = _mirror_meta_row(
            src_all[s], idx_all[s], s, row_of, boundary, b_pad,
            n_local, row_width)

    return StackedNetwork(
        n_shards=S, row_width=row_width, n_local=n_local, n_mirror=n_mirror,
        n_edges=e_pad, b_pad=b_pad, max_delay=spec.max_delay, graph=graph,
        blocked_meta=blocked_meta, block_shapes_spec=block_shapes_spec,
        boundary_slots=boundary_slots, mirror_is_intra=mirror_is_intra,
        mirror_row_gather=mirror_row_gather,
        mirror_remote_gather=mirror_remote_gather,
        mirror_src_flat=src_all)


def procedural_stack_plan(spec: NetworkSpec, dec: Decomposition, *,
                          devices=None, pad_to_multiple: int = 8,
                          with_blocked: bool = True,
                          block_shapes=None,
                          row_chunk: int | None = None) -> dict:
    """Dims pre-pass of the procedural stacked build (pass A only, per
    shard): everything every process must AGREE on before filling arrays -
    the uniform pads and the shared blocked shape - derived without ever
    holding more than one shard's counts.

    ``devices`` restricts the pass to a subset of shards (the multihost
    build runs it per process and allgathers the per-shard dims instead).
    Returns ``dict(e, n_local, n_mirror, row_degree)`` lists per shard plus
    the resolved pads under key ``"pads"`` when all shards were scanned.
    """
    from repro.core import builder as builder_mod
    devs = range(dec.n_devices) if devices is None else devices
    kw = {} if row_chunk is None else dict(row_chunk=row_chunk)
    dims = [builder_mod.procedural_shard_raw(spec, dec, int(s),
                                             dims_only=True, **kw)
            for s in devs]
    plan = dict(
        e=[d["e"] for d in dims],
        n_local=[int(d["owned"].size) for d in dims],
        n_mirror=[int(d["mirror_gids"].size) for d in dims],
        row_degree=[d["row_degree"] for d in dims],
    )
    if devices is None:
        plan["pads"] = resolve_stack_pads(
            plan, spec, pad_to_multiple=pad_to_multiple,
            with_blocked=with_blocked, block_shapes=block_shapes)
    return plan


def resolve_stack_pads(plan: dict, spec: NetworkSpec, *,
                       pad_to_multiple: int = 8,
                       with_blocked: bool = True,
                       block_shapes=None) -> dict:
    """Turn (possibly allgathered) per-shard dims into the agreed uniform
    pads and blocked meta - pure arithmetic, no RNG, so every process that
    holds the same dims derives the same answer."""
    from repro.core import autotune as autotune_mod
    _pad = lambda n: max(((int(n) + pad_to_multiple - 1) // pad_to_multiple)
                         * pad_to_multiple, pad_to_multiple)
    e_pad = _pad(max(plan["e"]))
    n_local_pad = _pad(max(plan["n_local"]))
    n_mirror_pad = _pad(max(plan["n_mirror"]))
    blocked_meta = shapes = None
    if with_blocked:
        shapes = autotune_mod.resolve_block_shapes_from_degrees(
            plan["row_degree"], block_shapes, n_local=n_local_pad,
            n_mirror=n_mirror_pad, max_delay=spec.max_delay)
        pb = DEFAULT_PB if shapes is None else shapes.pb
        need = max(autotune_mod.eb_from_degrees(rd, n_local_pad, pb=pb)
                   for rd in plan["row_degree"])
        if shapes is None:
            eb = need
        else:
            eb = shapes.eb
            if eb < need:
                raise ValueError(
                    f"block_shapes eb={eb} is below the widest shard's "
                    f"per-block edge count {need} at pb={pb} - raise eb "
                    "(or use 'auto')")
        blocked_meta = (max(-(-n_local_pad // pb), 1), eb, pb)
    return dict(e_pad=e_pad, n_local_pad=n_local_pad,
                n_mirror_pad=n_mirror_pad, blocked_meta=blocked_meta,
                shapes=shapes)


def procedural_shard_graphs(spec: NetworkSpec, dec: Decomposition,
                            devices, pads: dict, *,
                            pad_to_multiple: int = 8,
                            with_blocked: bool = True,
                            row_chunk: int | None = None):
    """Yield finalized ShardGraphs for ``devices`` one at a time, each built
    O(owned rows) and padded to the agreed ``pads`` - the generator both
    prepare_stacked (all shards) and the multihost per-process build (its
    own shards) drain."""
    from repro.core import builder as builder_mod
    kw = {} if row_chunk is None else dict(row_chunk=row_chunk)
    bm = pads["blocked_meta"]
    pad_dims = (pads["e_pad"], pads["n_local_pad"], pads["n_mirror_pad"])
    for s in devices:
        raw = builder_mod.procedural_shard_raw(spec, dec, int(s), **kw)
        [g] = builder_mod.finalize_shards(
            spec, dec, [raw], pad_to_multiple=pad_to_multiple,
            with_blocked=with_blocked, block_shapes=pads["shapes"],
            streamed=True, pad_dims=pad_dims,
            blocked_eb_min=None if bm is None else bm[1])
        yield g


def prepare_stacked(spec: NetworkSpec, dec: Decomposition,
                    n_rows: int, row_width: int, *,
                    pad_to_multiple: int = 8,
                    with_blocked: bool = True,
                    block_shapes=None) -> StackedNetwork:
    """Build uniform shards and the area/remote exchange index tables.

    ``with_blocked=False`` skips building/stacking the post-block ELL
    arrays (saves build time + host memory) for runs that will never select
    the pallas backend.  ``block_shapes`` (None | "auto" | BlockShapes)
    picks the shared (PB, EB) pair - see ``builder.build_shards``.

    For ``spec.connectivity == "procedural"`` the shards are built AND
    stacked one at a time (DESIGN.md §14): a dims pre-pass agrees on the
    uniform pads and blocked shape, then each shard is generated, written
    into the preallocated stacked arrays, and freed - peak host memory is
    the stacked consts plus one shard, never the global edge list.
    """
    S = n_rows * row_width
    assert S == dec.n_devices
    if spec.connectivity == "procedural":
        plan = procedural_stack_plan(spec, dec,
                                     pad_to_multiple=pad_to_multiple,
                                     with_blocked=with_blocked,
                                     block_shapes=block_shapes)
        pads = plan["pads"]
        shard_iter = procedural_shard_graphs(
            spec, dec, range(S), pads, pad_to_multiple=pad_to_multiple,
            with_blocked=with_blocked)
        e_pad, n_local, n_mirror = (pads["e_pad"], pads["n_local_pad"],
                                    pads["n_mirror_pad"])
        blocked_meta = pads["blocked_meta"]
    else:
        shards = build_shards(spec, dec, pad_to_multiple=pad_to_multiple,
                              uniform_pad=True, with_blocked=with_blocked,
                              block_shapes=block_shapes)
        assert len(shards) == S
        e_pad = shards[0].n_edges
        n_local = shards[0].n_local
        n_mirror = shards[0].n_mirror
        blocked_meta = None
        if all(g.blocked is not None for g in shards):
            bgs = [g.blocked for g in shards]
            blocked_meta = (bgs[0].nb, bgs[0].eb, bgs[0].pb)
            assert all((bg.nb, bg.eb, bg.pb) == blocked_meta for bg in bgs)
        shard_iter = iter(shards)
    return _stack_and_index(
        spec, shard_iter, S=S, row_width=row_width, e_pad=e_pad,
        n_local=n_local, n_mirror=n_mirror, blocked_meta=blocked_meta,
        pad_to_multiple=pad_to_multiple, block_shapes_spec=block_shapes)


# --------------------------------------------------------------------------
# the distributed step
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    engine: EngineConfig
    comm_mode: str = "area"       # "area" | "global"
    overlap: bool = True          # paper §III.C schedule
    axis_names: tuple[str, ...] = ("data", "model")  # (outer..., inner)
    # spike-exchange wire codec, resolved through the SpikeWire registry
    # (repro.core.wire, DESIGN.md §10): "f32" / "u8" / "packed" dense
    # bitmaps, "sparse" fixed-capacity (count, ids) payloads - CORTEX's
    # ID-based Spikes Broadcast; "sparse:<rate>" provisions capacity for
    # that per-step firing fraction.  A SpikeWire instance also works.
    spike_wire: str = "packed"
    # wire for the REMOTE tier: the cross-row boundary payload in "area"
    # mode (inter-host traffic under the host-aligned mesh of
    # repro.core.multihost) and the whole gather in "global" mode (every
    # payload crosses rows there).  None = same as ``spike_wire``.  The
    # split matters because the tiers see different regimes: intra-row
    # bitmaps are wide and dense-ish, boundary payloads are narrow and
    # fire hot - e.g. "packed" intra-host with "sparse" inter-host, where
    # the ID wire's small messages matter most (DESIGN.md §11).
    spike_wire_remote: Any = None

    @property
    def inner_axis(self) -> str:
        return self.axis_names[-1]

    @property
    def neuron_model(self) -> str:
        """The NeuronModel name this step runs (DESIGN.md §12); set it on
        the nested EngineConfig - the distributed step adds nothing
        model-specific, exactly like the backend choice."""
        return self.engine.neuron_model

    @property
    def wire(self) -> wire_mod.SpikeWire:
        return wire_mod.get_wire(self.spike_wire)

    @property
    def remote_wire(self) -> wire_mod.SpikeWire:
        spec = (self.spike_wire if self.spike_wire_remote is None
                else self.spike_wire_remote)
        return wire_mod.get_wire(spec)


@dataclasses.dataclass
class DistState:
    """Scan-carried state; every leaf is (S, ...) sharded on axis 0."""
    v_m: jax.Array
    syn_ex: jax.Array
    syn_in: jax.Array
    ref_count: jax.Array
    ring: jax.Array          # (S, D, n_mirror)
    weights: jax.Array       # (S, E) flat or (S, NB*EB) blocked - see marker
    k_pre: jax.Array
    k_post: jax.Array
    prev_bits: jax.Array     # (S, n_local) spikes fired last step (raw)
    t: jax.Array             # (S,) step counter (identical values)
    key: jax.Array           # (S, 2) PRNG key data, equal on every shard
    wire_overflow: jax.Array  # (S,) cumulative saturated lossy-wire payloads
    #: (S,) cumulative steps whose activity gate saturated its worklist and
    #: fell back to the dense sweep (DESIGN.md §13) - the compute twin of
    #: ``wire_overflow``; always 0 on ungated backends
    gate_overflow: jax.Array = None
    #: (S, 2) key data of the DECOMPOSITION-INVARIANT stochastic-drive
    #: stream: the same ``fold_in(key(seed), DRIVE_SALT)`` on every shard,
    #: differentiated per neuron by folding the GLOBAL id inside the model
    #: (engine.DRIVE_SALT) - so 1-shard and N-shard poisson trajectories
    #: match bit-for-bit.  None on deterministic models (legacy treedef).
    drive_key: jax.Array | None = None
    #: model-specific per-neuron state (S, n_local) arrays beyond the
    #: common four - Izhikevich's {"u"}, AdEx's {"w_ad"}; {} for lif and
    #: poisson.  The key set is fixed per NeuronModel (DESIGN.md §12), so
    #: the carry treedef varies by MODEL, never by step.
    aux: dict = dataclasses.field(default_factory=dict)
    #: static marker: layout of ``weights`` - "flat" or a shape-qualified
    #: blocked tag "blocked:{pb}x{eb}" (backends.layout_tag); pytree
    #: metadata so blocked-resident state is never misread as flat nor
    #: stepped under different (PB, EB) block shapes
    weights_layout: str = "flat"
    #: static marker: which NeuronModel this state was built for -
    #: struct-checked against cfg.engine.neuron_model at trace time
    neuron_model: str = "lif"


jax.tree_util.register_dataclass(
    DistState,
    data_fields=["v_m", "syn_ex", "syn_in", "ref_count", "ring", "weights",
                 "k_pre", "k_post", "prev_bits", "t", "key",
                 "wire_overflow", "gate_overflow", "drive_key", "aux"],
    meta_fields=["weights_layout", "neuron_model"])


def init_stacked_state(net: StackedNetwork, groups, seed: int = 0,
                       dtype=jnp.float32, weight_dtype=None,
                       sweep: str | None = None,
                       neuron_model: str = "lif",
                       mesh: Mesh | None = None,
                       axis_names: tuple[str, ...] = ("data", "model")
                       ) -> DistState:
    """``weight_dtype`` may be narrower than the neuron dtype (bf16) for
    non-plastic evaluation runs - weights are the largest per-edge stream
    (§Perf C4).  ``sweep`` (a backend name) stores the weights in that
    backend's native layout up front (blocked ELL slot order for pallas) so
    the distributed step never pays a per-step ``edge_perm`` conversion;
    without it the state is flat and the step converts at trace time.
    ``neuron_model`` picks the dynamics (DESIGN.md §12): ``groups`` must
    be that model's parameter class; model-specific state lands in
    ``DistState.aux``.

    ``mesh`` places every leaf straight onto its devices, shard ``i`` on
    the ``i``-th device of ``axis_names`` (the step's ``P(axis_names)``
    layout); without it the leaves are plain single-device arrays.

    Every shard carries the SAME PRNG key: the external drive draws one
    stream per global neuron id from it (``engine.poisson_events``), so the
    trajectory depends neither on how many shards nor on how many
    processes run the network.  Multi-process nets (``net.local_slice``)
    hold only their own shards: every state leaf then has that local
    leading dim."""
    S = net.n_shards
    lo, hi = (0, S) if net.local_slice is None else net.local_slice
    model = neuron_models_mod.get_model(neuron_model)
    gid = np.asarray(net.graph["group_id"])
    Sl = gid.shape[0]
    assert Sl == hi - lo, (Sl, net.local_slice)
    nvars = model.init_vars(gid, list(groups))
    key = np.asarray(jax.random.key_data(jax.random.key(seed)))
    drive_key = None
    if model.stochastic:
        # shard-independent drive stream (per-neuron via GLOBAL-id fold_in
        # inside the model) - the decomposition-invariance contract
        dk = jax.random.key_data(
            jax.random.fold_in(jax.random.key(seed), DRIVE_SALT))
        drive_key = np.broadcast_to(np.asarray(dk), (Sl,) + dk.shape)
    weights = np.asarray(net.graph["weight_init"])
    weights_layout = "flat"
    if sweep is not None and backends_mod.get_backend(
            sweep).weights_layout == "blocked":
        if net.blocked_meta is None:
            raise ValueError(
                f"sweep={sweep!r} stores blocked-resident weights; build "
                "the StackedNetwork with prepare_stacked(with_blocked=True)")
        perm = np.asarray(net.graph["blk_edge_perm"]).reshape(Sl, -1)
        weights = np.take_along_axis(weights, perm, axis=1)
        nb, eb, pb = net.blocked_meta
        weights_layout = f"blocked:{pb}x{eb}"
    if mesh is None:
        put = jnp.asarray
    else:
        sharding = NamedSharding(mesh, P(axis_names))
        put = lambda a: jax.device_put(a, sharding)
    host = lambda a, dt: put(np.asarray(a, dtype=dt))
    zeros = lambda shape, dt: put(np.zeros(shape, dt))
    return DistState(
        v_m=host(nvars["v_m"], dtype),
        syn_ex=host(nvars["syn_ex"], dtype),
        syn_in=host(nvars["syn_in"], dtype),
        ref_count=host(nvars["ref_count"], jnp.int32),
        ring=zeros((Sl, net.max_delay, net.n_mirror), dtype),
        weights=host(weights, weight_dtype or dtype),
        k_pre=zeros((Sl, net.n_mirror), dtype),
        k_post=zeros((Sl, net.n_local), dtype),
        prev_bits=zeros((Sl, net.n_local), dtype),
        t=zeros((Sl,), jnp.int32),
        key=put(np.broadcast_to(key, (Sl,) + key.shape)),
        wire_overflow=zeros((Sl,), jnp.int32),
        gate_overflow=zeros((Sl,), jnp.int32),
        drive_key=None if drive_key is None else put(drive_key),
        aux={k: host(nvars[k], dtype) for k in model.extra_fields},
        weights_layout=weights_layout,
        neuron_model=model.name,
    )


def advance_key_data(key_data, n_steps: int):
    """Advance (S, 2) raw per-shard key data by ``n_steps`` step-loop
    splits.

    The distributed step evolves each shard's stream as ``key, sub =
    split(key)`` once per step, so the stream after ``n_steps`` is
    ``split(key)[0]`` applied ``n_steps`` times.  Restart tooling that
    re-derives keys for a NEW shard count (elastic shrink) uses this to
    land on exactly the stream an uninterrupted run would hold.
    """
    keys = jax.random.wrap_key_data(jnp.asarray(key_data))

    def body(_, ks):
        return jax.vmap(lambda k: jax.random.split(k)[0])(ks)

    keys = jax.lax.fori_loop(0, int(n_steps), body, keys)
    return jax.random.key_data(keys)


def _exchange_issue(bits, g, cfg: DistributedConfig,
                    wire: wire_mod.SpikeWire,
                    remote_wire: wire_mod.SpikeWire):
    """Encode this shard's freshly fired local bits and ISSUE the exchange
    collectives (nothing is decoded yet).

    Two tiers in "area" mode: the cross-row boundary payload (inter-host
    under the host-aligned mesh - the slow hop, so its collective is
    issued FIRST) on ``remote_wire``, then the intra-row local payload on
    ``wire``.  "global" mode is a single all-rows gather - every payload
    crosses rows, so it rides ``remote_wire``.

    Returns ``(payloads, overflow)``: an opaque tuple for
    :func:`_exchange_finish`, and this step's saturated-payload count
    (each tier counted exactly once; 0 on dense wires).  Keeping issue
    separate from finish puts the collectives ahead of the delay>=2 sweep
    in the dataflow, so only the delay-1 path (which consumes the decoded
    result) waits on the wire - the §III.C / Du et al. 2022 overlap.
    """
    if cfg.comm_mode == "global":
        payload = remote_wire.encode(bits)
        overflow = remote_wire.overflow_count(payload)
        all_p = jax.lax.all_gather(payload, axis_name=cfg.axis_names,
                                   tiled=False)              # (S, W)
        return (all_p,), overflow
    if cfg.comm_mode == "area":
        # remote tier first: boundary neurons only (n(boundary) << n_local)
        bbits = jnp.take(bits, g["boundary_slots"],          # (B,)
                         mode="fill", fill_value=0)          # pads -> 0
        b_payload = remote_wire.encode(bbits)
        remote_p = jax.lax.all_gather(b_payload, axis_name=cfg.axis_names,
                                      tiled=False)           # (S, Wb)
        # intra tier: dense-ish local bitmap along the model axis only
        payload = wire.encode(bits)
        row_p = jax.lax.all_gather(payload, axis_name=cfg.inner_axis,
                                   tiled=False)              # (M, W)
        overflow = (wire.overflow_count(payload)
                    + remote_wire.overflow_count(b_payload))
        return (row_p, remote_p), overflow
    raise ValueError(f"unknown comm mode {cfg.comm_mode!r}")


def _exchange_finish(payloads, g, cfg: DistributedConfig,
                     wire: wire_mod.SpikeWire,
                     remote_wire: wire_mod.SpikeWire, n_local: int, dtype):
    """Decode the gathered payloads and map them onto this shard's mirror
    rows - the only consumer of the collectives' results."""
    if cfg.comm_mode == "global":
        (all_p,) = payloads
        all_bits = remote_wire.decode(all_p, n_local, dtype)
        flat = all_bits.reshape(-1)
        return jnp.take(flat, g["mirror_src_flat"] * n_local
                        + g["mirror_src_idx"])
    row_p, remote_p = payloads
    row_bits = wire.decode(row_p, n_local, dtype)
    b_pad = g["boundary_slots"].shape[0]
    remote = remote_wire.decode(remote_p, b_pad, dtype)
    intra_val = jnp.take(row_bits.reshape(-1), g["mirror_row_gather"])
    remote_val = jnp.take(remote.reshape(-1), g["mirror_remote_gather"])
    return jnp.where(g["mirror_is_intra"], intra_val, remote_val)


def _exchange(bits, g, cfg: DistributedConfig, wire: wire_mod.SpikeWire,
              remote_wire: wire_mod.SpikeWire | None = None):
    """Map this shard's freshly fired local bits to its mirror rows.

    The wire codec is config-selectable per tier (repro.core.wire): spikes
    are 1-bit events, so the payload can be packed 32x below the naive f32
    bitmap or shipped as (count, ids) - CORTEX's Spikes Broadcast of IDs.
    Returns ``(mirror_bits, overflow)`` where ``overflow`` counts this
    step's saturated payloads on a lossy wire (0 on dense wires)."""
    remote_wire = wire if remote_wire is None else remote_wire
    payloads, overflow = _exchange_issue(bits, g, cfg, wire, remote_wire)
    mirror = _exchange_finish(payloads, g, cfg, wire, remote_wire,
                              bits.shape[0], bits.dtype)
    return mirror, overflow


def _layout_from_consts(g: dict, n_local: int, n_mirror: int, max_delay: int,
                        blocked_meta) -> backends_mod.EdgeLayout:
    """Per-shard EdgeLayout around shard_map-traced const arrays.

    Static geometry comes from the closure; ``bucket_ptr`` stays None (per
    shard it would be a different static, which a single shard-uniform
    program cannot carry - the bucketed backend falls back to delay masks).
    """
    blk = None
    if blocked_meta is not None and "blk_pre_idx" in g:
        nb, eb, pb = blocked_meta
        blk = BlockedGraph(nb=nb, eb=eb, pb=pb, n_local=nb * pb,
                           pre_idx=g["blk_pre_idx"],
                           post_rel=g["blk_post_rel"],
                           delay=g["blk_delay"], channel=g["blk_channel"],
                           plastic=g.get("blk_plastic"),
                           edge_perm=g["blk_edge_perm"])
    return backends_mod.EdgeLayout(
        n_local=n_local, n_mirror=n_mirror, max_delay=max_delay,
        pre_idx=g["pre_idx"], post_idx=g["post_idx"], delay=g["delay"],
        channel=g["channel"], plastic=g["plastic"],
        bucket_ptr=None, blocked=blk)


def wire_bytes_split(mode: str, wire, remote_wire=None, *, n_shards: int,
                     row_width: int, n_local: int, b_pad: int
                     ) -> dict[str, int]:
    """Per-shard spike-exchange bytes per step, split by tier, from
    decomposition dims alone (no StackedNetwork) - the dry-run traffic
    model with per-tier wires.

    ``intra``: bytes that stay within a mesh row (intra-host under the
    host-aligned mesh) - the M intra-row local payloads of "area" mode;
    ``inter``: bytes that cross rows (inter-host) - the S boundary
    payloads of "area" mode, or everything in "global" mode
    (the M*n_local + S*B split of DESIGN.md §7, in wire-payload bytes).
    """
    lw = wire_mod.get_wire(wire)
    rw = lw if remote_wire is None else wire_mod.get_wire(remote_wire)
    if mode == "global":
        return dict(intra=0, inter=n_shards * rw.bytes_per_step(n_local))
    if mode == "area":
        return dict(intra=row_width * lw.bytes_per_step(n_local),
                    inter=n_shards * rw.bytes_per_step(b_pad))
    raise ValueError(f"unknown comm mode {mode!r}")


def wire_bytes_for_dims(mode: str, wire, remote_wire=None, *,
                        n_shards: int, row_width: int,
                        n_local: int, b_pad: int) -> int:
    """Total per-shard spike-exchange bytes per step (both tiers)."""
    split = wire_bytes_split(mode, wire, remote_wire, n_shards=n_shards,
                             row_width=row_width, n_local=n_local,
                             b_pad=b_pad)
    return split["intra"] + split["inter"]


def wire_bytes_per_step(net: StackedNetwork, mode: str = "area",
                        wire="packed", remote_wire=None) -> int:
    """Per-shard spike-exchange bytes per step for a wire codec pair."""
    return wire_bytes_for_dims(mode, wire, remote_wire,
                               n_shards=net.n_shards,
                               row_width=net.row_width,
                               n_local=net.n_local, b_pad=net.b_pad)


def make_raw_distributed_step(mesh: Mesh, groups: Sequence[snn.LIFParams],
                              cfg: DistributedConfig, *, max_delay: int,
                              n_local: int, n_mirror: int,
                              blocked_meta=None):
    """The shard_map'ed step as fn(state, consts) with consts as traced
    operands - usable with ShapeDtypeStructs for production-scale dry-runs
    (no graph materialization)."""
    if (backends_mod.get_backend(cfg.engine.sweep).needs_blocked
            and blocked_meta is None):
        raise ValueError(
            f"sweep={cfg.engine.sweep!r} on the raw step needs "
            "blocked_meta=(nb, eb, pb) plus blk_* entries in the consts "
            "(incl. blk_plastic) and blocked-resident state weights")
    return _build_step(mesh, groups, cfg, max_delay, n_local, n_mirror,
                       blocked_meta)


def check_net_backend(net: StackedNetwork,
                      cfg: DistributedConfig) -> backends_mod.SweepBackend:
    """Resolve ``cfg``'s backend and validate the net supports it (blocked
    consts present for blocked-resident backends; baked-shapes warning for
    shape-tuning backends on untuned nets)."""
    backend = backends_mod.get_backend(cfg.engine.sweep)
    if backend.needs_blocked and net.blocked_meta is None:
        raise ValueError(
            f"sweep={cfg.engine.sweep!r} needs a StackedNetwork built with "
            "blocked layouts (prepare_stacked with_blocked=True)")
    if (getattr(backend, "block_shapes", None) is not None
            and net.block_shapes_spec is None):
        # stacked blk_* consts are baked at build time; a backend-side
        # block_shapes spec (e.g. "pallas:auto") cannot retune them here -
        # the distributed path tunes through prepare_stacked(block_shapes=).
        # A net that WAS built with a block_shapes spec stays silent.
        import warnings
        warnings.warn(
            f"sweep={cfg.engine.sweep!r}: the distributed step uses the "
            f"StackedNetwork's baked block shapes {net.blocked_meta}; pass "
            "block_shapes to prepare_stacked/build_shards to autotune "
            "them", stacklevel=3)
    return backend


def stacked_consts(net: StackedNetwork, *, needs_blocked: bool,
                   dt: float) -> dict:
    """The (S, ...) host-side const arrays the sharded step consumes -
    graph edge arrays, the external drive's per-neuron thresholds for
    step ``dt`` (:func:`repro.core.engine.drive_threshold`) and the
    exchange metadata.  Device placement is the caller's job (sharded
    ``device_put`` single-process; global sharded arrays in
    :mod:`repro.core.multihost`)."""
    consts = {k: v for k, v in net.graph.items()
              if needs_blocked or not k.startswith("blk_")}
    consts.update(
        ext_thresh=drive_threshold(net.graph["ext_rate"], dt),
        boundary_slots=net.boundary_slots,
        mirror_is_intra=net.mirror_is_intra,
        mirror_row_gather=net.mirror_row_gather,
        mirror_remote_gather=net.mirror_remote_gather,
        mirror_src_flat=net.mirror_src_flat,
    )
    return consts


def make_distributed_step(net: StackedNetwork, mesh: Mesh,
                          groups: Sequence[snn.LIFParams],
                          cfg: DistributedConfig):
    """Build the jit-able sharded step: DistState -> (DistState, spike bits).

    All graph/metadata arrays are closed over as constants sharded over
    the mesh like the state (``P(cfg.axis_names)``).  The returned
    function is shard_map'ed over the mesh and can be scanned or called
    per-step.  (Single-process entry point; the
    multi-process twin is :func:`repro.core.multihost.make_multihost_step`,
    which shards the same consts across hosts.)
    """
    backend = check_net_backend(net, cfg)
    needs_blocked = backend.needs_blocked
    smapped = _build_step(mesh, groups, cfg, net.max_delay, net.n_local,
                          net.n_mirror,
                          net.blocked_meta if needs_blocked else None)
    consts = stacked_consts(net, needs_blocked=needs_blocked,
                            dt=cfg.engine.dt)
    # shard i's consts live on shard i's device only (never all of them on
    # device 0 first)
    sharding = NamedSharding(mesh, P(cfg.axis_names))
    consts_j = {k: jax.device_put(np.asarray(v), sharding)
                for k, v in consts.items()}

    def step(state: DistState):
        return smapped(state, consts_j)

    return step, consts_j


def _build_step(mesh: Mesh, groups, cfg: DistributedConfig, max_delay: int,
                n_local: int, n_mirror: int, blocked_meta=None):
    model = neuron_models_mod.get_model(cfg.engine.neuron_model)
    table_np = np.asarray(model.make_param_table(list(groups),
                                                 cfg.engine.dt))
    D = max_delay
    backend = backends_mod.get_backend(cfg.engine.sweep)
    wire = cfg.wire
    remote_wire = cfg.remote_wire

    def step_local(g, state: DistState):
        """Body on ONE shard: every array already squeezed to per-shard.

        The hot path (sweep, neuron update, STDP) is the SAME backend code
        the single-shard engine dispatches to; only the spike exchange and
        the overlap schedule around it are distributed-specific.

        The work runs in the ``engine.PHASES`` scopes, the names the chip
        benchmark reads: ``exchange`` (issue and finish of the spike
        exchange), ``sweep`` (the synaptic sweep with its ring write),
        ``neuron`` (the external drive and the neuron update) and ``stdp``
        (as in ``engine.engine_step``).
        """
        # edge/index arrays may arrive in compact dtypes (u16 indices, i8
        # delays - §Perf: the static edge arrays dominate sweep traffic);
        # compute in i32 regardless.
        g = dict(g)
        for k in ("pre_idx", "post_idx", "delay", "channel",
                  "mirror_src_idx", "boundary_slots", "mirror_row_gather",
                  "mirror_remote_gather", "mirror_src_flat", "global_id",
                  "blk_pre_idx", "blk_post_rel", "blk_delay",
                  "blk_channel", "blk_edge_perm"):
            if k in g and g[k].dtype != jnp.int32:
                g[k] = g[k].astype(jnp.int32)
        # neuron-state dtype drives the math; WEIGHTS may be stored
        # narrower (bf16 for non-plastic evaluation runs - §Perf C4) and
        # promote at the multiply.
        dtype = state.v_m.dtype
        t = state.t
        layout = _layout_from_consts(g, n_local, n_mirror, D, blocked_meta)

        # weights in the backend's native layout; converting here is the
        # compatibility path (state built without ``sweep=``) and costs one
        # edge gather per direction per step - init_stacked_state(sweep=...)
        # carries native state and skips both.  The shared resolver also
        # rejects a state minted under different (PB, EB) block shapes.
        w_native, native_tag, convert = backends_mod.resolve_runtime_weights(
            backend, layout, state.weights, state.weights_layout)

        # ---- (1) two-tier exchange of last step's spikes ------------------
        # collectives are ISSUED here - the cross-row/-host boundary tier
        # first - and their results consumed only below, so under
        # cfg.overlap the delay>=2 sweep (old ring slots only) never waits
        # on the wire (tests/test_multihost.py pins the independence)
        with phase("exchange"):
            payloads, overflow = _exchange_issue(state.prev_bits, g, cfg,
                                                 wire, remote_wire)
            mirror_prev = _exchange_finish(payloads, g, cfg, wire,
                                           remote_wire, n_local, dtype)

        # ---- (2) synaptic sweep ------------------------------------------
        with phase("sweep"):
            if cfg.overlap:
                # backend splits delays >= 2 (old ring, independent of the
                # collective) from delay == 1 (the fresh exchange) when it
                # can; otherwise it degrades to write-then-sweep
                (input_ex, input_in, arrived, ring,
                 gate_ovf) = backend.sweep_overlap_with_stats(
                    layout, w_native, state.ring, t, mirror_prev)
            else:
                # naive schedule: write first, then one full sweep (the
                # sweep then depends on the collective - no overlap
                # possible)
                ring = jax.lax.dynamic_update_index_in_dim(
                    state.ring, mirror_prev, jnp.mod(t - 1, D), axis=0)
                input_ex, input_in, arrived, gate_ovf = (
                    backend.sweep_with_stats(layout, w_native, ring, t))

        # ---- (3) external drive + neuron dynamics ------------------------
        with phase("neuron"):
            key = jax.random.wrap_key_data(state.key)
            key, sub = jax.random.split(key)
            mkey = None
            if model.stochastic:
                # split ONLY for stochastic models (poisson emitters) -
                # deterministic dynamics keep the pre-registry key stream
                sub, mkey = jax.random.split(sub)
                if state.drive_key is not None:
                    # decomposition-invariant drive: the shared stream
                    # keyed per neuron by GLOBAL id inside the model, not
                    # the per-shard split
                    mkey = jax.random.wrap_key_data(state.drive_key)
            if cfg.engine.external_drive:
                input_ex = input_ex + (
                    g["ext_weight"]
                    * poisson_events(sub, g["ext_thresh"], g["global_id"])
                ).astype(dtype)
            neurons = snn.NeuronState(
                v_m=state.v_m, syn_ex=state.syn_ex, syn_in=state.syn_in,
                ref_count=state.ref_count,
                spike=jnp.zeros((n_local,), jnp.bool_),
                group_id=g["group_id"], extra=dict(state.aux))
            if state.neuron_model != model.name:
                raise ValueError(
                    f"DistState was initialized for neuron_model="
                    f"{state.neuron_model!r} but cfg selects "
                    f"{model.name!r}; re-init with "
                    "init_stacked_state(neuron_model=...)")
            model.check_state(neurons)
            table = jnp.asarray(table_np, dtype)
            neurons = backend.neuron_update(
                layout, neurons, table, input_ex, input_in,
                synapse_model=cfg.engine.synapse_model,
                model=model, key=mkey, t=t, gid=g.get("global_id"),
                surrogate=cfg.engine.surrogate)
        bits = neurons.spike

        # ---- (4) plasticity ----------------------------------------------
        if cfg.engine.stdp is not None:
            with phase("stdp"):
                traces = stdp_mod.TraceState(k_pre=state.k_pre,
                                             k_post=state.k_post)
                weights = backend.stdp_update(layout, w_native, arrived,
                                              bits, traces, cfg.engine.stdp)
                pre_arr = jax.ops.segment_max(
                    arrived, backend.edge_pre_index(layout),
                    num_segments=n_mirror)
                traces = stdp_mod.update_traces(traces, cfg.engine.stdp,
                                                cfg.engine.dt, pre_arr, bits)
                k_pre, k_post = traces.k_pre, traces.k_post
                if convert:  # scan carry keeps the state's own layout
                    weights = backends_mod.convert_weights(
                        layout, weights, native_tag, state.weights_layout)
        else:
            # weights unchanged: carry the state's own vector (a round-trip
            # would cost two edge passes and zero flat padding slots)
            weights, k_pre, k_post = (state.weights, state.k_pre,
                                      state.k_post)

        new_state = DistState(
            v_m=neurons.v_m, syn_ex=neurons.syn_ex, syn_in=neurons.syn_in,
            ref_count=neurons.ref_count, ring=ring, weights=weights,
            k_pre=k_pre, k_post=k_post,
            prev_bits=bits.astype(dtype), t=t + 1,
            key=jax.random.key_data(key),
            wire_overflow=state.wire_overflow + overflow,
            gate_overflow=(gate_ovf if state.gate_overflow is None
                           else state.gate_overflow + gate_ovf),
            drive_key=state.drive_key,
            aux=neurons.extra,
            weights_layout=state.weights_layout,
            neuron_model=state.neuron_model)
        return new_state, bits

    # ---- shard_map wrapper ----------------------------------------------
    squeeze = lambda tree: jax.tree.map(lambda a: a[0], tree)
    expand = lambda tree: jax.tree.map(lambda a: a[None], tree)

    def sharded_step(state: DistState, consts_in):
        g = squeeze(consts_in)
        s = squeeze(state)
        new_s, bits = step_local(g, s)
        return expand(new_s), bits[None]

    state_specs = P(cfg.axis_names)
    return jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(state_specs, state_specs),
        out_specs=(state_specs, state_specs), check_vma=False)
