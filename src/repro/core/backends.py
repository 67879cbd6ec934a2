"""Pluggable execution backends for the sweep/update hot path (DESIGN.md §9).

The paper's indegree sub-graph ownership (eq. 14) makes every stage of the
per-dt hot path race-free *structurally*: each partition writes only its own
post rows.  That property is substrate-independent, so the three stages -

    sweep          edges -> per-neuron (input_ex, input_in) + per-edge arrivals
    neuron_update  fused propagate / threshold / reset / refractory
                   (model-dispatched through repro.core.neuron_models, §12)
    stdp_update    pl-STDP weight update on owned edges

- are expressed here once as a :class:`SweepBackend` interface with
interchangeable implementations, the same engine-extraction move CoreNEURON
made for NEURON (memory layout + compute engine swapped together under one
network description):

* ``flat``     - one fused gather + two ``segment_sum`` reductions (the
                 TPU/XLA-idiomatic form; DESIGN.md §2);
* ``bucketed`` - the paper's literal low-to-high delay sweep (a Fugaku
                 thread's schedule), kept as the structural cross-check;
* ``pallas``   - the Pallas TPU kernels (``synaptic_gather``, ``lif_step``,
                 ``stdp_update``) on the post-block ELL layout of
                 :mod:`repro.core.layout`; interpret mode off-TPU, compiled
                 on TPU.  ``"pallas:auto"`` resolves the same backend with
                 (PB, EB) autotuned from the shard degree distribution
                 (:mod:`repro.core.autotune`).

Both the single-shard engine (:mod:`repro.core.engine`) and the distributed
engine (:mod:`repro.core.distributed`) dispatch through this registry; the
distributed step additionally uses :meth:`SweepBackend.sweep_overlap` to
realize the paper's §III.C communication/computation overlap schedule.

Layout contract: a backend consumes an :class:`EdgeLayout` built either from
a ``ShardGraph`` (host side, numpy/jnp constants) or from shard_map-traced
per-shard arrays (device side).  Static geometry (counts, block shapes)
must be Python ints in both cases; array fields may be traced.

Weight/arrivals layout (the blocked-resident hot path): a backend declares
``weights_layout`` - ``"flat"`` (owner-sorted (E,), the default) or
``"blocked"`` (the ELL slot order, (NB*EB,)).  Run-time weights live in the
backend's native layout inside engine/distributed state; ``edge_perm``
conversions happen only at the build / checkpoint / telemetry boundaries
(:func:`to_native_weights` / :func:`to_flat_weights`), never per step.
``sweep`` returns ``arrived`` in the same native order, and
:meth:`SweepBackend.edge_pre_index` names the per-edge pre index aligned
with it (trace updates consume the pair).  New backends (e.g. GPU
Triton) register with :func:`register_backend` and become selectable via
``EngineConfig.sweep`` - and are multi-host-capable for free: the
multi-process engine (:mod:`repro.core.multihost`, DESIGN.md §11) runs
the same registry-dispatched step across hosts, changing only array
placement.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as autotune_mod
from repro.core import neuron_models as neuron_models_mod
from repro.core import snn
from repro.core import stdp as stdp_mod
from repro.core.layout import BlockedGraph, blocked_layout
from repro.kernels.stdp_update import stdp_update_kernel, stdp_update_worklist
from repro.kernels.synaptic_gather import (blocked_arrivals,
                                           blocked_reduce_sweep,
                                           synaptic_gather)

__all__ = ["EdgeLayout", "SweepBackend", "FlatBackend", "BucketedBackend",
           "PallasBackend", "SparsePallasBackend", "register_backend",
           "get_backend", "available_backends", "to_native_weights",
           "to_flat_weights", "flat_edge_values", "layout_tag",
           "layout_kind", "resolve_runtime_weights"]


# --------------------------------------------------------------------------
# layout handed to backends
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EdgeLayout:
    """Per-shard edge arrays + static geometry, as one backend-facing view.

    ``bucket_ptr`` (static numpy delay ranges) only exists host-side; under
    shard_map it is None and the bucketed backend falls back to delay
    masking.  ``blocked`` carries the ELL layout for the kernel path.
    """

    n_local: int
    n_mirror: int
    max_delay: int
    pre_idx: Any       # (E,) int32
    post_idx: Any      # (E,) int32
    delay: Any         # (E,) int32; 0 marks padding
    channel: Any       # (E,) int32
    plastic: Any       # (E,) bool
    bucket_ptr: np.ndarray | None = None
    blocked: BlockedGraph | None = None

    @property
    def n_edges(self) -> int:
        return int(self.pre_idx.shape[0])


def layout_of(graph) -> EdgeLayout:
    """EdgeLayout view of a :class:`repro.core.engine.ShardGraph`."""
    return EdgeLayout(
        n_local=graph.n_local, n_mirror=graph.n_mirror,
        max_delay=graph.max_delay,
        pre_idx=graph.pre_idx, post_idx=graph.post_idx, delay=graph.delay,
        channel=graph.channel, plastic=graph.plastic,
        bucket_ptr=graph.bucket_ptr,
        blocked=getattr(graph, "blocked", None),
    )


def _device_blocked(bg: BlockedGraph) -> BlockedGraph:
    """Device-resident copy of the blocked static edge arrays.

    Done ONCE in ``prepare`` so traced sweep calls never re-``jnp.asarray``
    the constants (each call would re-stage a host->device transfer into
    the jaxpr); build-time-only fields (weight) are dropped.  The arrays
    are made eagerly even when ``prepare`` runs inside a trace (``jit`` of
    ``engine.run``): the cache must never hold that trace's tracers.
    """
    as_j = lambda a, dt: (None if a is None
                          else jnp.asarray(np.asarray(a), dtype=dt))
    with jax.ensure_compile_time_eval():
        return dataclasses.replace(
            bg,
            pre_idx=as_j(bg.pre_idx, jnp.int32),
            post_rel=as_j(bg.post_rel, jnp.int32),
            delay=as_j(bg.delay, jnp.int32),
            channel=as_j(bg.channel, jnp.int32),
            plastic=as_j(bg.plastic, jnp.bool_),
            edge_perm=as_j(bg.edge_perm, jnp.int32),
            weight=None,
        )


def _accumulate(layout: EdgeLayout, weights, arrived):
    """Weighted per-edge arrivals -> (input_ex, input_in) via segment_sum.

    Race-free by construction: ``post_idx`` is owner-sorted, so this is the
    vector analogue of "each thread owns its rows" (eq. 14).
    """
    contrib = weights * arrived
    ex = jnp.where(layout.channel == 0, contrib, 0.0)
    inh = jnp.where(layout.channel == 1, contrib, 0.0)
    return (jax.ops.segment_sum(ex, layout.post_idx,
                                num_segments=layout.n_local),
            jax.ops.segment_sum(inh, layout.post_idx,
                                num_segments=layout.n_local))


def _flat_arrivals(layout: EdgeLayout, ring, t):
    """``arrived[e] = ring[(t - delay[e]) mod D, pre_idx[e]]``, padding
    masked.  One fused gather over the flattened ring."""
    row = jnp.mod(t - layout.delay, layout.max_delay)
    flat = ring.reshape(-1)
    arrived = jnp.take(flat, row * layout.n_mirror + layout.pre_idx)
    return arrived * (layout.delay > 0)


# --------------------------------------------------------------------------
# weight/arrivals layout conversion (build / checkpoint / telemetry only)
# --------------------------------------------------------------------------

def _require_blocked(layout: EdgeLayout) -> BlockedGraph:
    if layout.blocked is None:
        raise ValueError("layout carries no blocked ELL arrays; build "
                         "graphs via builder.build_shards(with_blocked="
                         "True) or call PallasBackend.prepare")
    return layout.blocked


def layout_kind(tag: str) -> str:
    """"flat" / "blocked:256x2048" / "blocked" -> the layout KIND."""
    return tag.split(":", 1)[0]


def layout_tag(layout: EdgeLayout, kind: str) -> str:
    """Canonical run-time layout tag for state markers.

    "flat" stays "flat"; "blocked" resolves to ``"blocked:{pb}x{eb}"`` so a
    state built under one (PB, EB) can never be silently stepped under
    another - equal slot TOTALS with different shapes would apply every
    weight to the wrong edge otherwise.
    """
    if kind == "flat":
        return "flat"
    if layout_kind(kind) == "blocked":
        if kind != "blocked":   # shape-qualified: must name THIS layout
            _check_blocked_tag(layout, kind)
        bg = _require_blocked(layout)
        return f"blocked:{bg.pb}x{bg.eb}"
    raise ValueError(f"unknown weights layout {kind!r}")


def _check_blocked_tag(layout: EdgeLayout, tag: str):
    """A blocked tag must name THIS layout's block shapes - converting a
    vector minted under different (PB, EB) through this layout's edge_perm
    would scramble it."""
    want = layout_tag(layout, "blocked")
    if tag not in ("blocked", want):   # bare "blocked" = trust the caller
        raise ValueError(
            f"weights carry layout {tag!r} but this graph's blocked layout "
            f"is {want!r} - different (PB, EB) block shapes; re-express "
            "through 'flat' with the ORIGINAL layout first")


def to_native_weights(layout: EdgeLayout, w_flat, target: str):
    """Flat owner-sorted weights -> ``target`` layout ("flat"|"blocked").

    Blocked padding slots receive ``w_flat[edge_perm=0]`` garbage; every
    consumer masks them (sweep by ``delay>0``, STDP by ``plastic``), and
    :func:`to_flat_weights` drops them on the way back.
    """
    kind = layout_kind(target)
    if kind == "flat":
        return w_flat
    if kind == "blocked":
        _check_blocked_tag(layout, target)
        bg = _require_blocked(layout)
        return jnp.take(w_flat, bg.edge_perm.reshape(-1))
    raise ValueError(f"unknown weights layout {target!r}")


def flat_edge_values(layout: EdgeLayout, vals, source: str, *, fill=0):
    """Per-edge values in ``source`` layout -> FLAT edge order.

    Blocked padding slots are dropped (flat padding edges read ``fill``);
    flat padding edges (delay==0 tail) also read ``fill`` - they carry no
    state in either layout.
    """
    kind = layout_kind(source)
    if kind == "flat":
        return vals
    if kind == "blocked":
        _check_blocked_tag(layout, source)
        bg = _require_blocked(layout)
        e = layout.n_edges
        perm = bg.edge_perm.reshape(-1)
        live = bg.delay.reshape(-1) > 0
        idx = jnp.where(live, perm, e)          # padding -> dump slot
        out = jnp.full((e + 1,), fill, vals.dtype).at[idx].set(vals)
        return out[:e]
    raise ValueError(f"unknown weights layout {source!r}")


def to_flat_weights(layout: EdgeLayout, w, source: str):
    """Inverse of :func:`to_native_weights` (flat padding slots read 0)."""
    return flat_edge_values(layout, w, source)


def convert_weights(layout: EdgeLayout, w, src: str, dst: str):
    if layout_kind(src) == layout_kind(dst):
        if layout_kind(src) == "blocked":   # same kind: shapes must match
            _check_blocked_tag(layout, src)
            _check_blocked_tag(layout, dst)
        return w
    return to_native_weights(layout, to_flat_weights(layout, w, src), dst)


def resolve_runtime_weights(backend: "SweepBackend", layout: EdgeLayout,
                            weights, state_tag: str):
    """One shared entry for both engines' per-step weight residency.

    Returns ``(w_native, native_tag, convert_back)``: ``w_native`` in the
    backend's native layout, and ``convert_back=True`` iff the caller must
    re-express updated weights as ``state_tag`` to keep its scan carry
    stable (the flat-state COMPATIBILITY path - one edge gather per
    direction per step; carry native state to avoid it).
    """
    native_tag = layout_tag(layout, backend.weights_layout)
    if state_tag == native_tag or (state_tag == "blocked"
                                   and layout_kind(native_tag) == "blocked"):
        ne = backend.native_edge_count(layout)
        if weights.shape[0] != ne:
            raise ValueError(
                f"state weights have {weights.shape[0]} slots but the "
                f"{native_tag!r} layout expects {ne} - mismatched block "
                "shapes; re-express through 'flat' first")
        return weights, native_tag, False
    if (layout_kind(state_tag) == "blocked"
            and layout_kind(native_tag) == "blocked"):
        raise ValueError(
            f"state weights carry layout {state_tag!r} but backend "
            f"{backend.name!r} on this graph expects {native_tag!r} - "
            "different (PB, EB) block shapes; convert the state to 'flat' "
            "with the layout it was built under first")
    # cross-KIND conversion (flat state under a blocked backend, or a
    # blocked state under a flat backend): both directions go through the
    # tag-checked converters - a blocked tag minted under different
    # (PB, EB) than this layout is rejected inside convert_weights
    w_native = convert_weights(layout, weights, state_tag, native_tag)
    return w_native, native_tag, True


# --------------------------------------------------------------------------
# backend interface + implementations
# --------------------------------------------------------------------------

class SweepBackend:
    """One execution substrate for the per-dt hot path.

    Subclasses override ``sweep`` (mandatory) and optionally
    ``neuron_update`` / ``stdp_update`` / ``sweep_overlap``; the base class
    provides the XLA formulations so a minimal backend only supplies its
    sweep.
    """

    name: str = "?"
    #: True if sweep() consumes EdgeLayout.blocked - the distributed engine
    #: uses this to decide whether to ship the stacked ELL consts
    needs_blocked: bool = False
    #: run-time layout of the weight and ``arrived`` vectors this backend's
    #: sweep/stdp_update consume and produce: "flat" or "blocked".  Engine
    #: state stores weights in THIS layout; conversions happen only at the
    #: build/checkpoint/telemetry boundaries (DESIGN.md §9).
    weights_layout: str = "flat"

    def prepare(self, graph) -> EdgeLayout:
        """Build-time: ShardGraph -> the layout this backend consumes."""
        return layout_of(graph)

    # -- run-time edge-vector layout --------------------------------------
    def native_edge_count(self, layout: EdgeLayout) -> int:
        """Length of the run-time weight/arrivals vectors."""
        if self.weights_layout == "blocked":
            bg = _require_blocked(layout)
            return bg.nb * bg.eb
        return layout.n_edges

    def to_native_weights(self, layout: EdgeLayout, w_flat):
        return to_native_weights(layout, w_flat, self.weights_layout)

    def to_flat_weights(self, layout: EdgeLayout, w):
        return to_flat_weights(layout, w, self.weights_layout)

    def edge_pre_index(self, layout: EdgeLayout):
        """Per-edge pre (mirror) index aligned with ``arrived``'s order."""
        if self.weights_layout == "blocked":
            return _require_blocked(layout).pre_idx.reshape(-1)
        return layout.pre_idx

    # -- synaptic sweep ---------------------------------------------------
    def sweep(self, layout: EdgeLayout, weights, ring, t):
        """Accumulate (input_ex, input_in, arrived) for step ``t``.

        ``weights`` and the returned ``arrived`` are in ``weights_layout``
        order; ``arrived[e]`` is 1.0 iff edge ``e``'s pre spike arrives
        exactly now - consumed by both the current accumulation and the
        STDP depression rule.
        """
        raise NotImplementedError

    def sweep_overlap(self, layout: EdgeLayout, weights, ring, t,
                      fresh_bits):
        """Sweep with last step's spikes ``fresh_bits`` not yet in the ring
        (paper §III.C): returns (input_ex, input_in, arrived, ring').

        Default schedule: write the fresh bits into slot ``t-1`` and run one
        full sweep - correct but serialized on the exchange.  Backends that
        can split the work (delay >= 2 from the old ring, delay == 1 from
        the fresh bits) override this so the exchange overlaps the
        independent part.
        """
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, fresh_bits, jnp.mod(t - 1, layout.max_delay), axis=0)
        ex, inh, arrived = self.sweep(layout, weights, ring, t)
        return ex, inh, arrived, ring

    # -- gate telemetry ---------------------------------------------------
    #: True iff sweep dispatch is activity-gated - the ``*_with_stats``
    #: variants then report real saturation counts (DESIGN.md §13)
    gated: bool = False

    def sweep_with_stats(self, layout: EdgeLayout, weights, ring, t):
        """:meth:`sweep` plus this step's gate-saturation count: 1 when an
        activity gate overflowed its worklist and fell back to the dense
        pass, 0 otherwise (always 0 on ungated backends).  Engines
        accumulate it into ``gate_overflow`` state, the compute twin of
        ``DistState.wire_overflow``."""
        ex, inh, arrived = self.sweep(layout, weights, ring, t)
        return ex, inh, arrived, jnp.zeros((), jnp.int32)

    def sweep_overlap_with_stats(self, layout: EdgeLayout, weights, ring,
                                 t, fresh_bits):
        """:meth:`sweep_overlap` plus the gate-saturation count."""
        ex, inh, arrived, ring = self.sweep_overlap(layout, weights, ring,
                                                    t, fresh_bits)
        return ex, inh, arrived, ring, jnp.zeros((), jnp.int32)

    # -- neuron dynamics --------------------------------------------------
    def neuron_update(self, layout: EdgeLayout, neurons, table, input_ex,
                      input_in, *,
                      synapse_model: str = snn.SynapseModel.CURRENT_EXP,
                      model=None, key=None, t=None, gid=None,
                      surrogate=None):
        """Fused propagate/threshold/reset/refractory for one dt,
        dispatched through the NeuronModel registry (DESIGN.md §12).

        ``model`` is a registry name or NeuronModel instance (None =
        "lif", the historical default - bit-identical to the pre-registry
        path); ``key``/``t``/``gid`` feed stochastic models (poisson
        emitters; ``gid`` keys per-neuron draws by GLOBAL id so they are
        decomposition-invariant) and are ignored by deterministic
        dynamics.  ``surrogate`` (DESIGN.md §17) selects the
        surrogate-gradient spike on models that support it; the kwarg is
        only forwarded when set, so inference-mode dispatch - and every
        model that never opted in - is untouched.
        """
        m = neuron_models_mod.get_model("lif" if model is None else model)
        if surrogate is None:
            return m.step(neurons, table, input_ex, input_in,
                          synapse_model=synapse_model, key=key, t=t,
                          gid=gid)
        m.spike_fn(surrogate)   # raises early on non-surrogate models
        return m.step(neurons, table, input_ex, input_in,
                      synapse_model=synapse_model, key=key, t=t, gid=gid,
                      surrogate=surrogate)

    # -- plasticity -------------------------------------------------------
    def stdp_update(self, layout: EdgeLayout, weights, arrived, post_spike,
                    traces, params: stdp_mod.STDPParams):
        """pl-STDP weight update on owned edges (``weights``/``arrived`` in
        ``weights_layout`` order); non-plastic edges pass through
        unchanged."""
        new_w = stdp_mod.stdp_edge_update(
            weights, layout.pre_idx, layout.post_idx, arrived, post_spike,
            traces, params)
        return jnp.where(layout.plastic, new_w, weights)


class FlatBackend(SweepBackend):
    """Fused-gather + segment_sum sweep - the XLA/TPU-idiomatic form: one
    large vectorized gather beats a per-bucket loop on a systolic/vector
    machine, and sparsity is exploited through zero values rather than
    skipped work (DESIGN.md §2)."""

    name = "flat"

    def sweep(self, layout, weights, ring, t):
        arrived = _flat_arrivals(layout, ring, t)
        ex, inh = _accumulate(layout, weights, arrived)
        return ex, inh, arrived

    def sweep_overlap(self, layout, weights, ring, t, fresh_bits):
        # Split schedule: delays >= 2 read only OLD ring slots, so their
        # gather+reduce is independent of the exchange producing
        # ``fresh_bits`` and XLA's async collectives overlap the two; only
        # the delay-1 part consumes the collective's result.
        D = layout.max_delay
        dtype = ring.dtype
        arrived_old = _flat_arrivals(layout, ring, t)
        mask_old = (layout.delay >= 2).astype(dtype)
        ex_o, in_o = _accumulate(layout, weights, arrived_old * mask_old)
        arrived_new = jnp.take(fresh_bits, layout.pre_idx)
        mask_new = (layout.delay == 1).astype(dtype)
        ex_n, in_n = _accumulate(layout, weights, arrived_new * mask_new)
        arrived = arrived_old * mask_old + arrived_new * mask_new
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, fresh_bits, jnp.mod(t - 1, D), axis=0)
        return ex_o + ex_n, in_o + in_n, arrived, ring


class BucketedBackend(SweepBackend):
    """The paper's literal low-to-high delay sweep (what a Fugaku thread
    does), kept as the structural twin of the Pallas kernel and for
    cross-checks.  Host-side it walks static ``bucket_ptr`` slices; under
    shard_map (no per-shard statics) it falls back to delay masking."""

    name = "bucketed"

    def sweep(self, layout, weights, ring, t):
        D = layout.max_delay
        n_local = layout.n_local
        dtype = weights.dtype
        input_ex = jnp.zeros((n_local,), dtype)
        input_in = jnp.zeros((n_local,), dtype)

        if layout.bucket_ptr is not None:
            arrived = jnp.zeros(layout.delay.shape, dtype)
            bp = np.asarray(layout.bucket_ptr)
            for d in range(1, D + 1):
                lo, hi = int(bp[d]), int(bp[d + 1])
                if lo == hi:
                    continue
                bits = ring[jnp.mod(t - d, D)]
                pre = jax.lax.slice_in_dim(layout.pre_idx, lo, hi)
                post = jax.lax.slice_in_dim(layout.post_idx, lo, hi)
                ch = jax.lax.slice_in_dim(layout.channel, lo, hi)
                w = jax.lax.slice_in_dim(weights, lo, hi)
                a = jnp.take(bits, pre).astype(dtype)
                contrib = w * a
                input_ex = input_ex + jax.ops.segment_sum(
                    jnp.where(ch == 0, contrib, 0.0), post,
                    num_segments=n_local)
                input_in = input_in + jax.ops.segment_sum(
                    jnp.where(ch == 1, contrib, 0.0), post,
                    num_segments=n_local)
                arrived = jax.lax.dynamic_update_slice(arrived, a, (lo,))
            return input_ex, input_in, arrived

        # traced-layout fallback: one masked full pass per delay value
        arrived = jnp.zeros(layout.delay.shape, ring.dtype)
        for d in range(1, D + 1):
            bits = ring[jnp.mod(t - d, D)]
            a = (jnp.take(bits, layout.pre_idx)
                 * (layout.delay == d).astype(ring.dtype))
            ex_d, in_d = _accumulate(layout, weights, a)
            input_ex, input_in = input_ex + ex_d, input_in + in_d
            arrived = arrived + a
        return input_ex, input_in, arrived


class PallasBackend(SweepBackend):
    """Kernel path: post-block ELL sweep on the MXU, fused LIF chain, and
    pl-STDP edge update as Pallas TPU kernels (interpret mode off-TPU).

    The blocked layout is the RESIDENT hot-path representation: run-time
    weights live in ELL slot order ((NB*EB,)) in engine/distributed state,
    the sweep returns the per-edge arrivals its reduction consumed (one
    ring gather per step - no second one for STDP, no per-step
    ``edge_perm`` re-gather of weights), and the STDP kernel
    consumes the blocked arrivals/weights directly with block-relative post
    rows.  ``edge_perm`` conversions run only at build, checkpoint and
    telemetry boundaries.

    ``block_shapes``: None uses the layout the builder emitted (or the
    fixed defaults), ``"auto"`` autotunes (PB, EB) from the shard's degree
    distribution against the sweep kernel's VMEM model
    (:mod:`repro.core.autotune`), an explicit
    :class:`repro.core.autotune.BlockShapes` pins them.
    """

    name = "pallas"
    needs_blocked = True
    weights_layout = "blocked"
    #: neuron block for the LIF kernel (lane-aligned)
    lif_nb = 128

    def __init__(self, interpret: bool | None = None, block_shapes=None):
        # interpret None -> auto: compiled on TPU, interpreter elsewhere
        self.interpret = interpret
        self.block_shapes = block_shapes
        # (id(anchor), spec) -> (weakref(anchor), device BlockedGraph);
        # repeated prepare calls (init_state + make_step_fn + run on one
        # graph) reuse the same device buffers - and, on the autotuned
        # path, the same relayout - instead of redoing both per call
        self._dev_cache: dict[tuple, tuple] = {}

    def _interp(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return self.interpret

    def prepare(self, graph) -> EdgeLayout:
        lay = layout_of(graph)
        # the cache anchor is whatever long-lived host object determines
        # the result: the prebuilt BlockedGraph if one exists, else the
        # graph itself (autotuned relayouts are derived from it)
        anchor = lay.blocked if lay.blocked is not None else graph
        key = (id(anchor), str(self.block_shapes))
        hit = self._dev_cache.get(key)
        if hit is not None and hit[0]() is anchor:
            return dataclasses.replace(lay, blocked=hit[1])
        bg = lay.blocked
        if self.block_shapes is not None:
            from repro.core.autotune import resolve_block_shapes
            shapes = resolve_block_shapes(graph, self.block_shapes)
            # a prebuilt layout already satisfying the resolved shapes is
            # reused (a wider uniform-stacked EB is still valid); only a
            # genuine mismatch pays the O(E log E) relayout
            if shapes is not None and (
                    bg is None or bg.pb != shapes.pb or bg.eb < shapes.eb):
                bg = blocked_layout(graph, pb=shapes.pb, eb_min=shapes.eb)
        if bg is None:
            bg = blocked_layout(graph)
        if not isinstance(bg.pre_idx, jax.Array):
            bg = _device_blocked(bg)
        try:
            ref = weakref.ref(anchor)
        except TypeError:       # non-weakrefable anchor: skip caching
            return dataclasses.replace(lay, blocked=bg)
        # drop dead entries on EVERY insert (a dead anchor's device arrays
        # would otherwise stay pinned in HBM), then hard-bound the rest
        self._dev_cache = {k: v for k, v in self._dev_cache.items()
                           if v[0]() is not None}
        while len(self._dev_cache) >= 64:       # evict oldest live entry
            self._dev_cache.pop(next(iter(self._dev_cache)))
        self._dev_cache[key] = (ref, bg)
        return dataclasses.replace(lay, blocked=bg)

    def _gather(self, layout, weights, ring, t, fresh):
        bg = _require_blocked(layout)
        w_blk = weights.astype(jnp.float32).reshape(bg.nb, bg.eb)
        i_ex, i_in, arrived = synaptic_gather(
            bg.pre_idx, bg.post_rel, w_blk, bg.delay, bg.channel,
            ring.astype(jnp.float32), jnp.asarray(t, jnp.int32),
            max_delay=layout.max_delay, pb=bg.pb, interpret=self._interp(),
            emit_arrivals=True,
            fresh=None if fresh is None else fresh.astype(jnp.float32))
        dtype = ring.dtype
        return (i_ex[:layout.n_local].astype(dtype),
                i_in[:layout.n_local].astype(dtype),
                arrived.reshape(-1).astype(dtype))

    def sweep(self, layout, weights, ring, t):
        return self._gather(layout, weights, ring, t, None)

    def sweep_overlap(self, layout, weights, ring, t, fresh_bits):
        # One dispatch serves the §III.C split: the kernel reads delay>=2
        # arrivals from the OLD ring and delay==1 from ``fresh_bits``, so
        # the slot-(t-1) ring write below is independent of the sweep (XLA
        # updates it in place instead of materializing a pre-sweep copy)
        # and only the delay-1 term waits on the exchange collective.
        ex, inh, arrived = self._gather(layout, weights, ring, t,
                                        fresh_bits)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, fresh_bits, jnp.mod(t - 1, layout.max_delay), axis=0)
        return ex, inh, arrived, ring

    def neuron_update(self, layout, neurons, table, input_ex, input_in, *,
                      synapse_model: str = snn.SynapseModel.CURRENT_EXP,
                      model=None, key=None, t=None, gid=None,
                      surrogate=None):
        # kernel path when the model ships a Pallas twin (lif/izhikevich/
        # adex); models without one (poisson) run their jnp step - it is
        # a single elementwise draw, the same on every backend.
        # Surrogate mode (DESIGN.md §17) runs the jnp oracle instead: the
        # kernels have no VJP, and the §12 interpret contract (kernel ==
        # oracle bit-for-bit) keeps the forward trajectory identical -
        # pinned by tests/test_diff.py.
        m = neuron_models_mod.get_model("lif" if model is None else model)
        if surrogate is not None:
            m.spike_fn(surrogate)   # raises early on non-surrogate models
            return m.step(neurons, table, input_ex, input_in,
                          synapse_model=synapse_model, key=key, t=t,
                          gid=gid, surrogate=surrogate)
        if m.kernel_step is None:
            return m.step(neurons, table, input_ex, input_in,
                          synapse_model=synapse_model, key=key, t=t, gid=gid)
        return m.kernel_step(neurons, table, input_ex, input_in,
                             synapse_model=synapse_model, nb=self.lif_nb,
                             interpret=self._interp(), key=key, t=t, gid=gid)

    def stdp_update(self, layout, weights, arrived, post_spike, traces,
                    params: stdp_mod.STDPParams):
        bg = _require_blocked(layout)
        if bg.plastic is None:
            raise ValueError(
                "blocked layout lacks the plastic mask (ship the "
                "blk_plastic const alongside the other blk_* arrays) - "
                "required by the blocked-resident STDP kernel")
        # blocked-resident path: weights/arrived already in ELL slot order,
        # post rows block-relative - zero layout conversion, one grid cell
        # per post block (race-free by eq. 14)
        new_w = stdp_update_kernel(
            weights.astype(jnp.float32), bg.pre_idx.reshape(-1),
            bg.post_rel.reshape(-1), bg.plastic.reshape(-1),
            arrived.astype(jnp.float32),
            post_spike.astype(jnp.float32),
            traces.k_pre.astype(jnp.float32),
            traces.k_post.astype(jnp.float32),
            params=(params.lam, params.alpha, params.mu, params.w0,
                    params.w_min, params.w_max),
            eb=bg.eb, pb=bg.pb, interpret=self._interp())
        return new_w.astype(weights.dtype)


class SparsePallasBackend(PallasBackend):
    """Activity-gated sweep: step cost scales with ACTIVITY, not topology
    (DESIGN.md §13).

    At biological rates only a few percent of neurons spike per step, yet
    the dense kernel touches every ELL slot of every post block every step.
    This backend runs the dense path's own arrivals gather
    (:func:`repro.kernels.synaptic_gather.blocked_arrivals` - (NB, EB)
    blocked arrivals), counts the per-block arrival population, and
    compacts the ACTIVE block ids into a fixed-capacity worklist:

    * capacity comes from the same firing-rate headroom policy as the
      ``sparse:<rate>`` wire (:func:`repro.core.autotune.gate_capacity`);
    * the gated Pallas grid (:func:`blocked_reduce_sweep`) dispatches ONLY
      worklist blocks - the compacted inputs are scattered back onto
      zero-initialized accumulators, so dead blocks keep their zeros and
      pay neither gather nor matmul;
    * saturation (more active blocks than capacity) deterministically falls
      back to the dense pass over the SAME pre-gathered arrivals - never a
      dropped spike - and reports 1 through :meth:`sweep_with_stats`, the
      compute twin of ``DistState.wire_overflow``;
    * the gate covers BOTH halves of the single edge pass: the STDP
      depression consuming ``emit_arrivals`` runs on a worklist grid too
      (:func:`repro.kernels.stdp_update.stdp_update_worklist`), with a
      block counted active when it has an arrival OR a post spike.  A
      skipped block keeps its weights - bit-identical to the dense update
      whenever resident plastic weights already sit inside
      ``[w_min, w_max]`` (the dense kernel's only effect on a dead block is
      the clip; engine init + every prior update maintain the invariant).

    ``capacity >= NB`` (tiny graphs, or rates near 1) degenerates to the
    dense reduce with no branch at all.  Dense ``pallas`` remains the
    bit-exact oracle: active blocks run the identical where/dot tail on the
    identical arrivals, so spikes AND voltages match bit-for-bit.
    """

    name = "pallas:sparse"
    gated = True

    def __init__(self, interpret: bool | None = None, block_shapes=None,
                 gate_rate=autotune_mod.DEFAULT_GATE_RATE,
                 min_capacity: int = autotune_mod.DEFAULT_GATE_MIN_CAPACITY):
        super().__init__(interpret=interpret, block_shapes=block_shapes)
        if isinstance(gate_rate, str):
            # "measured:<path>": capacity picked from the BENCH file's
            # gate_tune/ records for this layout's degree signature
            # (autotune.measured_gate_capacity), model fallback otherwise
            if not gate_rate.startswith("measured:"):
                raise ValueError(
                    f"gate rate must be a float in (0, 1] or "
                    f"'measured:<path>', got {gate_rate!r}")
            self.gate_rate = gate_rate
            self.name = f"pallas:sparse:{gate_rate}"
        else:
            if not 0.0 < gate_rate <= 1.0:
                raise ValueError(
                    f"gate rate must be in (0, 1], got {gate_rate!r}")
            self.gate_rate = float(gate_rate)
            if self.gate_rate != autotune_mod.DEFAULT_GATE_RATE:
                self.name = f"pallas:sparse:{self.gate_rate:g}"
        self.min_capacity = int(min_capacity)

    # -- gate policy ------------------------------------------------------
    def gate_capacity(self, layout: EdgeLayout) -> int:
        """Static worklist capacity (in post blocks) for this layout."""
        bg = _require_blocked(layout)
        sig = None
        if isinstance(self.gate_rate, str):
            # the signature is computed from the LAYOUT's degree arrays
            # (padding rows included) - bench_gate_tune keys its records
            # the same way, so emitter and consumer always agree
            sig = autotune_mod.degree_signature(
                autotune_mod.degrees_from_graphs([layout]))
        return autotune_mod.gate_capacity(
            bg.nb, layout.n_edges, self.gate_rate,
            min_capacity=self.min_capacity, signature=sig)

    def _blocked_arrivals(self, layout: EdgeLayout, ring, t, fresh):
        """(NB, EB) f32 per-edge arrivals - the pre-pass, the same gather
        the dense kernel path runs (:func:`blocked_arrivals`)."""
        bg = _require_blocked(layout)
        return blocked_arrivals(bg.pre_idx, bg.delay, ring, t,
                                max_delay=layout.max_delay, fresh=fresh)

    def gate_stats(self, layout: EdgeLayout, ring, t, fresh=None):
        """(per-block arrival counts (NB,), n_active (), capacity) - the
        observable the gate dispatches on; used by telemetry and tests."""
        arrived = self._blocked_arrivals(layout, ring, t, fresh)
        counts = jnp.sum(arrived > 0, axis=1).astype(jnp.int32)
        n_active = jnp.count_nonzero(counts).astype(jnp.int32)
        return counts, n_active, self.gate_capacity(layout)

    # -- gated edge pass --------------------------------------------------
    def _gated_sweep(self, layout, weights, ring, t, fresh):
        bg = _require_blocked(layout)
        nb, eb, pb = bg.nb, bg.eb, bg.pb
        interp = self._interp()
        arrived = self._blocked_arrivals(layout, ring, t, fresh)
        w32 = weights.astype(jnp.float32).reshape(nb, eb)
        cap = self.gate_capacity(layout)

        if cap >= nb:       # full-capacity gate == dense pass, no branch
            ex, inh = blocked_reduce_sweep(
                bg.post_rel, w32, arrived, bg.channel, pb=pb,
                interpret=interp)
            overflow = jnp.zeros((), jnp.int32)
        else:
            counts = jnp.sum(arrived > 0, axis=1)
            n_active = jnp.count_nonzero(counts).astype(jnp.int32)
            # deterministic fixed-size compaction: ascending block ids,
            # padding slots carry the out-of-range sentinel ``nb`` whose
            # takes clip and whose scatter rows drop
            (wl,) = jnp.nonzero(counts > 0, size=cap, fill_value=nb)
            wl = wl.astype(jnp.int32)
            overflow = (n_active > cap).astype(jnp.int32)

            def gated(_):
                take = lambda a: jnp.take(a, wl, axis=0)
                exc, inc = blocked_reduce_sweep(
                    take(bg.post_rel), take(w32), take(arrived),
                    take(bg.channel), pb=pb, interpret=interp)
                zeros = jnp.zeros((nb, pb), jnp.float32)
                return (zeros.at[wl].set(exc, mode="drop"),
                        zeros.at[wl].set(inc, mode="drop"))

            def dense(_):
                return blocked_reduce_sweep(
                    bg.post_rel, w32, arrived, bg.channel, pb=pb,
                    interpret=interp)

            ex, inh = jax.lax.cond(n_active <= cap, gated, dense, None)

        dtype = ring.dtype
        return (ex.reshape(-1)[:layout.n_local].astype(dtype),
                inh.reshape(-1)[:layout.n_local].astype(dtype),
                arrived.reshape(-1).astype(dtype), overflow)

    def sweep(self, layout, weights, ring, t):
        ex, inh, arrived, _ = self._gated_sweep(layout, weights, ring, t,
                                                None)
        return ex, inh, arrived

    def sweep_with_stats(self, layout, weights, ring, t):
        return self._gated_sweep(layout, weights, ring, t, None)

    def sweep_overlap(self, layout, weights, ring, t, fresh_bits):
        out = self.sweep_overlap_with_stats(layout, weights, ring, t,
                                            fresh_bits)
        return out[:4]

    def sweep_overlap_with_stats(self, layout, weights, ring, t,
                                 fresh_bits):
        # same §III.C split as the dense backend: the pre-pass folds
        # ``fresh_bits`` into the delay-1 arrivals, so the slot-(t-1) ring
        # write stays independent of the sweep and only the delay-1 term
        # waits on the exchange collective
        ex, inh, arrived, overflow = self._gated_sweep(
            layout, weights, ring, t, fresh_bits)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, fresh_bits, jnp.mod(t - 1, layout.max_delay), axis=0)
        return ex, inh, arrived, ring, overflow

    # -- gated plasticity -------------------------------------------------
    def stdp_update(self, layout, weights, arrived, post_spike, traces,
                    params: stdp_mod.STDPParams):
        bg = _require_blocked(layout)
        if bg.plastic is None:
            raise ValueError(
                "blocked layout lacks the plastic mask (ship the "
                "blk_plastic const alongside the other blk_* arrays) - "
                "required by the blocked-resident STDP kernel")
        nb, eb, pb = bg.nb, bg.eb, bg.pb
        cap = self.gate_capacity(layout)
        if cap >= nb:       # full-capacity gate: the dense oracle path
            return super().stdp_update(layout, weights, arrived,
                                       post_spike, traces, params)

        w32 = weights.astype(jnp.float32).reshape(nb, eb)
        arr = arrived.astype(jnp.float32).reshape(nb, eb)
        sp = post_spike.astype(jnp.float32)
        kpre = traces.k_pre.astype(jnp.float32)
        kpost = traces.k_post.astype(jnp.float32)
        ptuple = (params.lam, params.alpha, params.mu, params.w0,
                  params.w_min, params.w_max)
        interp = self._interp()

        # a block is active for plasticity if any edge arrival lands in it
        # (depression term) OR any of its post rows spiked (potentiation
        # term); a block with neither only re-clips in the dense kernel
        sp_blk = jnp.pad(sp > 0, (0, nb * pb - layout.n_local)
                         ).reshape(nb, pb)
        active = jnp.any(arr > 0, axis=1) | jnp.any(sp_blk, axis=1)
        n_active = jnp.count_nonzero(active).astype(jnp.int32)
        (wl,) = jnp.nonzero(active, size=cap, fill_value=nb)
        wl = wl.astype(jnp.int32)

        def gated(_):
            take = lambda a: jnp.take(a, wl, axis=0)
            out_c = stdp_update_worklist(
                take(w32), take(bg.pre_idx), take(bg.post_rel),
                take(bg.plastic), take(arr), wl, sp, kpre, kpost,
                params=ptuple, pb=pb, interpret=interp)
            return w32.at[wl].set(out_c, mode="drop")

        def dense(_):
            out = stdp_update_kernel(
                w32.reshape(-1), bg.pre_idx.reshape(-1),
                bg.post_rel.reshape(-1), bg.plastic.reshape(-1),
                arr.reshape(-1), sp, kpre, kpost, params=ptuple,
                eb=eb, pb=pb, interpret=interp)
            return out.reshape(nb, eb)

        new_w = jax.lax.cond(n_active <= cap, gated, dense, None)
        return new_w.reshape(-1).astype(weights.dtype)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, SweepBackend] = {}

#: parameterized variants ("pallas:auto", "pallas:sparse:<rate>") resolve
#: into THIS side cache, never the registry proper, so
#: ``available_backends()`` stays stable however many variants a run
#: touches - the same bug class as the "sparse:<rate>" wire cache fixed
#: in repro.core.wire (DESIGN.md §10)
_VARIANT_CACHE: dict[str, SweepBackend] = {}


def register_backend(name: str, backend: SweepBackend,
                     *, overwrite: bool = False) -> None:
    """Register an execution backend under ``EngineConfig.sweep`` name."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _REGISTRY[name] = backend


def _resolve_variant(name: str) -> SweepBackend | None:
    if not name.startswith("pallas:"):
        return None
    mode = name.split(":", 1)[1]
    if mode == "auto":
        return PallasBackend(block_shapes="auto")
    if mode.startswith("sparse:"):
        text = mode.split(":", 1)[1]
        if text.startswith("measured:"):
            hit = _VARIANT_CACHE.get(name)
            if hit is None:
                hit = _VARIANT_CACHE[name] = SparsePallasBackend(
                    gate_rate=text)
            return hit
        try:
            rate = float(text)
        except ValueError:
            raise ValueError(
                f"bad gate rate in backend name {name!r}: {text!r} is "
                "not a float") from None
        if not 0.0 < rate <= 1.0:
            raise ValueError(
                f"gate rate in backend name {name!r} must be in (0, 1], "
                f"got {rate!r}")
        # canonical-key cache, so "pallas:sparse:0.01" and
        # "pallas:sparse:0.010" share one backend (and its device caches)
        canon = f"pallas:sparse:{rate:g}"
        hit = _VARIANT_CACHE.get(canon)
        if hit is None:
            hit = _VARIANT_CACHE[canon] = SparsePallasBackend(
                gate_rate=rate)
        return hit
    return None


def get_backend(name) -> SweepBackend:
    if isinstance(name, SweepBackend):
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    # parameterized variants resolve (and cache) on first use, the same
    # move as the "sparse:<rate>" wire names (DESIGN.md §10)
    if isinstance(name, str):
        hit = _VARIANT_CACHE.get(name)
        if hit is not None:
            return hit
        backend = _resolve_variant(name)
        if backend is not None:
            _VARIANT_CACHE[name] = backend
            return backend
    raise ValueError(
        f"unknown sweep backend {name!r}; available: "
        f"{sorted(_REGISTRY)}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend("flat", FlatBackend())
register_backend("bucketed", BucketedBackend())
register_backend("pallas", PallasBackend())
register_backend("pallas:sparse", SparsePallasBackend())
