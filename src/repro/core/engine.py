"""Single-shard simulation engine: delay ring buffer + indegree edge sweep.

This is the reference ("one process / one device") engine.  The distributed
engine in :mod:`repro.core.distributed` wraps exactly this step inside
``shard_map`` and replaces the trivial local spike write with the two-level
spike exchange; :mod:`repro.core.multihost` carries that same step across
processes (DESIGN.md §11).

Data layout (the TPU adaptation of paper Fig. 12)
-------------------------------------------------
Each shard owns an indegree sub-graph ``inS(V_i)`` stored as flat, padded,
owner-sorted edge arrays:

    pre_idx[E]   mirror-table index of the pre neuron (local ++ remote)
    post_idx[E]  local index of the post neuron (the OWNER of the edge)
    delay[E]     integer delay in steps (1..max_delay)
    channel[E]   0 = excitatory, 1 = inhibitory
    plastic[E]   STDP participation mask
    weight[E]    in EngineState (mutable under plasticity)

Edges are sorted by (delay, post_idx) - the paper's "reordered according to
their delays and corresponding threads" layout - and ``bucket_ptr``
(static numpy, (max_delay+1,)) gives the per-delay edge ranges.

Spikes fired at step ``s`` are written to ``ring[s % D]`` (D = max_delay,
one bitmap over the mirror table).  At step ``t``, a delay-``d`` edge reads
``ring[(t - d) % D]`` - spikes fired at ``t-d`` arriving exactly at ``t``.

Per-neuron dynamics dispatch through the NeuronModel registry of
:mod:`repro.core.neuron_models` (DESIGN.md §12): ``EngineConfig.
neuron_model`` selects lif / izhikevich / adex / poisson (or a
``<base>+poisson`` composite); ``EngineState`` carries a model tag and the
model's ``extra`` state vars, struct-checked at trace time.

The hot path (sweep, neuron update, STDP edge update) dispatches through the
execution-backend registry of :mod:`repro.core.backends` (DESIGN.md §9):
``EngineConfig.sweep`` selects ``"flat"`` (fused gather + segment_sum, the
TPU/XLA-idiomatic form), ``"bucketed"`` (the paper's literal low-to-high
delay sweep, the structural cross-check), or ``"pallas"`` (the TPU kernels
on the post-block ELL layout; interpret mode off-TPU; ``"pallas:auto"``
autotunes the block shapes).  Tests assert the three produce identical
spike trajectories.

Run-time weights live in the backend's native layout
(``EngineState.weights_layout``: flat owner-sorted for flat/bucketed, ELL
slot order for pallas) so the hot path never pays a per-step ``edge_perm``
conversion; the public API stays FLAT-facing - ``init_state`` defaults to
flat, ``run`` returns flat weights, and :func:`state_with_weights_layout`
converts at the checkpoint/telemetry boundary.  ``engine_step`` accepts
either layout and converts at trace time only when state and backend
disagree (the compatibility path; pass ``sweep=`` to ``init_state`` to
avoid it in hand-rolled step loops).

Writes are conflict-free by construction: every backend reduces over
owner-sorted ``post_idx`` rows it exclusively owns - the vector analogue of
"each thread owns its rows" (eq. 14).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as backends_mod
from repro.core import neuron_models as neuron_models_mod
from repro.core import snn
from repro.core import stdp as stdp_mod

__all__ = ["ShardGraph", "EngineConfig", "EngineState", "init_state",
           "engine_step", "run", "synaptic_sweep", "PHASES", "phase",
           "state_with_weights_layout", "StepContext", "make_step_context",
           "make_step_fn", "make_session_step_fn", "stack_states",
           "slot_state", "set_slot_state", "masked_select",
           "normalize_spike_dtype"]


@dataclasses.dataclass(frozen=True)
class ShardGraph:
    """Static per-shard graph arrays (numpy at build, jnp at run)."""

    n_local: int
    n_mirror: int
    max_delay: int
    pre_idx: Any      # (E,) int32
    post_idx: Any     # (E,) int32
    delay: Any        # (E,) int32, 1..max_delay; 0 marks padding
    channel: Any      # (E,) int32: 0 ex, 1 in
    plastic: Any      # (E,) bool
    weight_init: Any  # (E,) float
    bucket_ptr: np.ndarray  # (max_delay + 2,) int64: edge range per delay d
    # mirror table: where each mirror row's spike bit comes from
    mirror_src_shard: Any   # (n_mirror,) int32
    mirror_src_idx: Any     # (n_mirror,) int32
    group_id: Any           # (n_local,) int32 neuron group per owned neuron
    # Per-neuron external Poisson drive (rate [Hz], weight [pA or nS]).
    ext_rate: Any = None    # (n_local,) float32
    ext_weight: Any = None  # (n_local,) float32
    # GLOBAL neuron id per owned row (-1 on padding rows): the
    # decomposition-invariant key stochastic models fold into their draws
    # so 1-shard and N-shard trajectories match (DESIGN.md §14).
    global_id: Any = None   # (n_local,) int32
    # Post-block ELL twin of the flat arrays (repro.core.layout.BlockedGraph),
    # emitted natively by the builder; consumed by the pallas backend.
    blocked: Any = None

    @property
    def n_edges(self) -> int:
        return int(np.shape(self.pre_idx)[0])

    def device_arrays(self) -> "ShardGraph":
        """numpy -> jnp for the run-time fields."""
        as_j = lambda a, dt: jnp.asarray(np.asarray(a), dtype=dt)
        return dataclasses.replace(
            self,
            pre_idx=as_j(self.pre_idx, jnp.int32),
            post_idx=as_j(self.post_idx, jnp.int32),
            delay=as_j(self.delay, jnp.int32),
            channel=as_j(self.channel, jnp.int32),
            plastic=as_j(self.plastic, jnp.bool_),
            weight_init=as_j(self.weight_init, jnp.float32),
            mirror_src_shard=as_j(self.mirror_src_shard, jnp.int32),
            mirror_src_idx=as_j(self.mirror_src_idx, jnp.int32),
            group_id=as_j(self.group_id, jnp.int32),
            ext_rate=(None if self.ext_rate is None
                      else as_j(self.ext_rate, jnp.float32)),
            ext_weight=(None if self.ext_weight is None
                        else as_j(self.ext_weight, jnp.float32)),
            global_id=(None if self.global_id is None
                       else as_j(self.global_id, jnp.int32)),
        )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    dt: float = 0.1                        # [ms]
    synapse_model: str = snn.SynapseModel.CURRENT_EXP
    stdp: stdp_mod.STDPParams | None = None
    sweep: str = "flat"                    # backend name: "flat" | "bucketed" | "pallas"
    external_drive: bool = True            # per-neuron Poisson (graph.ext_*)
    record_spikes: bool = True
    # neuron dynamics, resolved through the NeuronModel registry
    # (repro.core.neuron_models, DESIGN.md §12): "lif" | "izhikevich" |
    # "adex" | "poisson" | "<base>+poisson".  The graph's param table and
    # the state must be built for the same model (init_state(neuron_model=)
    # and <model>.make_param_table); mismatches raise at trace time.
    neuron_model: str = "lif"
    # surrogate-gradient mode (DESIGN.md §17): None = inference (the
    # historical bit-exact path); "st[:width]" / "fast_sigmoid[:beta]"
    # swap the spike Heaviside's backward for a pseudo-derivative on the
    # threshold models.  Forward trajectories are bit-identical either
    # way; spike_bits become float {0.0, 1.0} carrying the gradient.
    surrogate: str | None = None
    # external stochastic drive sampler: "poisson" (exact integer events,
    # the historical path) or "diffusion" (Gaussian mean + sqrt-variance
    # reparameterization of the same rate - differentiable w.r.t.
    # graph.ext_rate, the knob parameter inversion fits through).
    external_drive_mode: str = "poisson"


@dataclasses.dataclass
class EngineState:
    neurons: snn.NeuronState
    ring: jax.Array          # (D, n_mirror) float32 spike bits
    weights: jax.Array       # (E,) flat or (NB*EB,) blocked - see marker
    traces: stdp_mod.TraceState
    t: jax.Array             # () int32 step counter
    key: jax.Array           # PRNG key for stochastic drive
    #: () int32: steps whose activity gate saturated its worklist and fell
    #: back to the dense pass (DESIGN.md §13) - always 0 on ungated
    #: backends; the compute twin of ``DistState.wire_overflow``.  None
    #: (legacy states) is normalized to zeros at the step boundary.
    gate_overflow: jax.Array | None = None
    #: decomposition-invariant PRNG key for stochastic MODEL draws: derived
    #: once from the seed (never split per step; per-neuron streams come
    #: from folding in time and GLOBAL neuron id), so the same network
    #: sharded differently draws the same spikes.  None on deterministic
    #: models (zero extra leaves - legacy checkpoints stay compatible).
    drive_key: jax.Array | None = None
    #: static marker: layout of ``weights`` - "flat" or a shape-qualified
    #: blocked tag like "blocked:256x2048" (backends.layout_tag).  Pytree
    #: metadata, so a blocked-resident state can never be silently misread
    #: as flat NOR stepped under different (PB, EB) block shapes (equal
    #: slot totals with different shapes would scramble every edge)
    weights_layout: str = "flat"
    #: static marker: which NeuronModel ``neurons`` was built for
    #: (DESIGN.md §12) - struct-checked against cfg.neuron_model at trace
    #: time so a state can never be stepped under the wrong dynamics
    neuron_model: str = "lif"


jax.tree_util.register_dataclass(
    EngineState,
    data_fields=["neurons", "ring", "weights", "traces", "t", "key",
                 "gate_overflow", "drive_key"],
    meta_fields=["weights_layout", "neuron_model"])

# salt for deriving the shard-invariant drive key from the user seed/key
DRIVE_SALT = 0x5EED


def init_state(graph: ShardGraph, groups, key: jax.Array, *,
               dtype=jnp.float32, sweep: str | None = None,
               neuron_model: str = "lif") -> EngineState:
    """Fresh engine state.  ``sweep`` (a backend name) stores the weights in
    that backend's native layout up front - hand-rolled ``make_step_fn``
    loops then never pay the per-step layout conversion; without it the
    state is flat and ``engine_step``/``run`` convert at the boundary.
    ``neuron_model`` picks the dynamics (DESIGN.md §12): ``groups`` must be
    that model's parameter class and the state carries the model tag."""
    model = neuron_models_mod.get_model(neuron_model)
    neurons = model.init_state(graph.n_local, np.asarray(graph.group_id),
                               groups, dtype=dtype)
    weights = jnp.asarray(graph.weight_init, dtype=dtype)
    weights_layout = "flat"
    if sweep is not None:
        backend = backends_mod.get_backend(sweep)
        if backend.weights_layout != "flat":
            layout = backend.prepare(graph)
            weights = backend.to_native_weights(layout, weights)
            weights_layout = backends_mod.layout_tag(
                layout, backend.weights_layout)
    return EngineState(
        neurons=neurons,
        ring=jnp.zeros((graph.max_delay, graph.n_mirror), dtype=dtype),
        weights=weights,
        traces=stdp_mod.init_traces(graph.n_mirror, graph.n_local, dtype),
        t=jnp.zeros((), jnp.int32),
        key=key,
        gate_overflow=jnp.zeros((), jnp.int32),
        # stochastic models get the shard-invariant drive key (per-neuron
        # streams fold in t and global id); deterministic models carry None
        # so their state tree - and every existing LIF pin - is unchanged
        drive_key=(jax.random.fold_in(key, DRIVE_SALT)
                   if model.stochastic else None),
        weights_layout=weights_layout,
        neuron_model=model.name,
    )


def state_with_weights_layout(state: EngineState, graph: ShardGraph,
                              target: str = "flat", *,
                              backend=None) -> EngineState:
    """Checkpoint/telemetry boundary: re-express ``state.weights`` in
    ``target`` layout ("flat" or "blocked").  The conversion runs through
    ``edge_perm`` exactly once; everything else is untouched."""
    layout = (backend.prepare(graph) if backend is not None
              else backends_mod.layout_of(graph))
    tag = backends_mod.layout_tag(layout, target)
    if state.weights_layout == tag:
        return state
    w = backends_mod.convert_weights(layout, state.weights,
                                     state.weights_layout, tag)
    return dataclasses.replace(state, weights=w, weights_layout=tag)


def synaptic_sweep(graph: ShardGraph, weights: jax.Array, ring: jax.Array,
                   t: jax.Array, *, mode: str = "flat"):
    """Accumulate (input_ex, input_in, arrived[E]) for step ``t`` through the
    ``mode`` backend (see :mod:`repro.core.backends`).

    Flat-facing convenience wrapper: ``weights`` and the returned
    ``arrived`` are in FLAT edge order regardless of the backend's native
    layout (the hot path proper keeps everything native; this entry point
    converts at both ends).  ``arrived[e]`` is 1.0 iff edge ``e``'s pre
    spike arrives exactly now - consumed by both the current accumulation
    and the STDP depression rule.
    """
    backend = backends_mod.get_backend(mode)
    layout = backend.prepare(graph)
    w = backend.to_native_weights(layout, weights)
    ex, inh, arrived = backend.sweep(layout, w, ring, t)
    arrived = backends_mod.flat_edge_values(layout, arrived,
                                            backend.weights_layout)
    return ex, inh, arrived


#: largest expected external events per neuron and step the product
#: method of :func:`poisson_events` takes (the scenarios use 1.5-5)
MAX_DRIVE_EVENTS = 64.0


def drive_threshold(ext_rate, dt: float) -> np.ndarray:
    """``exp(-lam)`` per neuron, ``lam = ext_rate * dt`` the expected
    external events per step, computed on the host in float64.

    :func:`poisson_events` compares products of uniforms against it, so
    no transcendental runs on the device: TPU and CPU ``exp``/``log``
    differ in the last bits (up to ~4e-4 relative for ``log`` on a v5e),
    which flips the occasional event count of ``jax.random.poisson``.
    ``ext_rate`` must be concrete; a traced (fitted) rate takes
    ``external_drive_mode="diffusion"``.
    """
    lam = np.asarray(ext_rate, np.float64) * (dt * 1e-3)
    if lam.size and lam.max() > MAX_DRIVE_EVENTS:
        raise ValueError(
            f"the poisson drive expects at most {MAX_DRIVE_EVENTS} events "
            f"per neuron and step (exp(-lam) must stay a normal float32), "
            f"got {lam.max():g}; use external_drive_mode='diffusion'")
    return np.exp(-lam).astype(np.float32)


def poisson_events(key, thresh, gid):
    """Poisson event counts, one stream per GLOBAL neuron id folded into
    ``key`` (padding rows, id -1, have ``thresh`` 1: no events).

    Knuth's product method: multiply uniforms until the product falls to
    ``thresh = exp(-lam)`` (:func:`drive_threshold`).  Only exactly
    rounded multiplications and comparisons run on the device, so every
    platform counts the same events; keying by global id makes the drive
    independent of how the network is decomposed.
    """
    def count(k, t):
        def body(c):
            n, prod, k = c
            k, sub = jax.random.split(k)
            return n + 1, prod * jax.random.uniform(sub), k

        k, sub = jax.random.split(k)
        n, _, _ = jax.lax.while_loop(lambda c: c[1] > t, body,
                                     (jnp.int32(0), jax.random.uniform(sub),
                                      k))
        return n

    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.maximum(gid, 0))
    return jax.vmap(count)(keys, thresh)


def _poisson_drive(key, graph: ShardGraph, dt: float, dtype):
    """Background Poisson input accumulated into the excitatory channel."""
    gid = (jnp.arange(graph.n_local, dtype=jnp.int32)
           if graph.global_id is None else graph.global_id)
    events = poisson_events(key, drive_threshold(graph.ext_rate, dt), gid)
    return (graph.ext_weight * events).astype(dtype)


def _diffusion_drive(key, graph: ShardGraph, dt: float, dtype):
    """Gaussian diffusion approximation of the Poisson drive: same mean
    and variance (``lam + sqrt(lam) * N(0,1)``), but REPARAMETERIZED - the
    noise is sampled once from the key stream and the event count is a
    smooth function of ``graph.ext_rate``, so reverse-mode AD reaches the
    drive rate (the ``eta`` axis of brunel inversion, DESIGN.md §17).
    Integer-ness of event counts is given up; at the high collapsed rates
    the scenarios use (hundreds of expected events/s/neuron) the
    approximation error is far below the synaptic noise floor."""
    lam = graph.ext_rate * (dt * 1e-3)
    eps = jax.random.normal(key, (graph.n_local,), dtype=jnp.float32)
    events = lam + jnp.sqrt(lam) * eps
    return (graph.ext_weight * events).astype(dtype)


_DRIVES = {"poisson": _poisson_drive, "diffusion": _diffusion_drive}

# The phases of a step, each a ``jax.named_scope`` around its part of
# ``engine_step`` and ``distributed._build_step``'s ``step_local``.  XLA
# keeps the scope in every compiled instruction's ``op_name`` metadata
# (``.../while/body/closed_call/sweep/...``), which is how the chip
# benchmark (``bench/phases.py``) times each phase in a profiler trace.
PHASES = ("sweep", "neuron", "stdp", "exchange")


def phase(name: str):
    """The named scope of one of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; phases: {PHASES}")
    return jax.named_scope(name)


def engine_step(state: EngineState, graph: ShardGraph, table: jax.Array,
                cfg: EngineConfig, *,
                backend: "backends_mod.SweepBackend | None" = None,
                layout: "backends_mod.EdgeLayout | None" = None,
                model: "neuron_models_mod.NeuronModel | None" = None):
    """One dt: sweep -> neuron update -> STDP -> ring write. Returns
    (new_state, spike_bits).

    The work runs in three of the :data:`PHASES` scopes, the names the
    chip benchmark reads: ``sweep`` (the synaptic sweep and the ring
    write), ``neuron`` (the external drive and the neuron update) and
    ``stdp`` (the weight update, the pre-trace ``segment_max``, the trace
    update and the compatibility conversion back).

    ``backend``/``layout``/``model`` may be pre-resolved by callers that
    step in a loop (``run``); otherwise they derive from ``cfg``.
    """
    dtype = state.weights.dtype
    if backend is None:
        backend = backends_mod.get_backend(cfg.sweep)
    if layout is None:
        layout = backend.prepare(graph)
    if model is None:
        model = neuron_models_mod.get_model(cfg.neuron_model)
    if state.neuron_model != model.name:
        raise ValueError(
            f"state was initialized for neuron_model="
            f"{state.neuron_model!r} but cfg selects {model.name!r}; "
            "re-init with init_state(neuron_model=...)")
    model.check_state(state.neurons)

    # weights in the backend's native layout; converting here is the
    # COMPATIBILITY path (state built without ``sweep=``) - it costs one
    # edge gather per direction per step, so steady-state loops should
    # carry native state (init_state(sweep=...) / run() do).  The shared
    # resolver also rejects a blocked state minted under different
    # (PB, EB) block shapes than this backend's layout.
    w_native, native_tag, convert = backends_mod.resolve_runtime_weights(
        backend, layout, state.weights, state.weights_layout)

    # (1) synaptic sweep over owned edges (+ gate-saturation telemetry,
    #     a constant 0 on ungated backends)
    with phase("sweep"):
        input_ex, input_in, arrived, gate_ovf = backend.sweep_with_stats(
            layout, w_native, state.ring, state.t)
    gate_prev = (state.gate_overflow if state.gate_overflow is not None
                 else jnp.zeros((), jnp.int32))

    with phase("neuron"):
        # (2) external stochastic drive
        key, sub = jax.random.split(state.key)
        mkey = None
        if model.stochastic:
            # split ONLY for stochastic models - deterministic dynamics
            # keep the pre-registry key stream (the LIF bit-exactness
            # pin).  When the state carries the shard-invariant drive key,
            # model draws use THAT (per-neuron streams fold in t + global
            # id); the split still happens so the ext-drive stream is
            # unchanged either way.
            sub, mkey = jax.random.split(sub)
            if state.drive_key is not None:
                mkey = state.drive_key
        if cfg.external_drive and graph.ext_rate is not None:
            if cfg.external_drive_mode not in _DRIVES:
                raise ValueError(
                    f"unknown external_drive_mode "
                    f"{cfg.external_drive_mode!r}; available: "
                    f"{sorted(_DRIVES)}")
            drive = _DRIVES[cfg.external_drive_mode]
            input_ex = input_ex + drive(sub, graph, cfg.dt, dtype)

        # (3) neuron dynamics (model-dispatched, DESIGN.md §12)
        neurons = backend.neuron_update(
            layout, state.neurons, table, input_ex, input_in,
            synapse_model=cfg.synapse_model, model=model, key=mkey,
            t=state.t, gid=graph.global_id, surrogate=cfg.surrogate)
    spike_bits = neurons.spike

    # (4) plasticity: weights first (traces exclude this step's spikes:
    #     all-pairs convention), then trace update.
    if cfg.stdp is not None:
        with phase("stdp"):
            weights = backend.stdp_update(layout, w_native, arrived,
                                          spike_bits, state.traces, cfg.stdp)
            # pre trace is indexed by ARRIVAL at the mirror (axonal delay
            # folded in by reading the ring), so increment it with arrivals
            # mapped back to mirrors (through the pre index matching
            # ``arrived``'s layout); post trace with this step's spikes.
            pre_arrived_mirror = jax.ops.segment_max(
                arrived, backend.edge_pre_index(layout),
                num_segments=graph.n_mirror)
            traces = stdp_mod.update_traces(
                state.traces, cfg.stdp, cfg.dt, pre_arrived_mirror,
                spike_bits)
            if convert:  # keep the carried layout stable for scan/loop callers
                weights = backends_mod.convert_weights(
                    layout, weights, native_tag, state.weights_layout)
    else:
        # weights unchanged: carry the state's own vector (never the
        # round-tripped one - that would cost two edge passes and zero the
        # flat padding slots)
        weights, traces = state.weights, state.traces

    # (5) write this step's spikes into the ring at slot t % D.  In the
    # single-shard engine the mirror table is the identity over local
    # neurons; the distributed engine overrides this with exchanged bits.
    with phase("sweep"):
        local_bits = spike_bits.astype(dtype)
        mirror_bits = jnp.take(local_bits, graph.mirror_src_idx)
        ring = jax.lax.dynamic_update_index_in_dim(
            state.ring, mirror_bits, jnp.mod(state.t, graph.max_delay),
            axis=0)

    new_state = EngineState(neurons=neurons, ring=ring, weights=weights,
                            traces=traces, t=state.t + 1, key=key,
                            gate_overflow=gate_prev + gate_ovf,
                            drive_key=state.drive_key,
                            weights_layout=state.weights_layout,
                            neuron_model=state.neuron_model)
    return new_state, spike_bits


@dataclasses.dataclass(frozen=True)
class StepContext:
    """The shared, read-only half of a simulation: ``(graph, table, cfg)``
    plus their pre-resolved backend/layout/model.

    The per-instance half is the :class:`EngineState` pytree alone - the
    separation that makes the state vmappable over an instance axis
    (:func:`make_session_step_fn`): MANY independent instances of the same
    network share ONE context (consts, compiled step) while memory scales
    with per-instance state, not topology (DESIGN.md §16).
    """

    graph: ShardGraph
    table: Any
    cfg: EngineConfig
    backend: Any
    layout: Any
    model: Any

    def step(self, state: EngineState):
        """One dt of one instance: ``(state) -> (state, spike_bits)``."""
        return engine_step(state, self.graph, self.table, self.cfg,
                           backend=self.backend, layout=self.layout,
                           model=self.model)

    def init_state(self, groups, key: jax.Array, *,
                   dtype=jnp.float32) -> EngineState:
        """Fresh per-instance state in this context's NATIVE weight layout
        (no per-step conversion inside vmapped slot batches)."""
        return init_state(self.graph, groups, key, dtype=dtype,
                          sweep=self.cfg.sweep,
                          neuron_model=self.cfg.neuron_model)


def make_step_context(graph: ShardGraph, table: jax.Array,
                      cfg: EngineConfig) -> StepContext:
    """Resolve ``(graph, table, cfg)`` into a reusable :class:`StepContext`
    (backend prepared once, layout device-resident, model looked up)."""
    backend = backends_mod.get_backend(cfg.sweep)
    return StepContext(graph=graph, table=table, cfg=cfg, backend=backend,
                       layout=backend.prepare(graph),
                       model=neuron_models_mod.get_model(cfg.neuron_model))


def make_step_fn(graph: ShardGraph, table: jax.Array, cfg: EngineConfig):
    """Jit-compiled single-step closure (graph/table/cfg baked in)."""
    ctx = make_step_context(graph, table, cfg)
    return jax.jit(ctx.step)


# --------------------------------------------------------------------------
# multi-tenant instance axis (DESIGN.md §16)
# --------------------------------------------------------------------------

def _is_key(x) -> bool:
    return (hasattr(x, "dtype")
            and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key))


def masked_select(active: jax.Array, new, old):
    """Per-slot ``lax.select`` over two same-structure state pytrees:
    slot ``i`` takes ``new``'s leaves where ``active[i]``, else keeps
    ``old``'s bit-for-bit (the serve/engine.py done-mask discipline lifted
    to whole engine states).  Typed PRNG key leaves select through their
    key data."""
    def sel(n, o):
        if _is_key(n):
            nd = jax.random.key_data(n)
            od = jax.random.key_data(o)
            m = active.reshape((-1,) + (1,) * (nd.ndim - 1))
            return jax.random.wrap_key_data(jnp.where(m, nd, od))
        m = active.reshape((-1,) + (1,) * (jnp.ndim(n) - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def stack_states(states: "list[EngineState]") -> EngineState:
    """Stack per-instance states into one slot-batched state (leading
    instance axis on every leaf; static markers must agree)."""
    metas = {(s.weights_layout, s.neuron_model) for s in states}
    if len(metas) != 1:
        raise ValueError(
            f"cannot stack states with mixed static markers {sorted(metas)}"
            " - all slots must share weights_layout and neuron_model")
    return jax.tree.map(lambda *ls: jnp.stack(ls), *states)


def slot_state(batch: EngineState, slot: int) -> EngineState:
    """Extract slot ``slot``'s per-instance state from a slot batch."""
    return jax.tree.map(lambda l: l[slot], batch)


def set_slot_state(batch: EngineState, slot: int,
                   state: EngineState) -> EngineState:
    """Functionally write one instance state into slot ``slot``."""
    def put(b, s):
        if _is_key(b):
            return jax.random.wrap_key_data(
                jax.random.key_data(b).at[slot].set(
                    jax.random.key_data(s)))
        return b.at[slot].set(s)
    return jax.tree.map(put, batch, state)


def make_session_step_fn(graph: ShardGraph, table: jax.Array,
                         cfg: EngineConfig, max_sessions: int):
    """ONE jitted ``vmap(engine_step)`` over a fixed slot batch of
    ``max_sessions`` :class:`EngineState`\\ s - the resident multi-tenant
    step (DESIGN.md §16).

    Returns ``step(batch, active, n_steps=1) -> (batch, bits)`` where
    ``batch`` carries a leading instance axis of size ``max_sessions`` on
    every leaf, ``active`` is a ``(max_sessions,)`` bool mask, and ``bits``
    is ``(n_steps, max_sessions, n_local)`` spike bits (False on inactive
    slots).  Inactive slots are stepped-and-discarded through
    :func:`masked_select`, so their state - ``t``, key stream, weights,
    ``gate_overflow`` telemetry - stays bit-for-bit frozen while active
    slots advance; a session stepped inside any admission pattern computes
    exactly the trajectory of a solo run.  Stochastic models keep per-slot
    key streams (each slot's ``key``/``drive_key`` rides its own lane of
    the vmap); ``gate_overflow``/wire telemetry stays per-slot for the
    same reason.
    """
    if max_sessions < 1:
        raise ValueError(f"max_sessions must be >= 1, got {max_sessions}")
    ctx = make_step_context(graph, table, cfg)
    vstep = jax.vmap(ctx.step)

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def step(batch: EngineState, active: jax.Array, n_steps: int = 1):
        if active.shape != (max_sessions,):
            raise ValueError(
                f"active mask must be ({max_sessions},), got "
                f"{active.shape}")

        def body(b, _):
            new, bits = vstep(b)
            merged = masked_select(active, new, b)
            bits = jnp.where(active[:, None], bits.astype(bool), False)
            return merged, bits

        return jax.lax.scan(body, batch, None, length=n_steps)

    return step, ctx


def normalize_spike_dtype(state: EngineState,
                          cfg: EngineConfig) -> EngineState:
    """Match the state's ``spike`` leaf dtype to the config's spike mode
    before a scan: surrogate mode carries float spike bits (they ARE the
    gradient path), inference mode carries bools.  Values are always
    exactly {0, 1} so the cast is lossless both ways; this is the
    boundary twin of the ``gate_overflow`` normalization."""
    want = state.neurons.v_m.dtype if cfg.surrogate is not None else \
        jnp.bool_
    if state.neurons.spike.dtype == want:
        return state
    neurons = dataclasses.replace(
        state.neurons, spike=state.neurons.spike.astype(want))
    return dataclasses.replace(state, neurons=neurons)


def run(state: EngineState, graph: ShardGraph, table: jax.Array,
        cfg: EngineConfig, n_steps: int):
    """Scan ``n_steps``; returns (final_state, spikes (n_steps, n_local) bool).

    Flat-facing: whatever layout ``state`` arrives in, the scan carries the
    backend's NATIVE weights (one conversion in) and the returned final
    state is FLAT (one conversion out) - the per-step hot path never
    touches ``edge_perm``.
    """
    backend = backends_mod.get_backend(cfg.sweep)
    layout = backend.prepare(graph)
    model = neuron_models_mod.get_model(cfg.neuron_model)
    native_tag = backends_mod.layout_tag(layout, backend.weights_layout)
    if state.gate_overflow is None:   # stable scan carry structure
        state = dataclasses.replace(
            state, gate_overflow=jnp.zeros((), jnp.int32))
    state = normalize_spike_dtype(state, cfg)
    if state.weights_layout != native_tag:
        state = dataclasses.replace(
            state,
            weights=backends_mod.convert_weights(
                layout, state.weights, state.weights_layout, native_tag),
            weights_layout=native_tag)

    def body(s, _):
        s, bits = engine_step(s, graph, table, cfg, backend=backend,
                              layout=layout, model=model)
        return s, (bits if cfg.record_spikes else None)

    final, spikes = jax.lax.scan(body, state, None, length=n_steps)
    if final.weights_layout != "flat":
        final = dataclasses.replace(
            final,
            weights=backends_mod.convert_weights(
                layout, final.weights, final.weights_layout, "flat"),
            weights_layout="flat")
    return final, spikes
