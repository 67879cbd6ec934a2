"""NeuronModel registry: pluggable per-neuron dynamics (DESIGN.md §12).

The indegree sub-graph decomposition is model-agnostic - the race-freedom
and overlap arguments (eq. 14, §III.C) depend only on the edge layout -
yet the engine hardwired one LIF neuron.  This module is the third
registry axis next to the execution backends (§9) and the spike wires
(§10): a :class:`NeuronModel` owns the per-group parameter table, the
per-neuron state struct, and the fused propagate/threshold/reset update,
and registers under a name selectable via ``EngineConfig.neuron_model``.
Both engines and every :class:`~repro.core.backends.SweepBackend` dispatch
through it, so a new model runs on every backend, wire, comm mode and host
layout for free - the CoreNEURON "many mechanisms, one engine" move.

Shipped models:

* ``"lif"``         - the original leaky integrate-and-fire
  (:mod:`repro.core.snn`, exact-integration propagators); the registry
  entry delegates to the exact same code, so trajectories through the
  registry are bit-identical to the pre-registry engine (regression-pinned
  in ``tests/test_neuron_models.py``);
* ``"izhikevich"``  - the 2-variable quadratic model (Izhikevich 2003),
  recovery variable ``u`` in ``NeuronState.extra["u"]``;
* ``"adex"``        - adaptive exponential IF (Brette & Gerstner 2005),
  adaptation current in ``extra["w_ad"]``, exponential clamped for fp32
  safety (``repro.kernels.adex_step.EXP_CLAMP``);
* ``"poisson"``     - a stateless stochastic emitter population: spikes
  are counter-based Bernoulli draws (``jax.random.fold_in(key, t)``), no
  membrane dynamics.  Its spikes ride the ring / mirror tables / wires
  like any neuron's.

Composite names ``"<base>+poisson"`` (e.g. ``"lif+poisson"``) resolve
lazily, like ``"sparse:<rate>"`` wires: the group list may mix the base
model's parameter class with :class:`PoissonParams` entries, and the
emitter groups fire stochastically while the dynamical groups integrate -
a Poisson *input population* inside any network, wired through ordinary
projections instead of the collapsed per-neuron ``ext_rate`` drive.

Contract (DESIGN.md §12): ``make_param_table`` / ``init_vars`` /
``state_struct`` / ``step`` (the jnp oracle) and optionally
``kernel_step`` (the Pallas twin; izhikevich/adex share the oracle's exact
op order so interpret-mode trajectories are bit-exact).  Stochastic models
set ``stochastic=True`` and receive a per-step PRNG ``key`` (+ the step
counter ``t``) from the engine; deterministic models never touch the key
stream, which keeps pre-registry LIF runs bit-identical.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import snn
from repro.diff import surrogate as diff_surrogate_mod
from repro.kernels import adex_step as adex_kernel_mod
from repro.kernels import izhikevich_step as izh_kernel_mod
from repro.kernels.adex_step import EXP_CLAMP
from repro.kernels.lif_step import lif_step_kernel

__all__ = [
    "NeuronModel", "LIFModel", "IzhikevichModel", "AdExModel",
    "PoissonModel", "PoissonDriveModel", "IzhikevichParams", "AdExParams",
    "PoissonParams", "register_model", "get_model", "available_models",
    "EXP_CLAMP",
]


# --------------------------------------------------------------------------
# per-group parameter sets
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IzhikevichParams:
    """Izhikevich 2003 per-group parameters (RS defaults)."""

    a: float = 0.02           # recovery time scale [1/ms]
    b: float = 0.2            # recovery sensitivity
    c: float = -65.0          # reset potential [mV]
    d: float = 8.0            # recovery increment on spike
    v_peak: float = 30.0      # spike cutoff [mV]
    t_ref: float = 0.0        # absolute refractory period [ms] (0 = none)
    tau_syn_ex: float = 5.0   # exc. synaptic time constant [ms]
    tau_syn_in: float = 5.0
    i_e: float = 0.0          # constant drive (model current units)
    i_scale: float = 1.0      # synaptic input scale (pA -> model units)


@dataclasses.dataclass(frozen=True)
class AdExParams:
    """AdEx per-group parameters (Brette & Gerstner 2005 / NEST aeif
    defaults; ``aeif_psc_exp`` current-based synapses)."""

    c_m: float = 281.0        # membrane capacitance [pF]
    g_l: float = 30.0         # leak conductance [nS]
    e_l: float = -70.6        # leak reversal [mV]
    v_t: float = -50.4        # exponential threshold [mV]
    delta_t: float = 2.0      # slope factor [mV]
    v_peak: float = 0.0       # spike detection cutoff [mV]
    v_reset: float = -60.0
    tau_w: float = 144.0      # adaptation time constant [ms]
    a: float = 4.0            # subthreshold adaptation [nS]
    b: float = 80.5           # spike-triggered adaptation [pA]
    t_ref: float = 2.0
    tau_syn_ex: float = 2.0
    tau_syn_in: float = 2.0
    i_e: float = 0.0


@dataclasses.dataclass(frozen=True)
class PoissonParams:
    """A stochastic emitter group (rate in spikes/s per neuron)."""

    rate_hz: float = 10.0


# --------------------------------------------------------------------------
# model interface
# --------------------------------------------------------------------------

class NeuronModel:
    """One neuron dynamics implementation (DESIGN.md §12).

    Subclasses define the per-group parameter class, the parameter-table
    schema, the state struct (common fields + ``extra_fields``), the jnp
    reference ``step`` and optionally a Pallas ``kernel_step`` twin.
    """

    name: str = "?"
    param_cls: type = snn.LIFParams
    #: model-specific per-neuron state variables (NeuronState.extra keys)
    extra_fields: tuple[str, ...] = ()
    #: True iff ``step`` consumes a per-step PRNG key; the engines split
    #: one ONLY then (deterministic models keep the pre-registry key
    #: stream, the bit-exactness anchor of the "lif" regression pin)
    stochastic: bool = False
    #: Pallas twin of ``step`` or None (jnp path serves all backends)
    kernel_step: Callable | None = None
    #: True iff ``step`` accepts ``surrogate=`` (DESIGN.md §17): a
    #: surrogate-gradient spec ("st[:width]" / "fast_sigmoid[:beta]")
    #: that swaps the spike Heaviside's BACKWARD for a pseudo-derivative
    #: while the forward - and the whole membrane trajectory - stays
    #: bit-identical to inference mode.  Threshold models opt in; event
    #: emitters (poisson, composites) have no threshold to differentiate.
    supports_surrogate: bool = False

    # -- build-time -------------------------------------------------------
    def check_groups(self, groups) -> None:
        for i, g in enumerate(groups):
            if not isinstance(g, self.param_cls):
                raise TypeError(
                    f"model {self.name!r} takes {self.param_cls.__name__} "
                    f"groups; group {i} is {type(g).__name__} (pick the "
                    "matching EngineConfig.neuron_model)")

    def make_param_table(self, groups, dt: float,
                         dtype=jnp.float32) -> jax.Array:
        """Precompute the (G, NCOL) per-group table for time step ``dt``."""
        raise NotImplementedError

    def init_vars(self, group_id: np.ndarray, groups) -> dict[str, Any]:
        """Initial per-neuron state arrays (numpy, any ``group_id`` shape):
        keys ``v_m, syn_ex, syn_in, ref_count`` + ``extra_fields``."""
        raise NotImplementedError

    def init_state(self, n: int, group_id, groups, *,
                   dtype=jnp.float32) -> snn.NeuronState:
        gid = np.asarray(group_id, dtype=np.int32)
        v = self.init_vars(gid, groups)
        f = lambda k: jnp.asarray(v[k], dtype=dtype)
        return snn.NeuronState(
            v_m=f("v_m"), syn_ex=f("syn_ex"), syn_in=f("syn_in"),
            ref_count=jnp.asarray(v["ref_count"], dtype=jnp.int32),
            spike=jnp.zeros((n,), dtype=jnp.bool_),
            group_id=jnp.asarray(gid),
            extra={k: f(k) for k in self.extra_fields})

    # -- struct contract --------------------------------------------------
    def state_struct(self, n: int, dtype=jnp.float32) -> dict[str, Any]:
        """The per-neuron state leaves as ShapeDtypeStructs (the §12
        analogue of SpikeWire.payload_struct)."""
        f32 = jax.ShapeDtypeStruct((n,), dtype)
        out = dict(v_m=f32, syn_ex=f32, syn_in=f32,
                   ref_count=jax.ShapeDtypeStruct((n,), jnp.int32),
                   spike=jax.ShapeDtypeStruct((n,), jnp.bool_),
                   group_id=jax.ShapeDtypeStruct((n,), jnp.int32))
        out.update({k: f32 for k in self.extra_fields})
        return out

    def check_state(self, state: snn.NeuronState) -> None:
        """Struct-check a state against this model (clear trace-time error
        instead of silently misreading another model's ``extra``)."""
        have = tuple(sorted(state.extra))
        want = tuple(sorted(self.extra_fields))
        if have != want:
            raise ValueError(
                f"neuron state carries extra fields {have} but model "
                f"{self.name!r} expects {want} - state was built for a "
                "different neuron_model; re-init with init_state("
                f"neuron_model={self.name!r})")
        for k in self.extra_fields:
            if state.extra[k].shape != state.v_m.shape:
                raise ValueError(
                    f"extra field {k!r} has shape {state.extra[k].shape}, "
                    f"expected {state.v_m.shape}")

    # -- run-time ---------------------------------------------------------
    def step(self, state: snn.NeuronState, table, input_ex, input_in, *,
             synapse_model: str = snn.SynapseModel.CURRENT_EXP,
             key=None, t=None, gid=None) -> snn.NeuronState:
        """One dt of dynamics - the jnp oracle every backend can run.

        ``key``/``t`` feed stochastic draws; ``gid`` (GLOBAL neuron ids,
        (n,) int32, -1 on padding rows) keys them per neuron so the same
        network sharded differently draws the same spikes (DESIGN.md §14).
        Deterministic models ignore all three.

        Models with ``supports_surrogate`` additionally accept
        ``surrogate=`` (a spec string, None = inference mode): the
        returned state's ``spike`` leaf becomes the float surrogate spike
        (forward bits unchanged, surrogate VJP) - DESIGN.md §17.
        """
        raise NotImplementedError

    def spike_fn(self, surrogate: str | None):
        """Resolve a surrogate spec into the spike function ``step``
        threads to its threshold op; None in inference mode.  Raises for
        models that never opted in (the contract check both backends run
        before dispatch)."""
        if surrogate is None:
            return None
        if not self.supports_surrogate:
            raise ValueError(
                f"model {self.name!r} does not support surrogate-gradient "
                "mode (no spike threshold to differentiate); use one of "
                "the threshold models (lif / izhikevich / adex)")
        return diff_surrogate_mod.get_surrogate(surrogate)


def _gid_uniform(key, t, gid):
    """Per-neuron U(0,1) draws from counter-style streams keyed by GLOBAL
    neuron id (and step): ``fold_in(fold_in(key, t), gid[i])``.  Because
    the stream depends only on (key, t, global id) - never on shard shape
    or local index - 1-shard and N-shard trajectories of stochastic models
    match bit-exactly (DESIGN.md §14).  Padding rows (gid == -1) draw from
    their own harmless stream."""
    k = key if t is None else jax.random.fold_in(key, t)
    keys = jax.vmap(lambda g: jax.random.fold_in(k, g))(jnp.asarray(gid))
    return jax.vmap(
        lambda kk: jax.random.uniform(kk, (), dtype=jnp.float32))(keys)


def _require_current(model: NeuronModel, synapse_model: str) -> None:
    if synapse_model != snn.SynapseModel.CURRENT_EXP:
        raise ValueError(
            f"model {model.name!r} implements current-based exponential "
            f"synapses only; synapse_model={synapse_model!r} is not "
            "supported (use 'lif' for cond_exp)")


# --------------------------------------------------------------------------
# LIF: delegates to repro.core.snn - bit-identical to the pre-registry path
# --------------------------------------------------------------------------

class LIFModel(NeuronModel):
    """The original LIF neuron; every call delegates to
    :mod:`repro.core.snn` / :mod:`repro.kernels.lif_step` unchanged, so the
    registry detour costs nothing and changes no bit."""

    name = "lif"
    param_cls = snn.LIFParams
    supports_surrogate = True

    def make_param_table(self, groups, dt, dtype=jnp.float32):
        self.check_groups(groups)
        return snn.make_param_table(list(groups), dt, dtype=dtype)

    def init_vars(self, group_id, groups):
        e_l = np.asarray([g.e_l for g in groups], dtype=np.float64)
        z = np.zeros(group_id.shape, dtype=np.float32)
        return dict(v_m=e_l[group_id], syn_ex=z, syn_in=z,
                    ref_count=np.zeros(group_id.shape, dtype=np.int32))

    def step(self, state, table, input_ex, input_in, *,
             synapse_model=snn.SynapseModel.CURRENT_EXP, key=None, t=None,
             gid=None, surrogate=None):
        return snn.lif_step(state, table, input_ex, input_in,
                            synapse_model=synapse_model,
                            spike_fn=self.spike_fn(surrogate))

    def kernel_step(self, state, table, input_ex, input_in, *,
                    synapse_model=snn.SynapseModel.CURRENT_EXP,
                    nb: int = 128, interpret: bool = True,
                    key=None, t=None, gid=None):
        if synapse_model not in (snn.SynapseModel.CURRENT_EXP,
                                 snn.SynapseModel.COND_EXP):
            raise ValueError(f"unknown synapse model {synapse_model!r}")
        cond = synapse_model == snn.SynapseModel.COND_EXP
        f32 = lambda a: a.astype(jnp.float32)
        v, se, si, rc, sp = lif_step_kernel(
            f32(state.v_m), f32(state.syn_ex), f32(state.syn_in),
            state.ref_count, state.group_id,
            f32(input_ex), f32(input_in), table.astype(jnp.float32),
            cond=cond, nb=nb, interpret=interpret)
        dtype = state.v_m.dtype
        return snn.NeuronState(
            v_m=v.astype(dtype), syn_ex=se.astype(dtype),
            syn_in=si.astype(dtype), ref_count=rc,
            spike=sp, group_id=state.group_id, extra=state.extra)


# --------------------------------------------------------------------------
# Izhikevich
# --------------------------------------------------------------------------

class IzhikevichModel(NeuronModel):
    """Izhikevich 2003 quadratic 2-var dynamics; ``u`` in ``extra["u"]``.

    The jnp step and the Pallas kernel share
    :func:`repro.kernels.izhikevich_step.izhikevich_math` op-for-op, so
    interpret-mode trajectories are bit-exact across backends.
    """

    name = "izhikevich"
    param_cls = IzhikevichParams
    extra_fields = ("u",)
    supports_surrogate = True

    def make_param_table(self, groups, dt, dtype=jnp.float32):
        self.check_groups(groups)
        rows = [[
            np.exp(-dt / g.tau_syn_ex),
            np.exp(-dt / g.tau_syn_in),
            dt, g.a, g.b, g.c, g.d, g.v_peak,
            max(1.0, round(g.t_ref / dt)) if g.t_ref > 0 else 0.0,
            g.i_e, g.i_scale,
        ] for g in groups]
        return jnp.asarray(np.asarray(rows), dtype=dtype)

    def init_vars(self, group_id, groups):
        c = np.asarray([g.c for g in groups], dtype=np.float64)
        b = np.asarray([g.b for g in groups], dtype=np.float64)
        v0 = c[group_id]
        z = np.zeros(group_id.shape, dtype=np.float32)
        return dict(v_m=v0, syn_ex=z, syn_in=z,
                    ref_count=np.zeros(group_id.shape, dtype=np.int32),
                    u=b[group_id] * v0)

    def step(self, state, table, input_ex, input_in, *,
             synapse_model=snn.SynapseModel.CURRENT_EXP, key=None, t=None,
             gid=None, surrogate=None):
        _require_current(self, synapse_model)
        gid = state.group_id
        get = lambda name: jnp.take(
            table[:, izh_kernel_mod.COL[name]], gid, axis=0)
        v, u, se, si, rc, sp = izh_kernel_mod.izhikevich_math(
            state.v_m, state.extra["u"], state.syn_ex, state.syn_in,
            state.ref_count, input_ex, input_in, get,
            spike_fn=self.spike_fn(surrogate))
        return snn.NeuronState(v_m=v, syn_ex=se, syn_in=si, ref_count=rc,
                               spike=sp, group_id=gid, extra={"u": u})

    def kernel_step(self, state, table, input_ex, input_in, *,
                    synapse_model=snn.SynapseModel.CURRENT_EXP,
                    nb: int = 128, interpret: bool = True,
                    key=None, t=None, gid=None):
        _require_current(self, synapse_model)
        f32 = lambda a: a.astype(jnp.float32)
        v, u, se, si, rc, sp = izh_kernel_mod.izhikevich_step_kernel(
            f32(state.v_m), f32(state.extra["u"]), f32(state.syn_ex),
            f32(state.syn_in), state.ref_count, state.group_id,
            f32(input_ex), f32(input_in), table.astype(jnp.float32),
            nb=nb, interpret=interpret)
        dtype = state.v_m.dtype
        return snn.NeuronState(
            v_m=v.astype(dtype), syn_ex=se.astype(dtype),
            syn_in=si.astype(dtype), ref_count=rc,
            spike=sp, group_id=state.group_id,
            extra={"u": u.astype(dtype)})


# --------------------------------------------------------------------------
# AdEx
# --------------------------------------------------------------------------

class AdExModel(NeuronModel):
    """Adaptive exponential IF; adaptation current in ``extra["w_ad"]``.

    fp32 policy: the exponential's argument is clamped to ``EXP_CLAMP``
    inside the shared math (:mod:`repro.kernels.adex_step`), so the
    upstroke never overflows fp32 (DESIGN.md §12).
    """

    name = "adex"
    param_cls = AdExParams
    extra_fields = ("w_ad",)
    supports_surrogate = True

    def make_param_table(self, groups, dt, dtype=jnp.float32):
        self.check_groups(groups)
        rows = [[
            np.exp(-dt / g.tau_syn_ex),
            np.exp(-dt / g.tau_syn_in),
            dt / g.c_m, g.g_l, g.e_l, g.v_t, g.delta_t, g.v_peak,
            g.v_reset, dt / g.tau_w, g.a, g.b,
            max(1.0, round(g.t_ref / dt)) if g.t_ref > 0 else 0.0,
            g.i_e,
        ] for g in groups]
        return jnp.asarray(np.asarray(rows), dtype=dtype)

    def init_vars(self, group_id, groups):
        e_l = np.asarray([g.e_l for g in groups], dtype=np.float64)
        z = np.zeros(group_id.shape, dtype=np.float32)
        return dict(v_m=e_l[group_id], syn_ex=z, syn_in=z,
                    ref_count=np.zeros(group_id.shape, dtype=np.int32),
                    w_ad=z)

    def step(self, state, table, input_ex, input_in, *,
             synapse_model=snn.SynapseModel.CURRENT_EXP, key=None, t=None,
             gid=None, surrogate=None):
        _require_current(self, synapse_model)
        gid = state.group_id
        get = lambda name: jnp.take(
            table[:, adex_kernel_mod.COL[name]], gid, axis=0)
        v, w, se, si, rc, sp = adex_kernel_mod.adex_math(
            state.v_m, state.extra["w_ad"], state.syn_ex, state.syn_in,
            state.ref_count, input_ex, input_in, get,
            spike_fn=self.spike_fn(surrogate))
        return snn.NeuronState(v_m=v, syn_ex=se, syn_in=si, ref_count=rc,
                               spike=sp, group_id=gid, extra={"w_ad": w})

    def kernel_step(self, state, table, input_ex, input_in, *,
                    synapse_model=snn.SynapseModel.CURRENT_EXP,
                    nb: int = 128, interpret: bool = True,
                    key=None, t=None, gid=None):
        _require_current(self, synapse_model)
        f32 = lambda a: a.astype(jnp.float32)
        v, w, se, si, rc, sp = adex_kernel_mod.adex_step_kernel(
            f32(state.v_m), f32(state.extra["w_ad"]), f32(state.syn_ex),
            f32(state.syn_in), state.ref_count, state.group_id,
            f32(input_ex), f32(input_in), table.astype(jnp.float32),
            nb=nb, interpret=interpret)
        dtype = state.v_m.dtype
        return snn.NeuronState(
            v_m=v.astype(dtype), syn_ex=se.astype(dtype),
            syn_in=si.astype(dtype), ref_count=rc,
            spike=sp, group_id=state.group_id,
            extra={"w_ad": w.astype(dtype)})


# --------------------------------------------------------------------------
# Poisson emitter population
# --------------------------------------------------------------------------

class PoissonModel(NeuronModel):
    """Stateless stochastic emitter: ``spike ~ Bernoulli(rate * dt)`` via
    counter-based ``jax.random`` (the per-step key folded with ``t``), no
    membrane dynamics, inputs ignored.  Its spikes ride the ring, mirror
    tables and wires like any neuron's, so a pure-poisson population can
    drive any network across shards and hosts.

    No Pallas kernel: the update is a single Bernoulli draw - the jnp path
    serves every backend, which also makes cross-backend trajectories
    trivially bit-identical.
    """

    name = "poisson"
    param_cls = PoissonParams
    stochastic = True

    def make_param_table(self, groups, dt, dtype=jnp.float32):
        self.check_groups(groups)
        rows = [[min(max(g.rate_hz, 0.0) * dt * 1e-3, 1.0)] for g in groups]
        return jnp.asarray(np.asarray(rows), dtype=dtype)

    def init_vars(self, group_id, groups):
        z = np.zeros(group_id.shape, dtype=np.float32)
        return dict(v_m=z, syn_ex=z, syn_in=z,
                    ref_count=np.zeros(group_id.shape, dtype=np.int32))

    def step(self, state, table, input_ex, input_in, *,
             synapse_model=snn.SynapseModel.CURRENT_EXP, key=None, t=None,
             gid=None):
        if key is None:
            raise ValueError(
                f"model {self.name!r} is stochastic: the engine must pass "
                "a per-step PRNG key to neuron_update (key=)")
        p = jnp.take(table[:, 0], state.group_id, axis=0)
        if gid is None:
            # legacy per-shard stream (no global ids available)
            k = key if t is None else jax.random.fold_in(key, t)
            u = jax.random.uniform(k, p.shape, dtype=jnp.float32)
        else:
            u = _gid_uniform(key, t, gid)
        spike = u < p
        return dataclasses.replace(state, spike=spike)


# --------------------------------------------------------------------------
# composite: a dynamical model + poisson emitter groups in ONE network
# --------------------------------------------------------------------------

class PoissonDriveModel(NeuronModel):
    """``"<base>+poisson"``: mixed group lists - base-model groups
    integrate, :class:`PoissonParams` groups emit Bernoulli spikes.

    The table is the base model's with one extra trailing ``p_spike``
    column (0 for dynamical groups); emitter neurons' state is frozen at
    init and only their spike bit is stochastic.  The kernel path runs the
    base kernel then applies the same elementwise overlay as the oracle,
    so the bit-exactness contract carries over.
    """

    def __init__(self, base: NeuronModel):
        if base.stochastic:
            raise ValueError(f"cannot stack poisson onto stochastic base "
                             f"{base.name!r}")
        self.base = base
        self.name = f"{base.name}+poisson"
        self.param_cls = base.param_cls   # + PoissonParams, see _split
        self.extra_fields = base.extra_fields
        self.stochastic = True
        self.kernel_step = (None if base.kernel_step is None
                            else self._kernel_step)

    def _split(self, groups):
        """Substitute emitter groups with base defaults; emit rate row."""
        base_groups, rates = [], []
        for i, g in enumerate(groups):
            if isinstance(g, PoissonParams):
                base_groups.append(self.base.param_cls())
                rates.append(g.rate_hz)
            elif isinstance(g, self.base.param_cls):
                base_groups.append(g)
                rates.append(0.0)
            else:
                raise TypeError(
                    f"model {self.name!r} takes {self.base.param_cls.__name__}"
                    f" or PoissonParams groups; group {i} is "
                    f"{type(g).__name__}")
        return base_groups, rates

    def check_groups(self, groups) -> None:
        self._split(groups)

    def make_param_table(self, groups, dt, dtype=jnp.float32):
        base_groups, rates = self._split(groups)
        base_tbl = self.base.make_param_table(base_groups, dt, dtype=dtype)
        p = np.asarray([min(max(r, 0.0) * dt * 1e-3, 1.0) for r in rates])
        return jnp.concatenate(
            [base_tbl, jnp.asarray(p, dtype=dtype)[:, None]], axis=1)

    def init_vars(self, group_id, groups):
        base_groups, _ = self._split(groups)
        return self.base.init_vars(group_id, base_groups)

    def _overlay(self, state, new, table, key, t, gid=None):
        """Emitter groups: freeze the dynamical update, draw the spike."""
        if key is None:
            raise ValueError(
                f"model {self.name!r} is stochastic: the engine must pass "
                "a per-step PRNG key to neuron_update (key=)")
        p = jnp.take(table[:, -1], state.group_id, axis=0)
        emit = p > 0
        if gid is None:
            k = key if t is None else jax.random.fold_in(key, t)
            u = jax.random.uniform(k, p.shape, dtype=jnp.float32)
        else:
            u = _gid_uniform(key, t, gid)
        keep = lambda old, upd: jnp.where(emit, old, upd)
        return snn.NeuronState(
            v_m=keep(state.v_m, new.v_m),
            syn_ex=keep(state.syn_ex, new.syn_ex),
            syn_in=keep(state.syn_in, new.syn_in),
            ref_count=keep(state.ref_count, new.ref_count),
            spike=jnp.where(emit, u < p, new.spike),
            group_id=state.group_id,
            extra={f: keep(state.extra[f], new.extra[f])
                   for f in self.extra_fields})

    def step(self, state, table, input_ex, input_in, *,
             synapse_model=snn.SynapseModel.CURRENT_EXP, key=None, t=None,
             gid=None):
        new = self.base.step(state, table[:, :-1], input_ex, input_in,
                             synapse_model=synapse_model)
        return self._overlay(state, new, table, key, t, gid)

    def _kernel_step(self, state, table, input_ex, input_in, *,
                     synapse_model=snn.SynapseModel.CURRENT_EXP,
                     nb: int = 128, interpret: bool = True,
                     key=None, t=None, gid=None):
        new = self.base.kernel_step(state, table[:, :-1], input_ex,
                                    input_in, synapse_model=synapse_model,
                                    nb=nb, interpret=interpret)
        return self._overlay(state, new, table, key, t, gid)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, NeuronModel] = {}
# resolved "<base>+poisson" composites live in a SIDE cache so the public
# listing stays the base models - the same move as the "sparse:<rate>"
# wire cache (repro.core.wire), which keeps available_*() registry-stable
_COMPOSITE_CACHE: dict[str, NeuronModel] = {}


def register_model(name: str, model: NeuronModel,
                   *, overwrite: bool = False) -> None:
    """Register a model under an ``EngineConfig.neuron_model`` name."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"neuron model {name!r} already registered")
    _REGISTRY[name] = model


def get_model(name) -> NeuronModel:
    if isinstance(name, NeuronModel):
        return name
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in _COMPOSITE_CACHE:
        return _COMPOSITE_CACHE[name]
    # "<base>+poisson" resolves (and caches) on first use - the same move
    # as "sparse:<rate>" wires and "pallas:auto" (DESIGN.md §10/§9)
    if isinstance(name, str) and name.endswith("+poisson"):
        base = name[:-len("+poisson")]
        if base in _REGISTRY:
            model = PoissonDriveModel(_REGISTRY[base])
            _COMPOSITE_CACHE[name] = model
            return model
    raise ValueError(
        f"unknown neuron model {name!r}; available: "
        f"{sorted(_REGISTRY)}") from None


def available_models() -> tuple[str, ...]:
    """The registered base models (lazily-resolved ``<base>+poisson``
    composites do not appear here - they are derived names)."""
    return tuple(sorted(_REGISTRY))


register_model("lif", LIFModel())
register_model("izhikevich", IzhikevichModel())
register_model("adex", AdExModel())
register_model("poisson", PoissonModel())
