"""Pallas TPU kernel: fused Izhikevich (2003) neuron update.

Two-variable quadratic dynamics

    dv/dt = 0.04 v^2 + 5 v + 140 - u + I
    du/dt = a (b v - u)
    spike: v >= v_peak  ->  v <- c,  u <- u + d

integrated with forward Euler (the model's own convention) on top of the
engine's exactly-decaying exponential synapses: ``I = i_scale * (syn_ex +
syn_in) + i_e`` with the *previous* step's synaptic state (NEST arrival
convention, same as the LIF path).  ``u`` rides the model-generic
``NeuronState.extra["u"]`` slot (DESIGN.md §12).

Pure elementwise over neurons (VPU work), same grid/blocking as
:mod:`repro.kernels.lif_step`: lane-dense neuron blocks, the tiny
per-group parameter table resident in SMEM for every cell.  The parameter
table layout (COL / NCOL below) is owned HERE so the registry's jnp oracle
(:class:`repro.core.neuron_models.IzhikevichModel`) and the kernel share
one table without an import cycle.

Validated bit-exactly against the jnp oracle in interpret mode (identical
op order, DESIGN.md §12 contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lanes import elementwise_call, table_getter

__all__ = ["izhikevich_step_kernel", "COL", "NCOL", "_COLS"]

# Parameter-table row layout (columns of the (G, NCOL) table); dt-derived
# entries are precomputed by IzhikevichModel.make_param_table.
_COLS = (
    "p_ee",       # exp(-dt / tau_syn_ex)
    "p_ii",       # exp(-dt / tau_syn_in)
    "dt",         # Euler step [ms]
    "a",
    "b",
    "c",          # reset potential [mV]
    "d",          # recovery increment on spike
    "v_peak",     # spike cutoff [mV]
    "ref_steps",  # t_ref / dt, rounded (0 = no refractoriness)
    "i_e",        # constant drive (model units)
    "i_scale",    # synaptic-input scale (pA -> model units)
)
COL = {name: i for i, name in enumerate(_COLS)}
NCOL = len(_COLS)


def izhikevich_math(v, u, syn_ex, syn_in, rc, iex, iin, get, spike_fn=None):
    """One Euler dt of the quadratic dynamics; shared op-for-op by the jnp
    oracle and the kernel body so interpret-mode trajectories are
    bit-exact (the fp32 contract of DESIGN.md §12).

    ``spike_fn`` (surrogate mode, DESIGN.md §17; jnp oracle only - the
    kernel never passes it): emit the float surrogate spike on the peak
    distance instead of the bool; forward values identical, reset and
    refractory bookkeeping stay keyed off the exact bool."""
    dt = get("dt")
    se_new = syn_ex * get("p_ee") + iex
    si_new = syn_in * get("p_ii") + iin
    # previous-step synaptic state drives v (arrivals act from t+dt on)
    i_in = get("i_scale") * (syn_ex + syn_in) + get("i_e")
    v_prop = v + dt * (0.04 * v * v + 5.0 * v + 140.0 - u + i_in)
    u_prop = u + dt * get("a") * (get("b") * v - u)
    refractory = rc > 0
    c = get("c")
    v_new = jnp.where(refractory, c, v_prop)
    spike = jnp.logical_and(jnp.logical_not(refractory),
                            v_new >= get("v_peak"))
    spike_out = spike
    if spike_fn is not None:
        spike_out = jnp.where(refractory, jnp.zeros_like(v_new),
                              spike_fn(v_new - get("v_peak")))
    v_new = jnp.where(spike, c, v_new)
    u_new = jnp.where(spike, u_prop + get("d"), u_prop)
    rc_new = jnp.where(spike, get("ref_steps").astype(jnp.int32),
                       jnp.maximum(rc - 1, 0).astype(jnp.int32))
    return v_new, u_new, se_new, si_new, rc_new, spike_out


def _body(tabs, v, u, syn_ex, syn_in, rc, gid, iex, iin, *,
          n_groups: int):
    get = table_getter(tabs[0], gid, n_groups, NCOL, COL)
    return izhikevich_math(v, u, syn_ex, syn_in, rc, iex, iin, get)


@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def izhikevich_step_kernel(v, u, syn_ex, syn_in, ref_count, group_id,
                           input_ex, input_in, table, *, nb: int = 512,
                           interpret: bool = True):
    """All neuron arrays (N,); table (G, NCOL) f32."""
    f32, i32 = jnp.float32, jnp.int32
    return elementwise_call(
        functools.partial(_body, n_groups=table.shape[0]),
        (v, u, syn_ex, syn_in, ref_count, group_id, input_ex, input_in),
        (f32, f32, f32, f32, i32, jnp.bool_), block=nb,
        tables=(table.astype(f32).reshape(-1),), interpret=interpret)
