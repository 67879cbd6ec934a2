"""Pallas TPU kernel: fused AdEx (adaptive exponential IF) neuron update.

Brette & Gerstner 2005 / NEST ``aeif_psc_exp`` semantics:

    C dv/dt = -g_L (v - E_L) + g_L * Delta_T * exp((v - V_T)/Delta_T)
              + I_syn + I_e - w
    tau_w dw/dt = a (v - E_L) - w
    spike: v >= v_peak  ->  v <- v_reset,  w <- w + b,  refractory t_ref

Euler on (v, w) - the exponential term has no exact propagator - over the
engine's exactly-decaying exponential synapses.  The adaptation current
``w`` rides ``NeuronState.extra["w_ad"]`` (DESIGN.md §12).

**fp32 clamping policy** (DESIGN.md §12): the exponential's argument is
clamped to ``EXP_CLAMP`` before ``exp`` - between a threshold crossing and
its reset the membrane can overshoot arbitrarily far in one Euler step,
and an unclamped ``exp((v - V_T)/Delta_T)`` overflows fp32 (inf -> nan on
the next subtraction) long before fp64 would notice.  exp(EXP_CLAMP) keeps
the upstroke steep (the spike is detected the same step) while every
intermediate stays finite in fp32.

Same grid/blocking as :mod:`repro.kernels.lif_step`; the table layout is
owned here so the kernel and the registry's jnp oracle share one table
with no import cycle.  Validated bit-exactly against the oracle in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lanes import elementwise_call, table_getter

__all__ = ["adex_step_kernel", "COL", "NCOL", "_COLS", "EXP_CLAMP"]

#: fp32 safety clamp on (v - V_T)/Delta_T before exp (DESIGN.md §12)
EXP_CLAMP = 10.0

_COLS = (
    "p_ee",       # exp(-dt / tau_syn_ex)
    "p_ii",       # exp(-dt / tau_syn_in)
    "dt_cm",      # dt / c_m
    "g_l",
    "e_l",
    "v_t",        # exponential threshold [mV]
    "delta_t",    # slope factor [mV]
    "v_peak",     # spike cutoff [mV]
    "v_reset",
    "dt_tw",      # dt / tau_w
    "a",          # subthreshold adaptation [nS]
    "b",          # spike-triggered adaptation increment [pA]
    "ref_steps",
    "i_e",
)
COL = {name: i for i, name in enumerate(_COLS)}
NCOL = len(_COLS)


def adex_math(v, w, syn_ex, syn_in, rc, iex, iin, get, spike_fn=None):
    """One Euler dt of the AdEx dynamics; shared op-for-op by the jnp
    oracle and the kernel body (bit-exact interpret contract).

    ``spike_fn`` (surrogate mode, DESIGN.md §17; jnp oracle only - the
    kernel never passes it): emit the float surrogate spike on the peak
    distance; forward values identical, reset bookkeeping stays on the
    exact bool."""
    se_new = syn_ex * get("p_ee") + iex
    si_new = syn_in * get("p_ii") + iin
    g_l, e_l, delta_t = get("g_l"), get("e_l"), get("delta_t")
    # fp32 policy: clamp the exponent argument, never the voltage
    exp_arg = jnp.minimum((v - get("v_t")) / delta_t, EXP_CLAMP)
    i_exp = g_l * delta_t * jnp.exp(exp_arg)
    dv = (-g_l * (v - e_l) + i_exp + syn_ex + syn_in + get("i_e") - w)
    v_prop = v + get("dt_cm") * dv
    w_prop = w + get("dt_tw") * (get("a") * (v - e_l) - w)
    refractory = rc > 0
    v_reset = get("v_reset")
    v_new = jnp.where(refractory, v_reset, v_prop)
    spike = jnp.logical_and(jnp.logical_not(refractory),
                            v_new >= get("v_peak"))
    spike_out = spike
    if spike_fn is not None:
        spike_out = jnp.where(refractory, jnp.zeros_like(v_new),
                              spike_fn(v_new - get("v_peak")))
    v_new = jnp.where(spike, v_reset, v_new)
    w_new = jnp.where(spike, w_prop + get("b"), w_prop)
    rc_new = jnp.where(spike, get("ref_steps").astype(jnp.int32),
                       jnp.maximum(rc - 1, 0).astype(jnp.int32))
    return v_new, w_new, se_new, si_new, rc_new, spike_out


def _body(tabs, v, w_ad, syn_ex, syn_in, rc, gid, iex, iin, *,
          n_groups: int):
    get = table_getter(tabs[0], gid, n_groups, NCOL, COL)
    return adex_math(v, w_ad, syn_ex, syn_in, rc, iex, iin, get)


@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def adex_step_kernel(v, w_ad, syn_ex, syn_in, ref_count, group_id,
                     input_ex, input_in, table, *, nb: int = 512,
                     interpret: bool = True):
    """All neuron arrays (N,); table (G, NCOL) f32."""
    f32, i32 = jnp.float32, jnp.int32
    return elementwise_call(
        functools.partial(_body, n_groups=table.shape[0]),
        (v, w_ad, syn_ex, syn_in, ref_count, group_id, input_ex, input_in),
        (f32, f32, f32, f32, i32, jnp.bool_), block=nb,
        tables=(table.astype(f32).reshape(-1),), interpret=interpret)
