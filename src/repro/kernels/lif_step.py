"""Pallas TPU kernel: fused LIF neuron update (exact-integration propagators).

Pure elementwise over neurons (VPU work): one pass reads the neuron state
block plus the per-group propagator table (tiny, resident in SMEM for every
grid cell) and writes the propagated state + spike bits.  Fusing the
propagate / threshold / reset / refractory chain into one kernel removes
five HBM round-trips of the unfused XLA elementwise chain - this mirrors the
paper's "neural dynamics" stage (Fig. 6e) on TPU.

Grid: 1-D over lane-dense ``(rb, 128)`` blocks of about ``nb`` neurons
(:mod:`repro.kernels.lanes`).  Validated against
:func:`repro.core.snn.lif_step` (the jnp oracle) in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.snn import COL, NCOL
from repro.kernels.lanes import elementwise_call, table_getter

__all__ = ["lif_step_kernel", "DEFAULT_NB"]

DEFAULT_NB = 512


def _body(tabs, v, syn_ex, syn_in, rc, gid, iex, iin, *, cond: bool,
          n_groups: int):
    get = table_getter(tabs[0], gid, n_groups, NCOL, COL)
    p_vv, p_ee, p_ii = get("p_vv"), get("p_ee"), get("p_ii")
    v_th, v_reset = get("v_th"), get("v_reset")
    ref_steps = get("ref_steps").astype(jnp.int32)

    se_new = syn_ex * p_ee + iex
    si_new = syn_in * p_ii + iin

    if cond:
        i_cond = syn_ex * (get("e_ex") - v) - syn_in * (v - get("e_in"))
        v_prop = v * p_vv + get("p_vconst") + i_cond * get("inv_cm_dt")
    else:
        v_prop = (v * p_vv + syn_ex * get("p_ve") + syn_in * get("p_vi")
                  + get("p_vconst"))

    refractory = rc > 0
    v_new = jnp.where(refractory, v_reset, v_prop)
    spike = jnp.logical_and(jnp.logical_not(refractory), v_new >= v_th)
    v_new = jnp.where(spike, v_reset, v_new)
    rc_new = jnp.where(spike, ref_steps,
                       jnp.maximum(rc - 1, 0).astype(jnp.int32))
    return v_new, se_new, si_new, rc_new, spike


@functools.partial(jax.jit, static_argnames=("cond", "nb", "interpret"))
def lif_step_kernel(v, syn_ex, syn_in, ref_count, group_id, input_ex,
                    input_in, table, *, cond: bool = False,
                    nb: int = DEFAULT_NB, interpret: bool = True):
    """All neuron arrays (N,); table (G, NCOL) f32.  Returns
    ``(v, syn_ex, syn_in, ref_count, spike)``."""
    f32, i32 = jnp.float32, jnp.int32
    return elementwise_call(
        functools.partial(_body, cond=cond, n_groups=table.shape[0]),
        (v, syn_ex, syn_in, ref_count, group_id, input_ex, input_in),
        (f32, f32, f32, i32, jnp.bool_), block=nb,
        tables=(table.astype(f32).reshape(-1),), interpret=interpret)
