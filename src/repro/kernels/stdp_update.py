"""Pallas TPU kernel: pl-STDP weight update on owner-sorted edges.

The nonlinear per-edge update of the verification case (§IV.A):

    w -= pre_arrived * lam*alpha * w * K_post[post]
    w += post_spiked * lam * w0^(1-mu) * w^mu * K_pre[pre]

Race-freedom is inherited from the indegree layout: each edge belongs to
one post-block owner, and the only write is to the edge's own weight.  The
three per-edge lookups (``K_pre[pre]``, ``K_post[post]``, ``spike[post]``)
are XLA gathers ahead of the kernel - the TPU compiler lowers no general
gather inside a kernel - so the kernel itself is a pure elementwise pass
over lane-dense edge blocks (:mod:`repro.kernels.lanes`).  The power
``w^mu`` runs as exp(mu*log(w)) on the VPU (transcendental), masked on
padding edges.

Two edge layouts are served (DESIGN.md §9):

* flat owner-sorted ``(E,)`` arrays with absolute ``post_idx``;
* the post-block ELL layout ``(NB, EB)`` flattened to slot order, with
  **block-relative** post rows (``pb`` given): slot row ``i`` belongs to
  post rows ``[i*pb, (i+1)*pb)``, so the absolute post index is
  ``i*pb + post_rel`` - the blocked-resident hot path consumes the sweep's
  arrivals and weights without any layout conversion.

Validated against :func:`repro.core.stdp.stdp_edge_update` in interpret
mode, including the clip and the non-plastic passthrough.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lanes import elementwise_call

__all__ = ["stdp_update_kernel", "stdp_update_worklist", "DEFAULT_EB"]

DEFAULT_EB = 2048
#: edges per grid cell of the elementwise pass
_BLOCK = 64 * 1024


def _body(_, w, plastic, arrived, k_pre, k_post, post_sp, *, lam, alpha,
          mu, w0, wmin, wmax):
    w1 = w - arrived * (lam * alpha) * w * k_post
    w_safe = jnp.maximum(w1, 1e-12)
    pot = lam * (w0 ** (1.0 - mu)) * jnp.exp(mu * jnp.log(w_safe)) * k_pre
    w2 = jnp.clip(w1 + post_sp * pot, wmin, wmax)
    return (jnp.where(plastic, w2, w),)


def _update(weights, pre, post, plastic, arrived, post_spike, k_pre,
            k_post, params, interpret):
    """Gather the per-edge traces/spikes (XLA), then the elementwise
    kernel.  Out-of-range indices (worklist padding) clamp; their rows are
    dropped by the caller."""
    lam, alpha, mu, w0, wmin, wmax = params
    take = lambda a, i: jnp.take(a, i, axis=0, mode="clip")
    (new_w,) = elementwise_call(
        functools.partial(_body, lam=lam, alpha=alpha, mu=mu, w0=w0,
                          wmin=wmin, wmax=wmax),
        (weights, plastic, arrived, take(k_pre, pre), take(k_post, post),
         take(post_spike, post)),
        (jnp.float32,), block=_BLOCK, interpret=interpret)
    return new_w


@functools.partial(jax.jit, static_argnames=("eb", "interpret", "params",
                                             "pb"))
def stdp_update_kernel(weights, pre_idx, post_idx, plastic, arrived,
                       post_spike, k_pre, k_post, *, params,
                       eb: int = DEFAULT_EB, interpret: bool = True,
                       pb: int = 0):
    """weights/pre/post/plastic/arrived: (E,) owner-sorted;
    post_spike (n_local,) f32; traces k_pre (M,), k_post (n_local,).
    ``params`` is a hashable tuple (lam, alpha, mu, w0, wmin, wmax).

    With ``pb > 0`` the edge arrays are the blocked ELL layout flattened to
    ``(NB*EB,)`` slot order: ``post_idx`` holds block-RELATIVE rows and
    ``eb`` must be the layout's per-block edge count.  The returned weights
    stay in the same slot order.
    """
    if pb:
        e = weights.shape[0]
        assert e % eb == 0, (e, eb)
        blk = jnp.arange(e // eb, dtype=jnp.int32)[:, None] * pb
        post_idx = (blk + post_idx.reshape(e // eb, eb)).reshape(e)
    return _update(weights, pre_idx, post_idx, plastic, arrived,
                   post_spike, k_pre, k_post, params, interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "params", "pb"))
def stdp_update_worklist(weights, pre_idx, post_rel, plastic, arrived,
                         worklist, post_spike, k_pre, k_post, *, params,
                         pb: int, interpret: bool = True):
    """pl-STDP over a compacted worklist of post blocks.

    ``weights``/``pre_idx``/``post_rel``/``plastic``/``arrived`` are
    (G, EB) ELL arrays already compacted through ``worklist`` (G = the
    gate's fixed capacity); ``worklist`` is (G,) int32 absolute post-block
    ids (entries ``>= NB`` mark padding slots - their rows compute on
    clamped gathers and are dropped by the caller's scatter).  Returns the
    updated (G, EB) weights in the same compacted order.
    """
    g, eb = weights.shape
    post = (worklist[:, None] * pb + post_rel).reshape(-1)
    flat = lambda a: a.reshape(-1)
    return _update(flat(weights), flat(pre_idx), post, flat(plastic),
                   flat(arrived), post_spike, k_pre, k_post, params,
                   interpret).reshape(g, eb)
