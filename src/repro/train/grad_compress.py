"""Gradient compression: int8 error-feedback for the cross-pod all-reduce.

At multi-pod scale the expensive hop is the pod axis (DCI, not ICI): the
per-step gradient all-reduce across pods moves |params| x 4 bytes.  With
int8 + per-tensor scales that drops ~4x; error feedback (Seide et al.)
carries the quantization residual into the next step so convergence is
preserved.

Two layers:

* pure tensor ops (:func:`quantize` / :func:`dequantize` /
  :func:`ef_compress_step`) - unit-testable, mesh-free;
* :func:`make_cross_pod_reduce` - a ``shard_map`` over the "pod" axis that
  all-gathers int8 payloads + fp32 scales and sums dequantized, used as the
  ``grad_transform`` hook of :func:`repro.train.loop.make_train_step` when
  ``TrainConfig.grad_compress == "int8_ef"``.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["quantize", "dequantize", "ef_compress_step",
           "make_cross_pod_reduce", "init_error_state"]


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp -> (int8 payload, fp32 scale). Symmetric per-tensor."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def init_error_state(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_like)


def ef_compress_step(g: jax.Array, err: jax.Array):
    """One error-feedback round on a single tensor.

    Returns (payload int8, scale, new_err) where dequant(payload)*scale is
    what the wire carries and new_err is the residual to re-inject next
    step.
    """
    target = g.astype(jnp.float32) + err
    q, scale = quantize(target)
    sent = dequantize(q, scale)
    return q, scale, target - sent


def make_cross_pod_reduce(mesh: Mesh, *, compress: bool = True):
    """Returns grads_tree -> grads_tree averaging over the "pod" axis.

    Without "pod" in the mesh this is the identity.  With compression each
    pod quantizes its local gradient (plus carried error), all-gathers the
    int8 payloads + scales over the pod axis, and sums dequantized.  The
    error state is carried in a closure-free functional style: the caller
    keeps ``err_tree`` and passes it in; we return (reduced, new_err).
    """
    if "pod" not in mesh.axis_names:
        def identity(grads, err_tree=None):
            return grads, err_tree
        return identity

    other_axes = tuple(a for a in mesh.axis_names if a != "pod")

    def reduce_leaf(g, err):
        def body(g_shard, e_shard):
            if not compress:
                return jax.lax.pmean(g_shard, "pod"), e_shard
            q, scale, new_err = ef_compress_step(g_shard, e_shard)
            qs = jax.lax.all_gather(q, "pod")          # (P, ...)
            ss = jax.lax.all_gather(scale, "pod")      # (P,)
            summed = jnp.tensordot(
                ss, qs.astype(jnp.float32), axes=([0], [0]))
            return (summed / qs.shape[0]).astype(g_shard.dtype), new_err

        # grads are already sharded over (data, model); shard_map manual
        # only over "pod", auto over the rest.
        spec = P()  # per-pod replica view of the (data,model)-sharded leaf
        f = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                          out_specs=(spec, spec), axis_names={"pod"},
                          check_vma=False)
        return f(g, err)

    def reduce_tree(grads, err_tree):
        pairs = jax.tree.map(reduce_leaf, grads, err_tree)
        reduced = jax.tree.map(lambda p: p[0], pairs,
                               is_leaf=lambda x: isinstance(x, tuple))
        new_err = jax.tree.map(lambda p: p[1], pairs,
                               is_leaf=lambda x: isinstance(x, tuple))
        return reduced, new_err

    return reduce_tree
