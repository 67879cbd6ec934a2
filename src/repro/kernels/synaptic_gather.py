"""Pallas TPU kernel: delay-bucketed, indegree-owned synaptic accumulation.

This is the paper's hotspot (synaptic interactions on edges, §III.B) adapted
to the TPU memory hierarchy (DESIGN.md §2):

* the grid iterates over **post-neuron row blocks** - the Pallas analogue of
  CORTEX's thread ownership.  Grid cell ``(i, k)`` may write ONLY output
  rows ``[i*PB, (i+1)*PB)``; by eq. 14 those rows' edges are disjoint from
  every other block's, so the kernel is race-free *structurally* - no
  mutex, no atomic, no scatter;
* edges arrive pre-sorted by (post_block, delay, post) and padded to a
  uniform ``EB`` per block (ELL-of-blocks), the Fig. 12 layout; the second
  grid axis walks a block's ``EB`` edges in lane-dense chunks and
  accumulates into the block's resident output;
* the per-edge arrivals ``ring[(t - delay) mod D, pre]`` are one XLA
  gather over the flattened ring ahead of the kernel
  (:func:`blocked_arrivals`): the TPU compiler lowers no general gather
  inside a kernel, and the ring of a large shard does not fit VMEM;
* the per-block reduction is a **one-hot matmul** on the MXU instead of a
  serial scatter - the TPU-native replacement for the CPU's owner-thread
  loop.  One ``(2, C) x (C, PB)`` product yields both channels: row ``r``
  of the left operand carries the contributions of channel ``r``;
* with ``emit_arrivals=True`` the arrivals are returned as well - the same
  gather feeds both the MXU reduction and the STDP depression rule, so
  the plasticity path pays no second edge-sized ring gather (DESIGN.md §9:
  the single edge pass of the hot path);
* with ``fresh`` (a (M,) bitmap of spikes fired at ``t-1`` that are NOT yet
  in the ring) the delay==1 arrivals are read from ``fresh`` instead of the
  ring - the paper's §III.C overlap schedule folded into the one dispatch:
  the ring write for slot ``t-1`` becomes independent of the sweep and the
  exchange collective only gates the delay-1 term.

VMEM per grid cell (the model ``repro.core.autotune`` sizes (PB, EB)
against)::

    edge chunks  4*C*4 x2    (post_rel, w, arrived, channel; double-buffered)
    onehot       C*PB*4
    output       2*PB*4

with ``C`` (<= 2048) the largest lane multiple dividing ``EB``.

Validated against :func:`repro.kernels.ref.synaptic_gather_ref` in
``interpret=True`` mode on the CPU; the TPU compile is checked in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["synaptic_gather", "blocked_reduce_sweep", "blocked_arrivals",
           "DEFAULT_EB", "DEFAULT_PB", "MAX_CHUNK"]

DEFAULT_EB = 2048   # edges per post-block (padded)
DEFAULT_PB = 256    # post neurons per block
MAX_CHUNK = 2048    # edges per reduction grid step

_LANES = 128


def _chunk(eb: int) -> int:
    """Largest multiple of 128 that divides ``eb`` and is <= MAX_CHUNK (the
    whole row when ``eb`` is not a lane multiple)."""
    if eb % _LANES:
        return eb
    q = eb // _LANES
    return _LANES * max(d for d in range(1, MAX_CHUNK // _LANES + 1)
                        if q % d == 0)


def blocked_arrivals(pre_idx, delay, ring, t, *, max_delay: int,
                     fresh=None):
    """(NB, EB) f32 per-edge arrivals: ``ring[(t - delay) mod D, pre]``,
    delay==1 edges reading ``fresh`` when given, padding (delay 0) masked.
    One flat XLA gather; bit-identical to the flat backend's."""
    d, m = ring.shape
    t = jnp.asarray(t, jnp.int32)
    row = jnp.mod(t - delay, max_delay)
    arrived = jnp.take(ring.astype(jnp.float32).reshape(-1),
                       row * m + pre_idx, axis=0)
    if fresh is not None:
        fresh_arr = jnp.take(fresh.astype(jnp.float32).reshape(-1),
                             pre_idx, axis=0)
        arrived = jnp.where(delay == 1, fresh_arr, arrived)
    return arrived * (delay > 0).astype(jnp.float32)


def _reduce_kernel(post_ref, w_ref, arr_ref, chan_ref, out_ref, *, pb: int):
    """One ``C``-edge chunk of one post block into the block's (2, PB)
    accumulator (row 0 excitatory, row 1 inhibitory)."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    post = post_ref[...]                      # (1, C) int32 in [0, PB)
    contrib = w_ref[...] * arr_ref[...]       # (1, C) f32
    chan = chan_ref[...]                      # (1, C) int32
    c = post.shape[1]
    lhs = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (2, c), 0) == chan,
                    contrib, 0.0)             # (2, C)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (pb, c), 0) == post
              ).astype(jnp.float32)           # (PB, C)
    out_ref[...] += jax.lax.dot_general(
        lhs, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("pb", "interpret"))
def blocked_reduce_sweep(post_rel, weight, arrived, channel, *,
                         pb: int = DEFAULT_PB, interpret: bool = True):
    """Arrivals-consuming sweep reduction: (G, EB) blocks -> (G, PB) x 2.

    ``G`` is whatever leading dimension the caller dispatches - the full
    ``NB`` for the dense pass, or the gate's fixed worklist capacity with
    every input compacted through the worklist (two-pass compact-then-
    sweep, DESIGN.md §13).  Outputs stay (G, PB); the gated caller scatters
    worklist rows back onto the zero-initialized (NB, PB) accumulators
    (dead blocks keep their zeros).  Each row's result depends only on
    that row's inputs, which is what makes the gated pass bit-identical to
    the dense one.
    """
    g, eb = post_rel.shape
    c = _chunk(eb)
    row = lambda a: a.reshape(g, 1, eb)
    edge_spec = pl.BlockSpec((None, 1, c), lambda i, k: (i, 0, k))
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, pb=pb),
        grid=(g, eb // c),
        in_specs=[edge_spec] * 4,
        out_specs=pl.BlockSpec((None, 2, pb), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 2, pb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row(post_rel), row(weight.astype(jnp.float32)),
      row(arrived.astype(jnp.float32)), row(channel))
    return out[:, 0], out[:, 1]


@functools.partial(jax.jit, static_argnames=("max_delay", "pb", "interpret",
                                             "emit_arrivals"))
def synaptic_gather(pre_idx, post_rel, weight, delay, channel, ring, t, *,
                    max_delay: int, pb: int = DEFAULT_PB,
                    interpret: bool = True, emit_arrivals: bool = False,
                    fresh=None):
    """Blocked edge arrays (NB, EB) -> (i_ex, i_in) each (NB*PB,).

    Args mirror the blocked layout of :class:`repro.core.layout.BlockedGraph`.
    ``ring`` is (D, M) f32; ``t`` a scalar int32 array.

    ``emit_arrivals=True`` appends the per-edge arrival bits in blocked
    (NB, EB) order to the result: ``(i_ex, i_in, arrived)``.  ``fresh``
    (optional (M,) f32) supplies the not-yet-written spikes of step ``t-1``
    for delay==1 edges (overlap dispatch).
    """
    nb = pre_idx.shape[0]
    assert ring.shape[0] == max_delay
    arrived = blocked_arrivals(pre_idx, delay, ring, t, max_delay=max_delay,
                               fresh=fresh)
    ex, inh = blocked_reduce_sweep(post_rel, weight, arrived, channel,
                                   pb=pb, interpret=interpret)
    ex, inh = ex.reshape(nb * pb), inh.reshape(nb * pb)
    if emit_arrivals:
        return ex, inh, arrived
    return ex, inh
