"""Lane-dense tiling shared by the elementwise Pallas kernels.

The TPU compiler accepts a block only when its last two dims are multiples
of (8, 128) or equal the array's own dims.  The elementwise kernels (the
neuron updates and the pl-STDP edge update) therefore view every ``(N,)``
operand as an ``(R, 128)`` slab and walk it in ``(rb, 128)`` blocks:
``rb`` is a multiple of 8, or all ``R`` rows when the slab is one block.
Operands are zero-padded up to whole blocks and results cut back to
``N``, so callers pass any length.

Bool operands travel as int32 (the compiler lowers no bool buffers) and
are converted back on both sides; kernel bodies see bools.  Small tables
(per-group neuron parameters) sit in SMEM and are read as scalars.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "SUBLANES", "elementwise_call", "table_getter"]

LANES = 128
SUBLANES = 8


def _block_rows(rows: int, block: int) -> int:
    """Rows per grid cell for ~``block`` elements: a multiple of 8, or the
    whole slab when that is no larger."""
    rb = -(-max(block, 1) // LANES)
    rb = -(-rb // SUBLANES) * SUBLANES
    return rows if rb >= rows else rb


def elementwise_call(body, inputs, out_dtypes, *, block: int,
                     tables=(), interpret: bool):
    """Run ``body`` over lane-dense blocks of equal-length ``(N,)`` arrays.

    ``body(tables, *values)`` gets the SMEM table refs and one block of
    each input, and returns one array per ``out_dtypes`` entry.  ``block``
    is the number of elements per grid cell (rounded up to whole
    ``(8, 128)`` tiles).  Returns the ``(N,)`` outputs.
    """
    n = inputs[0].shape[0]
    rows = -(-n // LANES)
    rb = _block_rows(rows, block)
    rows_pad = -(-rows // rb) * rb
    pad = rows_pad * LANES - n

    def slab(a):
        a = a.astype(jnp.int32) if a.dtype == jnp.bool_ else a
        return jnp.pad(a, (0, pad)).reshape(rows_pad, LANES)

    in_bool = [a.dtype == jnp.bool_ for a in inputs]
    store = [jnp.int32 if d == jnp.bool_ else d for d in out_dtypes]
    n_tab = len(tables)

    def kernel(*refs):
        tab_refs = refs[:n_tab]
        in_refs = refs[n_tab:n_tab + len(inputs)]
        out_refs = refs[n_tab + len(inputs):]
        vals = [r[...] != 0 if b else r[...]
                for r, b in zip(in_refs, in_bool)]
        outs = body(tab_refs, *vals)
        for r, v in zip(out_refs, outs):
            r[...] = v.astype(r.dtype)

    blk = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(rows_pad // rb,),
        in_specs=[smem] * n_tab + [blk] * len(inputs),
        out_specs=[blk] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((rows_pad, LANES), d)
                   for d in store],
        interpret=interpret,
    )(*tables, *map(slab, inputs))
    return tuple((o.reshape(-1)[:n] != 0) if d == jnp.bool_
                 else o.reshape(-1)[:n]
                 for o, d in zip(outs, out_dtypes))


def table_getter(table_ref, gid, n_groups: int, ncol: int, col: dict):
    """``get(name)`` -> per-neuron parameter ``table[gid, col[name]]`` from
    a flattened ``(G * NCOL,)`` SMEM table.

    A select chain over the (few, static) groups instead of a gather: the
    TPU compiler lowers no general gather inside a kernel, and a select
    copies the table value bit-for-bit.
    """
    def get(name):
        c = col[name]
        val = jnp.full(gid.shape, table_ref[c], jnp.float32)
        for g in range(1, n_groups):
            val = jnp.where(gid == g, table_ref[g * ncol + c], val)
        return val
    return get
