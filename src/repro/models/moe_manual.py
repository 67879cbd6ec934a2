"""Manual expert-parallel MoE dispatch (shard_map) - §Perf iteration.

Baseline finding (EXPERIMENTS.md §Perf): XLA's SPMD partitioner handles the
capacity-buffer scatter/gather of :mod:`repro.models.moe` by replicating
token buffers across the mesh - collective terms of 100-3000 s/step for the
MoE train cells.  This module replaces the dispatch with the communication
pattern real EP systems use, which is also the paper's own comm philosophy
("broadcast only the spike IDs"): move ONLY the routed tokens.

Layout:

* expert weights are **expert-resident**: the expert dim shards over as many
  mesh axes as divide E (deepseek 256e -> ("data","model") = 256-way, one
  expert per chip; qwen3 128e / jamba 16e -> ("model",)); no weight
  collectives ever - this replaces FSDP for expert tensors;
* each device routes a disjoint SLICE of its data-shard's tokens (the slice
  index is its position along the non-expert axes), packs per-destination
  capacity buffers, and ``all_to_all``s them to the expert owners;
* experts compute locally; an inverse ``all_to_all`` returns outputs;
  gates+combine are local; an ``all_gather`` along the slicing axes rebuilds
  the activation.

Per-device traffic per MoE layer ~= T_slice*k*d*2 bytes each way - the
information-theoretic floor for EP dispatch (+capacity padding), vs the
baseline's replicated (T*k, d) buffers.

The body is differentiable (a2a/gather have exact transposes), so the same
path serves train/prefill/decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import mlp_apply
from repro.sharding import rules

__all__ = ["expert_axes_for", "moe_apply_manual", "expert_param_spec"]


def expert_axes_for(mesh, n_experts: int) -> tuple[str, ...]:
    """Model-major mesh axes owning the expert dim (must divide E).

    Ordering is significant: the same tuple keys both the parameter
    PartitionSpec and the all_to_all axis_name, so the device flattening
    is consistent by construction.
    """
    names = mesh.axis_names
    if ("data" in names and "model" in names
            and n_experts % (mesh.shape["data"] * mesh.shape["model"]) == 0):
        return ("model", "data")
    if "model" in names and n_experts % mesh.shape["model"] == 0:
        return ("model",)
    if "data" in names and n_experts % mesh.shape["data"] == 0:
        return ("data",)
    return ()


def expert_param_spec(mesh, n_experts: int, which: str = "wi",
                      lead_dims: int = 0) -> P:
    """PartitionSpec for an expert tensor: E over the expert axes,
    everything else replicated (expert-RESIDENT weights).

    NOTE (§Perf, tested-and-rejected alternative): sharding the expert ff
    dim over "data" for few-expert models is INVALID under this dispatch -
    tokens are data-sharded, so a token's ff columns would live with other
    rows' tokens (caught by tests/test_moe_manual.py).  Few-expert models
    (jamba 16e) therefore pay data-axis weight replication; the honest
    alternatives (per-layer weight gathers, or a2a+allgather sub-expert
    residency) are documented in EXPERIMENTS.md §Perf.
    """
    ax = expert_axes_for(mesh, n_experts)
    dims = [None] * (lead_dims + 3)
    if ax:
        dims[lead_dims] = ax if len(ax) > 1 else ax[0]
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def _pack_dispatch(xt, idx, gate, n_exp: int, cap: int, compute_dtype):
    """Owner-sort + capacity-pack one device's token slice.

    xt (T, d); idx (T, k); gate (T, k) ->
      send (E, cap, d), slots (T*k,) flat dest or -1, keep mask, sorted maps
    """
    t, k = idx.shape
    d = xt.shape[-1]
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st_, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(se, length=n_exp)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - jnp.take(starts, se)
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)
    send = jnp.zeros((n_exp, cap, d), compute_dtype)
    vals = xt.astype(compute_dtype)[st_] * keep.astype(compute_dtype)[:, None]
    send = send.at[se, pos_c].add(vals)
    return send, (se, st_, sg, pos_c, keep)


def _ffn(buf, p, mlp_kind, compute_dtype):
    """buf (E_loc, R, d) through local experts (E_loc, d, ff)."""
    wg = p["wi_gate"].astype(compute_dtype)
    wu = p["wi_up"].astype(compute_dtype)
    wo = p["wo"].astype(compute_dtype)
    if mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("erd,edf->erf", buf, wg,
                                   preferred_element_type=jnp.float32)
                        ).astype(compute_dtype) * \
            jnp.einsum("erd,edf->erf", buf, wu,
                       preferred_element_type=jnp.float32
                       ).astype(compute_dtype)
    else:
        h = jax.nn.gelu(jnp.einsum("erd,edf->erf", buf, wg,
                                   preferred_element_type=jnp.float32)
                        ).astype(compute_dtype)
    return jnp.einsum("erf,efd->erd", h, wo,
                      preferred_element_type=jnp.float32
                      ).astype(compute_dtype)


def moe_apply_manual(p, cfg_moe, mlp_kind: str, x, compute_dtype,
                     mesh) -> tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux), dispatched via manual EP a2a."""
    e = cfg_moe
    names = mesh.axis_names
    exp_ax = expert_axes_for(mesh, e.n_experts)
    if not exp_ax:  # mesh cannot own experts; fall back handled by caller
        raise ValueError("no expert axes")
    batch_ax = tuple(a for a in ("pod", "data") if a in names)
    # token slicing happens along "model" - the axis the activation is
    # replicated over (x is batch-sharded over (pod, data)).
    n_exp_dev = int(np.prod([mesh.shape[a] for a in exp_ax]))
    e_loc = e.n_experts // n_exp_dev

    b, s, d = x.shape
    bsz = int(np.prod([mesh.shape[a] for a in batch_ax])) if batch_ax else 1
    batch_sharded = batch_ax and b % bsz == 0
    bspec = P((batch_ax if len(batch_ax) > 1 else batch_ax[0])
              if batch_sharded else None, None, None)
    # token slicing covers every axis the block is replicated over, so no
    # device routes a token twice (decode B=1 replicates over data too)
    slice_axes = tuple(a for a in ("data", "model")
                       if a in names and (a == "model" or not batch_sharded))

    def body(xb, router_w, wg, wu, wo):
        bb, ss, _ = xb.shape
        t_loc = bb * ss
        xt = xb.reshape(t_loc, d)
        # --- slice my share of the replicated tokens ----------------------
        msize = int(np.prod([mesh.shape[a] for a in slice_axes])) \
            if slice_axes else 1
        midx = jnp.zeros((), jnp.int32)
        for a in slice_axes:
            midx = midx * mesh.shape[a] + jax.lax.axis_index(a)
        pad = (-t_loc) % msize
        xt_p = jnp.pad(xt, ((0, pad), (0, 0))) if pad else xt
        t_s = xt_p.shape[0] // msize
        x_slice = jax.lax.dynamic_slice_in_dim(xt_p, midx * t_s, t_s)

        # --- route (fp32) -------------------------------------------------
        logits = jnp.einsum("td,de->te", x_slice.astype(jnp.float32),
                            router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, e.top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e.n_experts,
                                             dtype=jnp.float32), axis=1),
                      axis=0)
        aux_loss = e.n_experts * jnp.sum(me * ce)

        cap = max(4, int(np.ceil(t_s * e.top_k / e.n_experts
                                 * e.capacity_factor)))
        send, (se, st_, sg, pos_c, keep) = _pack_dispatch(
            x_slice, idx, gate, e.n_experts, cap, compute_dtype)

        # --- a2a to expert owners -----------------------------------------
        # send (E, cap, d) -> (D, E_loc, cap, d); swap shard dim with srcs
        send4 = send.reshape(n_exp_dev, e_loc, cap, d)
        recv = jax.lax.all_to_all(send4, exp_ax, split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv (D_src, E_loc, cap, d): my experts' tokens from every source
        buf = recv.transpose(1, 0, 2, 3).reshape(e_loc,
                                                 n_exp_dev * cap, d)
        out = _ffn(buf, {"wi_gate": wg, "wi_up": wu, "wo": wo},
                   mlp_kind, compute_dtype)
        out4 = out.reshape(e_loc, n_exp_dev, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out4, exp_ax, split_axis=0,
                                  concat_axis=0, tiled=True)
        # back (D, E_loc, cap, d) == my send layout, now holding outputs
        out_flat = back.reshape(n_exp_dev * e_loc * cap, d)

        # --- combine ------------------------------------------------------
        y_rows = out_flat[se * cap + pos_c] \
            * (sg.astype(compute_dtype)
               * keep.astype(compute_dtype))[:, None]
        y_slice = jax.ops.segment_sum(y_rows, st_, num_segments=t_s)

        # --- rebuild the full token block along the slicing axes ----------
        if msize > 1:
            y_full = jax.lax.all_gather(y_slice, slice_axes, axis=0,
                                        tiled=True)
        else:
            y_full = y_slice
        y = y_full[:t_loc].reshape(bb, ss, d)
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
        # aux scalars: average over every manual axis group
        aux_loss = jax.lax.pmean(aux_loss, names)
        drop = jax.lax.pmean(drop, names)
        return y, aux_loss, drop

    spec_e = expert_param_spec(mesh, e.n_experts)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(), spec_e, spec_e, spec_e),
        out_specs=(bspec, P(), P()), check_vma=False)
    y, aux_loss, drop = fn(x, p["router"]["w"], p["wi_gate"], p["wi_up"],
                           p["wo"])

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, mlp_kind, compute_dtype)
    return y.astype(x.dtype), {"load_balance_loss": aux_loss,
                               "drop_frac": drop}
