"""Logical-axis sharding rules: parameter/activation/cache -> PartitionSpec.

One place decides how every tensor in the system is laid out on the mesh:

* **batch**   -> ("pod", "data")   (data parallel across pods and rows)
* **fsdp**    -> "data"            (weights fully sharded *within* a pod;
                                    replicated across pods so that the only
                                    cross-pod traffic is the once-per-step
                                    gradient all-reduce - DCI-friendly)
* **tensor**  -> "model"           (TP: heads / ffn-hidden / vocab)
* **expert**  -> "model"           (EP: MoE expert dim)

Parameters are matched by path suffix (first rule wins).  Activations are
annotated inside model code through :func:`shard_act`, which reads a
context-set mesh so the same model source runs un-annotated on a single
device (tests) and fully sharded under the production mesh (launcher sets
:func:`use_mesh`).

Divisibility fallback: any dim whose size does not divide the assigned mesh
axes is replicated instead (e.g. kv_heads=2 on a 16-wide "model" axis) - the
rule engine checks real shapes, so specs are always valid.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = ["use_mesh", "shard_act", "param_specs", "cache_specs",
           "batch_spec", "act_spec", "named_sharding", "current_mesh"]

_CTX: contextvars.ContextVar = contextvars.ContextVar("mesh_ctx",
                                                      default=None)

# (path-regex, logical axes per dim) - first match wins; None = replicated.
# Logical names: "batch", "fsdp", "tensor", "expert", None.
PARAM_RULES: list[tuple[str, tuple[Any, ...]]] = [
    (r"embed/table$",          ("tensor", "fsdp")),
    (r"unembed/w$",            ("fsdp", "tensor")),
    (r"router/w$",             (None, None)),
    # expert tensors are expert-RESIDENT (manual EP dispatch): the expert
    # dim takes as many mesh axes as divide it, nothing else is sharded
    (r"moe/wi_gate$",          ("expert_all", None, None)),
    (r"moe/wi_up$",            ("expert_all", None, None)),
    (r"moe/wo$",               ("expert_all", None, None)),
    (r"(wq|wk|wv|wi|wi_gate|wi_up|cm_k)/w$", ("fsdp", "tensor")),
    (r"(wo|cm_v)/w$",          ("tensor", "fsdp")),
    (r"(wq|wk|wv)/b$",         ("tensor",)),
    (r"wq_a/w$",               ("fsdp", None)),
    (r"wq_b/w$",               (None, "tensor")),
    (r"wkv_a/w$",              ("fsdp", None)),
    (r"wkv_b/w$",              (None, "tensor")),
    (r"in_proj/w$",            ("fsdp", "tensor")),
    (r"out_proj/w$",           ("tensor", "fsdp")),
    (r"x_proj/w$",             ("tensor", None)),
    (r"dt_proj/w$",            (None, "tensor")),
    (r"dt_proj/b$",            ("tensor",)),
    (r"conv_w$",               (None, "tensor")),
    (r"conv_b$",               ("tensor",)),
    (r"a_log$",                ("tensor", None)),
    (r"d_skip$",               ("tensor",)),
    (r"dt_bias_init$",         ("tensor",)),
    (r"(wr|wg)/w$",            ("fsdp", "tensor")),
    (r"(decay_base|bonus_u|gn_scale|gn_bias|mix_base|cm_mix)", (None,)),
    (r"(mix_lora|decay_lora)/(a|b)/w$", (None, None)),
    (r"(norm|scale|bias)",     (None,)),
]

ACT_KINDS = {
    "btd": ("batch", None, None),
    "btv": ("batch", None, "tensor"),
    "bthd": ("batch", None, "tensor", None),
    # MoE dispatch: flat tokens (T, d) stay batch-sharded; expert buffers
    # (E, C, d) shard experts over "model" and capacity over "data"
    "td": ("batch", None),
    "ecd": ("expert", "fsdp", None),
}


class MeshCtx:
    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        names = mesh.axis_names
        self.logical = {
            "batch": tuple(a for a in ("pod", "data") if a in names) or None,
            "fsdp": "data" if "data" in names else None,
            "tensor": "model" if "model" in names else None,
            "expert": "model" if "model" in names else None,
            # expert-resident EP: model-major, falls back to prefixes via
            # the divisibility logic in _resolve
            "expert_all": tuple(a for a in ("model", "data")
                                if a in names) or None,
        }

    def axis_size(self, logical) -> int:
        ax = self.logical.get(logical)
        if ax is None:
            return 1
        if isinstance(ax, tuple):
            return int(np.prod([self.mesh.shape[a] for a in ax]))
        return int(self.mesh.shape[ax])


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for the logical-axis rules.  The rules constrain
    activations and let sharding propagation do the rest, so every mesh
    axis must be ``AxisType.Auto`` (``repro.launch.mesh.auto_mesh``)."""
    if any(t != AxisType.Auto for t in mesh.axis_types):
        raise ValueError(
            f"use_mesh needs AxisType.Auto mesh axes, got {mesh.axis_types}"
            " - build the mesh with repro.launch.mesh.auto_mesh")
    token = _CTX.set(MeshCtx(mesh))
    try:
        with mesh:
            yield
    finally:
        _CTX.reset(token)


def current_mesh() -> MeshCtx | None:
    return _CTX.get()


def _resolve(ctx: MeshCtx, logical_dims, shape) -> P:
    """Logical dims -> mesh axes, dropping non-divisible assignments."""
    out = []
    for dim, logical in enumerate(logical_dims):
        if logical is None or dim >= len(shape):
            out.append(None)
            continue
        ax = ctx.logical.get(logical)
        if ax is None:
            out.append(None)
            continue
        size = ctx.axis_size(logical)
        if shape[dim] % size != 0:
            # try a prefix of the axis tuple, else replicate
            if isinstance(ax, tuple):
                for k in range(len(ax) - 1, 0, -1):
                    sz = int(np.prod([ctx.mesh.shape[a] for a in ax[:k]]))
                    if shape[dim] % sz == 0:
                        out.append(ax[:k])
                        break
                else:
                    out.append(None)
            else:
                out.append(None)
            continue
        out.append(ax)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_act(x, kind: str):
    """Annotate an activation with its logical layout (no-op w/o mesh)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    spec = _resolve(ctx, ACT_KINDS[kind], x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def act_spec(mesh: Mesh, kind: str, shape) -> P:
    return _resolve(MeshCtx(mesh), ACT_KINDS[kind], shape)


def batch_spec(mesh: Mesh) -> P:
    """Spec for (global_batch, ...) input arrays: batch over (pod, data)."""
    ctx = MeshCtx(mesh)
    ax = ctx.logical["batch"]
    return P(ax)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def gather_params_once(params) -> Any:
    """Cast params to bf16 and drop their FSDP ("data") sharding dims -
    forces ONE all-gather per step instead of one per microbatch (§Perf:
    per-micro re-gathers dominated dense-arch collective terms).  No-op
    without a mesh context.  Only sensible when the gathered copy fits
    (callers gate on parameter count)."""
    ctx = _CTX.get()
    if ctx is None:
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 else p, params)
    specs = param_specs(ctx.mesh, params)

    def drop_fsdp(p, sh):
        spec = tuple(None if a in ("data", ("data",)) else a
                     for a in sh.spec)
        out = p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(ctx.mesh, P(*spec)))

    return jax.tree.map(drop_fsdp, params, specs)


def param_specs(mesh: Mesh, params_shape) -> Any:
    """Tree of PartitionSpec for a params (or grads/opt-state) shape tree.

    Stacked-depth leading axes (period scan, per-period lists) are skipped
    automatically: rules address the *trailing* dims; leading extra dims are
    replicated.
    """
    ctx = MeshCtx(mesh)

    def one(path, leaf):
        pstr = _path_str(path)
        shape = leaf.shape
        # expert tensors: specs must match the manual EP dispatch exactly
        # (single source of truth in models.moe_manual)
        m_moe = re.search(r"moe/(wi_gate|wi_up|wo)$", pstr)
        if m_moe and len(shape) >= 3:
            from repro.models.moe_manual import expert_param_spec
            which = "wo" if m_moe.group(1) == "wo" else "wi"
            lead = len(shape) - 3
            n_e = shape[lead]
            return NamedSharding(mesh, expert_param_spec(
                mesh, n_e, which, lead_dims=lead))
        for pat, logical in PARAM_RULES:
            if re.search(pat, pstr):
                nlead = len(shape) - len(logical)
                if nlead < 0:
                    return NamedSharding(mesh, P())
                spec = _resolve(ctx, (None,) * nlead + tuple(logical), shape)
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_specs(mesh: Mesh, cache_shape, *, seq_shard: bool = False) -> Any:
    """KV/state cache shardings.

    Layout policy (per leaf, after stripping stacked-depth leading dims):

    * k/v ``(B, T, Hk, dh)``: batch over ("pod","data"); kv-heads over
      "model" when divisible, otherwise the SEQUENCE dim shards over "model"
      (GQA kv-head counts rarely divide a 16-wide TP axis - sequence-sharded
      KV with XLA's distributed softmax is the fallback that keeps the cache
      per-device bounded).  With ``seq_shard=True`` (the batch=1 ``long_*``
      cells) the sequence additionally shards over "data" (flash-decoding
      layout).
    * MLA ``c_kv/k_rope (B, T, r)``: batch over ("pod","data"), seq over
      "model" (no head dim by construction).
    * SSM / RWKV states: batch + channel/head dims over "model" if divisible.
    """
    ctx = MeshCtx(mesh)

    def seq_axes(shape, t_dim, head_dim_idx=None):
        """Pick (seq_axis, head_axis) respecting divisibility."""
        head_ax = None
        if head_dim_idx is not None:
            spec = _resolve(ctx, ("tensor",), (shape[head_dim_idx],))
            head_ax = spec[0] if len(spec) else None
        seq_ax = []
        if seq_shard and "data" in ctx.mesh.axis_names \
                and shape[t_dim] % ctx.mesh.shape["data"] == 0:
            seq_ax.append("data")
        if head_ax is None and "model" in ctx.mesh.axis_names:
            div = int(np.prod([ctx.mesh.shape[a] for a in seq_ax])) \
                * ctx.mesh.shape["model"]
            if shape[t_dim] % div == 0:
                seq_ax.append("model")
        return (tuple(seq_ax) if seq_ax else None), head_ax

    def one(path, leaf):
        pstr = _path_str(path)
        shape = leaf.shape
        if re.search(r"(^|/)(k|v)$", pstr) and len(shape) >= 4:
            nlead = len(shape) - 4
            s_ax, h_ax = seq_axes(shape, nlead + 1, nlead + 2)
            dims = (None,) * nlead + ("batch", ("raw", s_ax), ("raw", h_ax),
                                      None)
        elif re.search(r"(c_kv|k_rope)$", pstr):
            nlead = len(shape) - 3
            s_ax, _ = seq_axes(shape, nlead + 1)
            dims = (None,) * nlead + ("batch", ("raw", s_ax), None)
        elif re.search(r"(^|/)h$", pstr):      # mamba ssm state
            dims = (None,) * (len(shape) - 3) + ("batch", "tensor", None)
        elif re.search(r"(^|/)s$", pstr):      # rwkv state
            dims = (None,) * (len(shape) - 4) + ("batch", "tensor", None,
                                                 None)
        elif re.search(r"conv$", pstr):
            dims = (None,) * (len(shape) - 3) + ("batch", None, "tensor")
        elif re.search(r"(x_tm|x_cm)$", pstr):
            dims = (None,) * (len(shape) - 3) + ("batch", None, None)
        else:
            dims = (None,) * (len(shape) - 1) + ("batch",)
        spec = _resolve_cache(ctx, dims, shape)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def _resolve_cache(ctx: MeshCtx, dims, shape) -> P:
    out = []
    for i, d in enumerate(dims):
        if d is None:
            out.append(None)
        elif isinstance(d, tuple) and d[0] == "raw":
            out.append(d[1])  # pre-validated raw mesh axes (or None)
        elif d in ("batch", "fsdp", "tensor", "expert"):
            spec = _resolve(ctx, (d,), (shape[i],))
            out.append(spec[0] if len(spec) else None)
        else:  # raw mesh axis name
            if d in ctx.mesh.axis_names and shape[i] % ctx.mesh.shape[d] == 0:
                out.append(d)
            else:
                out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
