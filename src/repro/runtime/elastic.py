"""Elastic re-meshing: pick a new mesh for the surviving device set.

When a pod row (or whole pod) is lost, training resumes on fewer devices:
the checkpoint is mesh-agnostic (full-value leaves), so the only decision
is the new mesh shape.  Policy: keep the tensor-parallel width fixed when
possible (TP width is baked into kernel-level efficiency and cache
layouts) and shrink the (pod x data) rows - matching how real fleets
degrade: lose rows, keep the within-row topology.

For the SNN engine the same plan re-runs the two-level decomposition for
the new row count - Area-Processes Mapping is row-granular by design, so a
row loss re-partitions areas without touching the multisection width.
:func:`shrink_remap_state` is that promise as code: it takes a full
host-side state snapshot written under ONE decomposition and re-expresses
it under ANOTHER (fewer rows), per-neuron state gathered to global order
and re-scattered, the delay ring rebuilt per-shard from the global ring
via the new mirror tables, and the per-shard PRNG streams re-derived for
the new shard count and advanced to the checkpoint step.  Bit-exactness
across the shrink requires the decomposition-invariance contract
(procedural connectivity, invariant drive, no STDP - DESIGN.md §15).

This module stays importable without jax (the gang launcher is
deliberately jax-free); jax is imported lazily where needed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["ElasticPlan", "plan_mesh", "shrink_remap_state"]


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]
    n_devices: int
    dropped: int

    def make_mesh(self):
        import jax
        return jax.make_mesh(self.shape, self.axes)


def plan_mesh(available_devices: int, *, model_width: int = 16,
              prefer_pods: bool = True) -> ElasticPlan:
    """Largest mesh (rows x model_width) <= available, rows maximal."""
    if available_devices < model_width:
        # degrade TP width as last resort (halving keeps divisibility)
        width = model_width
        while width > 1 and available_devices < width:
            width //= 2
        model_width = max(width, 1)
    rows = available_devices // model_width
    if rows == 0:
        raise ValueError("no usable devices")
    used = rows * model_width
    if prefer_pods and rows % 2 == 0 and rows >= 4:
        shape = (2, rows // 2, model_width)
        axes = ("pod", "data", "model")
    else:
        shape = (rows, model_width)
        axes = ("data", "model")
    return ElasticPlan(shape=shape, axes=axes, n_devices=used,
                       dropped=available_devices - used)


def shrink_remap_state(spec, seed: int, host: dict, *, step: int,
                       old_n_rows: int, old_row_width: int,
                       new_dec, new_net, groups,
                       sweep: str | None = None,
                       neuron_model: str = "lif",
                       stdp_active: bool = False):
    """Re-express a checkpointed DistState snapshot on a NEW decomposition.

    ``host`` is the full host-side field dict written by
    :func:`repro.core.multihost.snapshot_host_state` under the
    ``(old_n_rows, old_row_width)`` decomposition; ``new_dec``/``new_net``
    describe the surviving topology (``repro.core.distributed.
    mesh_decompose`` + ``prepare_stacked_local``).  Returns
    ``(fields, carried)``:

    * ``fields`` - host-side DistState data fields for THIS process's new
      rows (``new_net.local_slice``), ready for
      ``repro.core.multihost.state_from_fields``;
    * ``carried`` - overflow totals accumulated before the shrink (the
      per-shard counters cannot be re-scattered across a different shard
      count, so they restart at zero and the totals ride the telemetry).

    Topology and initial weights regenerate procedurally from
    ``spec``+``seed`` (decomposition-invariant per edge); plastic weights
    and STDP traces are per-EDGE-SET state that has no decomposition-
    independent global form, so shrink-restart requires STDP off.
    """
    import jax.numpy as jnp

    from repro.core import builder as builder_mod
    from repro.core import distributed as dist

    if stdp_active:
        raise ValueError(
            "elastic shrink-restart needs stdp disabled: plastic weights "
            "and traces live per edge set, which changes with the "
            "decomposition - run with --no-stdp (same-topology restarts "
            "restore plastic state exactly)")
    if spec.connectivity != "procedural":
        raise ValueError(
            "elastic shrink-restart needs connectivity='procedural' - the "
            "new processes must regenerate their own rows' topology from "
            "spec+seed (network_metadata), not reload a materialized one")

    old_dec = dist.mesh_decompose(spec, old_n_rows, old_row_width)
    li_old = old_dec.local_index()
    N = old_dec.n_neurons
    lo, hi = ((0, new_net.n_shards) if new_net.local_slice is None
              else new_net.local_slice)
    parts_new = [new_dec.parts[s] for s in range(lo, hi)]
    mirror_new = [
        builder_mod.procedural_shard_raw(spec, new_dec, s,
                                         dims_only=True)["mirror_gids"]
        for s in range(lo, hi)]

    # fresh state on the NEW topology: regenerated weights/layout, fresh
    # key, model aux structure
    fresh = dist.init_stacked_state(new_net, list(groups), seed=seed,
                                    sweep=sweep, neuron_model=neuron_model)
    fields = {}
    for f in dataclasses.fields(fresh):
        if f.name in ("weights_layout", "neuron_model"):
            continue
        v = getattr(fresh, f.name)
        if isinstance(v, dict):
            fields[f.name] = {k: np.array(a) for k, a in v.items()}
        elif v is None:
            fields[f.name] = None
        else:
            fields[f.name] = np.array(v)

    def to_global(a):
        """(S_old, n_local_old_pad, ...) -> (N, ...) per-neuron gather."""
        return np.asarray(a)[old_dec.owner, li_old]

    def scatter(name, global_vals, tgt):
        for i, part in enumerate(parts_new):
            tgt[i, :part.size] = global_vals[part]

    per_neuron = ["v_m", "syn_ex", "syn_in", "ref_count", "k_post",
                  "prev_bits"]
    for name in per_neuron:
        scatter(name, to_global(host[name]), fields[name])
    for k, tgt in fields["aux"].items():
        scatter(f"aux.{k}", to_global(host["aux"][k]), tgt)

    # delay ring: mirror rows hold the PRE neuron's delayed spike bits, so
    # the global (D, N) ring reconstructed from each old shard's OWNED
    # section re-gathers through the new mirror tables bit-exactly
    ring_old = np.asarray(host["ring"])
    D = ring_old.shape[1]
    ring_g = np.zeros((D, N), ring_old.dtype)
    for s, part in enumerate(old_dec.parts):
        ring_g[:, part] = ring_old[s][:, :part.size]
    for i, mg in enumerate(mirror_new):
        fields["ring"][i] = 0
        fields["ring"][i][:, :mg.size] = ring_g[:, mg]

    fields["t"][:] = step
    # advance the fresh key by the steps already run (the exact stream an
    # uninterrupted run holds; it is the same for every shard count)
    fields["key"] = np.array(dist.advance_key_data(
        jnp.asarray(fields["key"]), step))

    carried = {
        "wire_overflow": int(np.asarray(host["wire_overflow"]).sum()),
        "gate_overflow": int(np.asarray(host.get(
            "gate_overflow", np.zeros(1, np.int32))).sum()),
    }
    fields["wire_overflow"][:] = 0
    fields["gate_overflow"][:] = 0
    return fields, carried
