"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; only the dry-run process
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its
first jax import.

Axis semantics (DESIGN.md §6):
    pod    - data parallel across pods; only gradient all-reduce crosses it
    data   - batch sharding + FSDP within a pod (+ sequence-sharded KV for
             the 500k decode cells)
    model  - tensor/expert parallelism (heads, ffn-hidden, vocab, experts)
For the SNN engine the same axes carry the paper's decomposition:
(pod, data) rows = Area-Processes groups, model = multisection cells.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_snn_host_mesh",
           "auto_mesh", "POD_SHAPE", "SINGLE_POD_SHAPE"]

SINGLE_POD_SHAPE = (16, 16)              # 256 chips (one v5e pod)
POD_SHAPE = (2, 16, 16)                  # 2 pods = 512 chips


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the sharding-
    propagation mode the LM substrate is written for (``shard_act``
    constraints, GSPMD propagation).  JAX's own default is ``Explicit``
    axes, under which every reshape or gather of a sharded array must name
    its output sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small host-device mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=prod(shape))."""
    return auto_mesh(shape, axes)


def make_snn_host_mesh(n_rows: int, row_width: int):
    """Host-ALIGNED (rows, model) mesh for the multi-host SNN engine:
    Area-Processes rows land on single hosts, so the intra-row spike
    bitmap gather never crosses the inter-host fabric (DESIGN.md §11).
    Works single- and multi-process; validates the alignment."""
    from repro.core.multihost import make_host_mesh
    return make_host_mesh(n_rows, row_width)
