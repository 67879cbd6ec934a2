"""Every Pallas kernel of the main path compiles for a TPU v5e.

Ahead-of-time compiles against a described (not attached) ``v5e:2x2``
topology, at the widths the chip runs: ``hpc_benchmark(scale=1)`` on one
chip and one shard of ``marmoset(scale=0.05)`` on a 2x2 mesh.  Interpret
mode cannot see what these catch - blocks that break the (8, 128) tiling
rule, gathers the kernel compiler does not lower, VMEM overflow.  Each
kernel test checks that the compiled program holds the kernel
(``tpu_custom_call``), i.e. nothing fell back to interpret mode.  The
flat sweep's step is compiled whole at the benchmark's size, where XLA's
layout choices decide its scratch memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine, neuron_models, snn
from repro.core.models import HPC_STDP
from repro.kernels import adex_step, izhikevich_step
from repro.kernels.lif_step import lif_step_kernel
from repro.kernels.stdp_update import stdp_update_kernel, stdp_update_worklist
from repro.kernels.synaptic_gather import (DEFAULT_PB, blocked_reduce_sweep,
                                           synaptic_gather)

# (n_local, n_mirror, max_delay, NB, EB) per width:
# - hpc_benchmark(scale=1): 11,250 neurons padded to 11,256, indegree
#   900 + 225 = 1125, so a 256-row post block holds 288,000 edges; D = 16;
# - marmoset(scale=0.05) on 2x2, one shard: 12,568 rows, 23,680 mirrors,
#   12,534,136 edges over 50 post blocks; D = 106.
WIDTHS = {
    "hpc": (11256, 11256, 16, 44, 288000),
    "marmoset_shard": (12568, 23680, 106, 50, 250752),
}
STDP = (HPC_STDP.lam, HPC_STDP.alpha, HPC_STDP.mu, HPC_STDP.w0,
        HPC_STDP.w_min, HPC_STDP.w_max)
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dt: jax.ShapeDtypeStruct(dims, dt,
                                                 sharding=one_chip)


def assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("cond", [False, True])
def test_lif_step_compiles(shape, width, cond):
    n = WIDTHS[width][0]
    assert_kernel_compiles(
        functools.partial(lif_step_kernel, cond=cond, interpret=False),
        *[shape((n,), f32)] * 3, shape((n,), i32), shape((n,), i32),
        shape((n,), f32), shape((n,), f32), shape((2, snn.NCOL), f32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("mod,fn", [
    (izhikevich_step, izhikevich_step.izhikevich_step_kernel),
    (adex_step, adex_step.adex_step_kernel)], ids=["izhikevich", "adex"])
def test_two_variable_neuron_step_compiles(shape, width, mod, fn):
    n = WIDTHS[width][0]
    assert_kernel_compiles(
        functools.partial(fn, interpret=False),
        *[shape((n,), f32)] * 4, shape((n,), i32), shape((n,), i32),
        shape((n,), f32), shape((n,), f32), shape((2, mod.NCOL), f32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("blocked", [False, True], ids=["flat", "blocked"])
def test_stdp_update_compiles(shape, width, blocked):
    n, m, _, nb, eb = WIDTHS[width]
    e, nl = nb * eb, nb * DEFAULT_PB
    kw = dict(eb=eb, pb=DEFAULT_PB) if blocked else {}
    assert_kernel_compiles(
        functools.partial(stdp_update_kernel, params=STDP, interpret=False,
                          **kw),
        shape((e,), f32), shape((e,), i32), shape((e,), i32),
        shape((e,), jnp.bool_), shape((e,), f32), shape((nl,), f32),
        shape((m,), f32), shape((nl,), f32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_stdp_update_worklist_compiles(shape, width):
    _, m, _, nb, eb = WIDTHS[width]
    g, nl = 8, nb * DEFAULT_PB
    assert_kernel_compiles(
        functools.partial(stdp_update_worklist, params=STDP,
                          pb=DEFAULT_PB, interpret=False),
        shape((g, eb), f32), shape((g, eb), i32), shape((g, eb), i32),
        shape((g, eb), jnp.bool_), shape((g, eb), f32), shape((g,), i32),
        shape((nl,), f32), shape((m,), f32), shape((nl,), f32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("fresh", [False, True], ids=["ring", "overlap"])
def test_synaptic_gather_compiles(shape, width, fresh):
    _, m, d, nb, eb = WIDTHS[width]
    edges = [shape((nb, eb), dt) for dt in (i32, i32, f32, i32, i32)]
    extra = (shape((m,), f32),) if fresh else ()

    def sweep(pre, post, w, delay, chan, ring, t, *fresh_bits):
        return synaptic_gather(
            pre, post, w, delay, chan, ring, t, max_delay=d,
            pb=DEFAULT_PB, interpret=False, emit_arrivals=True,
            fresh=fresh_bits[0] if fresh_bits else None)

    assert_kernel_compiles(sweep, *edges, shape((d, m), f32),
                           shape((), i32), *extra)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_blocked_reduce_sweep_compiles(shape, width):
    nb, eb = WIDTHS[width][3:]
    assert_kernel_compiles(
        functools.partial(blocked_reduce_sweep, pb=DEFAULT_PB,
                          interpret=False),
        shape((nb, eb), i32), shape((nb, eb), f32), shape((nb, eb), f32),
        shape((nb, eb), i32))


# hpc_benchmark at the benchmark's scale 0.1 on one chip: 1,125 rows of
# 11,250 edges (no multiple of the 128 lanes), padded to 1,128 rows and
# 12,656,256 edges; one delay of 15 steps, D = 16.
ROWS, N_LOCAL, K, E_PAD, D = 1125, 1128, 11250, 12656256, 16


def _flat_run_text(shape, row_indegree, stdp):
    """Compiled text and temp bytes of ``engine.run`` (10 steps, flat
    sweep) with the graph's arrays as operands, as the benchmark runs it."""
    edge = {k: shape((E_PAD,), dt) for k, dt in (
        ("pre_idx", i32), ("post_idx", i32), ("delay", i32),
        ("channel", i32), ("plastic", jnp.bool_))}
    rows = {k: shape((N_LOCAL,), dt) for k, dt in (
        ("mirror_src_shard", i32), ("mirror_src_idx", i32),
        ("group_id", i32), ("ext_weight", f32), ("global_id", i32))}
    gid = np.where(np.arange(N_LOCAL) < ROWS, np.arange(N_LOCAL), -1)
    static = engine.ShardGraph(
        n_local=N_LOCAL, n_mirror=N_LOCAL, max_delay=D, pre_idx=None,
        post_idx=None, delay=None, channel=None, plastic=None,
        weight_init=np.zeros(E_PAD, np.float32),
        bucket_ptr=np.zeros(D + 2, np.int64), mirror_src_shard=None,
        mirror_src_idx=None, group_id=np.zeros(N_LOCAL, np.int32),
        ext_rate=np.full(N_LOCAL, 2e4, np.float32), ext_weight=None,
        global_id=gid.astype(np.int32), row_indegree=row_indegree)
    groups = [snn.LIFParams()]
    state = jax.eval_shape(lambda: engine.init_state(
        static, groups, jax.random.key(0)))
    state = jax.tree.map(lambda a: shape(a.shape, a.dtype), state)
    static = dataclasses.replace(static, global_id=None, weight_init=None)
    table = neuron_models.get_model("lif").make_param_table(groups, 0.1)
    cfg = engine.EngineConfig(dt=0.1, stdp=HPC_STDP if stdp else None)

    def call(state, ops):
        ops = dict(ops)
        tab = ops.pop("table")
        return engine.run(state, dataclasses.replace(static, **ops), tab,
                          cfg, 10)

    ops = dict(edge, **rows, table=shape(table.shape, table.dtype))
    compiled = jax.jit(call).lower(state, ops).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("stdp", [False, True], ids=["static", "stdp"])
def test_flat_sweep_row_reduce_compiles_without_relayout(shape, stdp):
    """On a fixed-indegree graph the compiled step sums rows in one pass
    over the edges with no scatter in the ``sweep`` scope, copies no edge
    vector outside a fusion, and needs no more scratch memory than the
    segment_sum form: a (rows, K) view of the flat edge vectors would copy
    each one into a 2-D layout."""
    text, temp = _flat_run_text(shape, K, stdp)
    # one pass over the edges: a single fusion yields all four per-chunk
    # sums (two channels, either side of a row start)
    chunk_sums = [line for line in text.splitlines()
                  if " fusion(" in line and "row_reduce/" in line
                  and f"f32[{E_PAD // 128}]" in line.split(" fusion(")[0]]
    assert len(chunk_sums) == 1, chunk_sums
    assert chunk_sums[0].split(" fusion(")[0].count(
        f"f32[{E_PAD // 128}]") == 4
    in_sweep = re.compile(r'op_name="[^"]*/sweep/')
    assert not [line for line in text.splitlines()
                if " scatter(" in line and in_sweep.search(line)]
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    array = re.compile(r"= \w+\[([\d,]+)\]\S* ([\w\-]+)\(")
    comp = None
    for line in text.splitlines():
        header = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if header:
            comp = header.group(1)
            continue
        m = array.search(line)
        if not m or comp in fused:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if np.prod(dims) >= ROWS * K:   # an edge vector, outside a fusion
            # XLA's relayouts into 2-D carry no op_name of their own
            assert len(dims) == 1 or m.group(2) == "bitcast", line
            assert not (in_sweep.search(line)
                        and m.group(2) in ("copy", "transpose")), line
    _, temp_segment = _flat_run_text(shape, None, stdp)
    assert temp <= temp_segment + 2**20
