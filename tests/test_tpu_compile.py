"""Every Pallas kernel of the main path compiles for a TPU v5e.

Ahead-of-time compiles against a described (not attached) ``v5e:2x2``
topology, at the widths the chip runs: ``hpc_benchmark(scale=1)`` on one
chip and one shard of ``marmoset(scale=0.05)`` on a 2x2 mesh.  Interpret
mode cannot see what these catch - blocks that break the (8, 128) tiling
rule, gathers the kernel compiler does not lower, VMEM overflow.  Each
test checks that the compiled program holds the kernel
(``tpu_custom_call``), i.e. nothing fell back to interpret mode.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import snn
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
