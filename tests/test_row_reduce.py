"""The flat sweep's fixed-indegree row reduction (backends._row_reduce).

Where the builder finds every owned row holding the same ``K`` edges as a
post-sorted prefix (``ShardGraph.row_indegree``), the flat sweep sums each
row on its own in place of two ``segment_sum`` scatters; everywhere else
the ``segment_sum`` form stays as it was, bit for bit.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, builder, distributed, engine, models, snn

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _one_shard(spec, method="area"):
    return builder.build_shards(
        spec, builder.decompose(spec, 1, method=method))[0]


def _indegree(spec) -> int:
    """The fixed indegree of every post neuron of an hpc_benchmark spec."""
    per_dst = {}
    for pr in spec.projections:
        per_dst[pr.dst_pop] = per_dst.get(pr.dst_pop, 0) + pr.indegree
    assert len(set(per_dst.values())) == 1
    return per_dst[0]


@pytest.fixture(scope="module")
def hpc():
    """hpc_benchmark with rows of 135 edges (at least LANES), whose edge
    count is no multiple of LANES, so the ragged last chunk is summed."""
    spec, _ = models.hpc_benchmark(scale=0.12, stdp=False)
    g = _one_shard(spec)
    assert g.n_edges % backends.LANES
    return spec, g


def _segment_sum_form(layout, weights, arrived):
    """The flat sweep's reduction as it stood before the row form."""
    contrib = weights * arrived
    ex = jnp.where(layout.channel == 0, contrib, 0.0)
    inh = jnp.where(layout.channel == 1, contrib, 0.0)
    return (jax.ops.segment_sum(ex, layout.post_idx,
                                num_segments=layout.n_local),
            jax.ops.segment_sum(inh, layout.post_idx,
                                num_segments=layout.n_local))


def _random_inputs(g, seed=0):
    rng = np.random.default_rng(seed)
    w = np.asarray(g.weight_init, np.float32) * rng.uniform(
        0.5, 1.5, g.n_edges).astype(np.float32)
    arrived = (rng.uniform(size=g.n_edges) < 0.3) & (np.asarray(g.delay) > 0)
    return jnp.asarray(w), jnp.asarray(arrived.astype(np.float32))


@pytest.mark.parametrize("scale", [0.02, 0.12])
def test_hpc_benchmark_row_indegree_is_the_indegree(scale):
    spec, _ = models.hpc_benchmark(scale=scale, stdp=False)
    g = _one_shard(spec)
    assert g.row_indegree == _indegree(spec)
    assert backends.layout_of(g).row_indegree == g.row_indegree


def test_row_reduce_matches_segment_sum(hpc):
    _, g = hpc
    layout = backends.layout_of(g.device_arrays())
    assert layout.row_indegree >= backends.LANES
    w, arrived = _random_inputs(g)
    got = backends._accumulate(layout, w, arrived)
    want = _segment_sum_form(layout, w, arrived)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())
    # padding rows read nothing
    n_owned = int((np.asarray(g.global_id) >= 0).sum())
    assert not np.asarray(got[0])[n_owned:].any()


def test_row_reduce_gradient_matches_segment_sum(hpc):
    """Surrogate-gradient training differentiates through the sweep."""
    _, g = hpc
    layout = backends.layout_of(g.device_arrays())
    w, arrived = _random_inputs(g, seed=3)
    probe = jnp.arange(layout.n_local, dtype=jnp.float32)

    def grad(reduce):
        loss = lambda w: sum((s * probe).sum()
                             for s in reduce(layout, w, arrived))
        return np.asarray(jax.grad(loss)(w))

    np.testing.assert_array_equal(grad(backends._accumulate),
                                  grad(_segment_sum_form))


def test_flat_sweep_takes_the_row_form(hpc):
    """The engine's flat sweep reaches the row form, and a short run keeps
    the segment_sum run's potentials to float32 rounding."""
    spec, g = hpc
    g = g.device_arrays()
    table = jnp.asarray(snn.make_param_table(list(spec.groups), dt=0.1))
    cfg = engine.EngineConfig(dt=0.1)
    text = jax.jit(lambda s: engine.run(s, g, table, cfg, 2)).lower(
        engine.init_state(g, list(spec.groups), jax.random.key(0))
    ).compile().as_text()
    assert "row_reduce" in text and "segment_reduce" not in text
    plain = dataclasses.replace(g, row_indegree=None)
    finals = []
    for graph in (g, plain):
        st = engine.init_state(graph, list(spec.groups), jax.random.key(1))
        final, _ = engine.run(st, graph, table, cfg, 5)
        finals.append(np.asarray(final.neurons.v_m))
    np.testing.assert_allclose(finals[0], finals[1], rtol=1e-5, atol=1e-4)


def test_multi_delay_graph_keeps_segment_sum():
    g = _one_shard(models.marmoset(scale=0.001, n_areas=4), method="random")
    assert len(np.unique(np.asarray(g.delay))) > 2
    assert g.row_indegree is None
    layout = backends.layout_of(g.device_arrays())
    w, arrived = _random_inputs(g, seed=1)
    got = backends._accumulate(layout, w, arrived)
    want = _segment_sum_form(layout, w, arrived)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (post_idx, delay, n_owned) of graphs that break one condition each
IRREGULAR = {
    "short_row": ([0, 0, 0, 1, 1, 2, 2, 2], [3] * 8, 3),
    "unsorted": ([0, 0, 1, 0, 1, 1, 2, 2, 2], [3] * 9, 3),
    "live_not_a_prefix": ([0, 0, 0, 1, 1, 1, 0], [3, 3, 0, 3, 3, 3, 3], 2),
    "padding_row_with_edges": ([0, 0, 1, 1, 2, 2], [3] * 6, 2),
    "no_edges": ([0, 0], [0, 0], 2),
}


@pytest.mark.parametrize("case", sorted(IRREGULAR))
def test_irregular_rows_read_none(case):
    post, delay, n_owned = IRREGULAR[case]
    assert builder.row_indegree(np.asarray(post), np.asarray(delay),
                                n_owned) is None


def test_regular_rows_with_padding_read_k():
    post = np.asarray([0, 0, 0, 1, 1, 1, 0, 0])
    delay = np.asarray([2, 2, 2, 2, 2, 2, 0, 0])
    assert builder.row_indegree(post, delay, 2) == 3


def test_graph_with_a_short_row_keeps_segment_sum():
    """A hand-made graph whose last row holds one edge fewer: no row form,
    and the flat sweep's sums are the segment_sum form's, bit for bit."""
    k, n_owned, n_local = 130, 3, 8
    post = np.repeat(np.arange(n_owned), k)[:-1]
    post = np.concatenate([post, np.zeros(7, np.int64)])
    e = post.size
    delay = np.where(np.arange(e) < e - 7, 1, 0)
    assert builder.row_indegree(post, delay, n_owned) is None
    rng = np.random.default_rng(2)
    layout = backends.EdgeLayout(
        n_local=n_local, n_mirror=n_local, max_delay=2,
        pre_idx=jnp.zeros(e, jnp.int32), post_idx=jnp.asarray(post, jnp.int32),
        delay=jnp.asarray(delay, jnp.int32),
        channel=jnp.asarray(rng.integers(0, 2, e), jnp.int32),
        plastic=jnp.zeros(e, bool))
    w = jnp.asarray(rng.normal(size=e), jnp.float32)
    arrived = jnp.asarray(delay > 0, jnp.float32)
    got = backends._accumulate(layout, w, arrived)
    want = _segment_sum_form(layout, w, arrived)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_operand_free_graph_keeps_row_indegree(hpc):
    """The benchmark's one-chip runner passes the graph's arrays as jit
    operands and keeps the rest of the graph static: ``row_indegree``
    stays on the static half and reaches the sweep's layout."""
    sys.path.insert(0, ROOT)
    from bench.sim import EngineSim

    _, g = hpc
    static = dataclasses.replace(g, **{k: None for k in EngineSim._OPERANDS})
    assert static.row_indegree == g.row_indegree
    ops = {k: getattr(g.device_arrays(), k) for k in EngineSim._OPERANDS}
    layout = backends.layout_of(dataclasses.replace(static, **ops))
    assert layout.row_indegree == g.row_indegree


def test_shard_map_layout_carries_none():
    """The distributed step's per-shard layout has no row form: its shards
    are stacked and padded to a common edge count."""
    e = 16
    consts = {k: jnp.zeros(e, jnp.int32) for k in
              ("pre_idx", "post_idx", "delay", "channel")}
    consts["plastic"] = jnp.zeros(e, bool)
    layout = distributed._layout_from_consts(consts, 8, 8, 4, None)
    assert layout.row_indegree is None
