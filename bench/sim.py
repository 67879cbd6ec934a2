"""The one generator that drives the program: it reads a configuration and
a traffic mix (both data) and runs the program's own entry on them.

A traffic mix names its ``entry``:

- ``"engine.run"``: one chip; ``repro.core.engine.run`` scans
  ``steps_per_call`` steps per call.  The graph's arrays go in as
  operands of the jitted call, so the executable holds no graph and the
  persistent compilation cache can keep it.
- ``"distributed"``: a ``rows x width`` mesh; the shard_map'ed step of
  ``repro.core.distributed`` (``make_distributed_step`` places the
  consts; the same step taking them as operands is scanned
  ``steps_per_call`` times per call).

Both run the program's default backends: ``EngineConfig`` and
``DistributedConfig`` with nothing but ``dt`` and the STDP parameters set.
The network comes from the configuration's table (``bench.netspec``);
the initial membrane potentials are drawn from the seed by the
configuration's ``v_init`` (a normal distribution, as NEST's
hpc_benchmark randomises V_m).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

__all__ = ["Seeds", "make_sim", "EngineSim", "MeshSim"]


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Everything a run draws, derived from ``--seed`` (any size)."""

    network: int   # connectivity stream (where the configuration lets it)
    key: int       # the program's PRNG key (Poisson drive)
    v_init: int    # initial membrane potentials

    @classmethod
    def from_seed(cls, seed: int) -> "Seeds":
        s = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
        return cls(network=int(s[0]), key=int(s[1]), v_init=int(s[2]))


def v_init(data: dict, seed: int) -> np.ndarray:
    """Per-neuron initial potential [mV], float32: normal with the
    configuration's ``v_init`` mean and standard deviation."""
    n = sum(p["n"] for p in data["network"]["populations"])
    spec = data["v_init"]
    return np.random.default_rng(seed).normal(
        spec["mean_mV"], spec["std_mV"], n).astype(np.float32)


class _Sim:
    """What the harness needs of a run, whichever entry drives it."""

    def __init__(self, config, traffic: dict, seeds: Seeds, devices):
        self.config, self.traffic, self.seeds = config, traffic, seeds
        self.devices = devices
        self.plastic = bool(traffic["plastic"])
        self.steps = int(traffic["steps_per_call"])
        self.dt = float(config.data["dt_ms"])
        self.times: dict[str, float] = {}
        self.net_seed = config.network_seed(seeds)

    def setup(self):
        """Build, place and compile; returns the initial state.  Records
        ``build_s`` and ``compile_s``."""
        import jax

        t0 = time.perf_counter()
        self.spec, self.stdp = self.config.build(self.net_seed, self.plastic)
        self._build()
        self.times["build_s"] = time.perf_counter() - t0
        self.v0 = v_init(self.config.data, self.seeds.v_init)
        state = self._place()
        t0 = time.perf_counter()
        self.exe = jax.jit(self._call).lower(state, self.operands).compile()
        self.times["compile_s"] = time.perf_counter() - t0
        return state

    def call(self, state):
        return self.exe(state, self.operands)

    def hlo_text(self) -> str:
        return self.exe.as_text()

    @property
    def n(self) -> int:
        return int(self.spec.n_neurons)


class EngineSim(_Sim):
    """One chip through ``repro.core.engine.run``."""

    # graph fields that go in as jit operands; the rest (sizes, the delay
    # bucket table, the drive rates the drive thresholds are computed
    # from on the host) stay static
    _OPERANDS = ("pre_idx", "post_idx", "delay", "channel", "plastic",
                 "mirror_src_shard", "mirror_src_idx", "group_id",
                 "ext_weight", "global_id")

    def _build(self):
        from repro.core import builder
        self.dec = builder.decompose(self.spec, 1)
        self.graph = builder.build_shards(self.spec, self.dec)[0]

    def _place(self):
        import jax
        import jax.numpy as jnp
        from repro.core import engine, neuron_models

        dev = self.devices[0]
        g = self.graph.device_arrays()
        self.cfg = engine.EngineConfig(dt=self.dt, stdp=self.stdp)
        model_table = neuron_models.get_model(
            self.cfg.neuron_model).make_param_table(list(self.spec.groups),
                                                    self.dt)
        self.operands = jax.device_put(
            dict({k: getattr(g, k) for k in self._OPERANDS},
                 table=jnp.asarray(model_table)), dev)
        self.static = dataclasses.replace(
            self.graph, **{k: None for k in self._OPERANDS})
        state = engine.init_state(g, list(self.spec.groups),
                                  jax.random.key(self.seeds.key))
        gid = np.asarray(self.graph.global_id)
        v = np.asarray(state.neurons.v_m).copy()
        v[gid >= 0] = self.v0[gid[gid >= 0]]
        state = dataclasses.replace(state, neurons=dataclasses.replace(
            state.neurons, v_m=jnp.asarray(v)))
        return jax.device_put(state, dev)

    def _call(self, state, ops):
        from repro.core import engine
        ops = dict(ops)
        table = ops.pop("table")
        graph = dataclasses.replace(self.static, **ops)
        return engine.run(state, graph, table, self.cfg, self.steps)

    def raster(self, bits) -> np.ndarray:
        gid = np.asarray(self.graph.global_id)
        b = np.asarray(bits)
        out = np.zeros((b.shape[0], self.n), bool)
        out[:, gid[gid >= 0]] = b[:, gid >= 0]
        return out

    def final(self, state) -> dict:
        gid = np.asarray(self.graph.global_id)
        real = gid >= 0

        def glob(a):
            out = np.zeros(self.n, np.float32)
            out[gid[real]] = np.asarray(a)[real]
            return out
        n = state.neurons
        return dict(v=glob(n.v_m), syn_ex=glob(n.syn_ex),
                    syn_in=glob(n.syn_in),
                    w=np.asarray(state.weights).reshape(-1))

    def program_edges(self) -> dict:
        return _edges_global([self.graph.__dict__], self.dec.parts)


class MeshSim(_Sim):
    """A ``rows x width`` mesh through ``repro.core.distributed``."""

    def _build(self):
        from repro.core import distributed as dist
        rows, width = self.traffic["mesh"]
        self.dec = dist.mesh_decompose(self.spec, rows, width)
        self.net = dist.prepare_stacked(self.spec, self.dec, rows, width)

    def _place(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import distributed as dist
        from repro.core import engine

        rows, width = self.traffic["mesh"]
        self.cfg = dist.DistributedConfig(
            engine=engine.EngineConfig(dt=self.dt, stdp=self.stdp))
        mesh = jax.make_mesh((rows, width), self.cfg.axis_names,
                             devices=self.devices[:rows * width])
        groups = list(self.spec.groups)
        _, self.operands = dist.make_distributed_step(self.net, mesh, groups,
                                                      self.cfg)
        needs_blocked = dist.check_net_backend(self.net,
                                               self.cfg).needs_blocked
        self.step = dist.make_raw_distributed_step(
            mesh, groups, self.cfg, max_delay=self.net.max_delay,
            n_local=self.net.n_local, n_mirror=self.net.n_mirror,
            blocked_meta=self.net.blocked_meta if needs_blocked else None)
        state = dist.init_stacked_state(self.net, groups,
                                        seed=self.seeds.key, mesh=mesh,
                                        axis_names=self.cfg.axis_names)
        v = np.asarray(state.v_m).copy()
        for s, part in enumerate(self.dec.parts):
            v[s, :part.size] = self.v0[part]
        return dataclasses.replace(state, v_m=jax.device_put(
            v, NamedSharding(mesh, P(self.cfg.axis_names))))

    def _call(self, state, consts):
        import jax
        return jax.lax.scan(lambda s, _: self.step(s, consts), state, None,
                            length=self.steps)

    def _glob(self, a) -> np.ndarray:
        a = np.asarray(a)
        out = np.zeros(a.shape[:-2] + (self.n,), a.dtype)
        for s, part in enumerate(self.dec.parts):
            out[..., part] = a[..., s, :part.size]
        return out

    def raster(self, bits) -> np.ndarray:
        return self._glob(bits) > 0

    def final(self, state) -> dict:
        return dict(v=self._glob(state.v_m), syn_ex=self._glob(state.syn_ex),
                    syn_in=self._glob(state.syn_in),
                    w=np.asarray(state.weights).reshape(-1))

    def program_edges(self) -> dict:
        g = self.net.graph
        shards = [dict({k: np.asarray(g[k][s]) for k in
                        ("pre_idx", "post_idx", "delay", "channel",
                         "plastic", "weight_init", "mirror_src_idx")},
                       mirror_src_shard=self.net.mirror_src_flat[s])
                  for s in range(self.net.n_shards)]
        return _edges_global(shards, self.dec.parts)


def _edges_global(shards: list, parts: list) -> dict:
    """The program's edge arrays in global neuron ids, padding dropped;
    ``index`` is each edge's place in the flattened (shards x edges)
    weight vector of the final state."""
    cols = {k: [] for k in ("pre", "post", "delay", "channel", "plastic",
                            "w", "index")}
    offset = 0
    for s, g in enumerate(shards):
        delay = np.asarray(g["delay"])
        real = np.flatnonzero(delay > 0)
        msrc = np.asarray(g["mirror_src_shard"])
        midx = np.asarray(g["mirror_src_idx"])
        m = np.asarray(g["pre_idx"])[real]
        pre = np.empty(real.size, np.int64)
        for src in np.unique(msrc[m]).tolist():
            sel = msrc[m] == src
            pre[sel] = parts[src][midx[m[sel]]]
        cols["pre"].append(pre)
        cols["post"].append(parts[s][np.asarray(g["post_idx"])[real]])
        cols["delay"].append(delay[real].astype(np.int64))
        cols["channel"].append(np.asarray(g["channel"])[real])
        cols["plastic"].append(np.asarray(g["plastic"])[real])
        cols["w"].append(np.asarray(g["weight_init"], np.float32)[real])
        cols["index"].append(real + offset)
        offset += delay.size
    return {k: np.concatenate(v) for k, v in cols.items()}


def make_sim(config, traffic: dict, seeds: Seeds, devices) -> _Sim:
    entries = {"engine.run": EngineSim, "distributed": MeshSim}
    if traffic["entry"] not in entries:
        raise ValueError(f"unknown entry {traffic['entry']!r}; "
                         f"known: {sorted(entries)}")
    return entries[traffic["entry"]](config, traffic, seeds, devices)
