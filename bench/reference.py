"""Plain reference of the simulated dynamics, independent of the program.

It imports nothing of ``repro``.  Everything it uses comes from the
configuration's file (the network table, the neuron and STDP parameters)
and from the run's seed:

- :func:`edges` regenerates the network's connectivity from the table with
  the fixed-indegree rule and the documented random streams (a copy of the
  program's two generators: one stream per projection, or one Philox
  stream per post row);
- :func:`replay` integrates current-based LIF neurons with exponential
  synapses (exact integration, NEST's ``iaf_psc_exp``), the external
  Poisson drive (Knuth's product method on JAX's key stream) and the
  power-law STDP rule (``stdp_pl_synapse_hom``), event by event in numpy.

The replay is teacher-forced by the raster the program served: each step
it computes every neuron's membrane potential from its own state, records
whether the neuron would fire, and then resets the neurons that the served
raster says fired.  Spikes then reach their targets as served, so one
spike that a rounding difference flips near the threshold does not make
the two trajectories part (the network is chaotic), while every served
spike is still checked against the potential that should have caused it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Net", "net_from_config", "edges", "lif_constants",
           "drive_thresholds", "drive_events", "replay", "Replay"]

_MATERIALIZED_SALT = 7919
_ROW_SALT = 104729


@dataclasses.dataclass(frozen=True)
class Net:
    """The network table of a configuration file, with derived offsets."""

    populations: list       # dicts: n, group, ext_rate_hz, ext_weight
    projections: list       # dicts: src_pop, dst_pop, indegree, weight_mean, ...
    groups: list            # dicts of LIF parameters
    max_delay: int
    seed: int
    connectivity: str       # "materialized" | "procedural"
    dt: float               # [ms]
    stdp: dict | None       # pl-STDP parameters when plasticity is on

    @property
    def offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(
            [p["n"] for p in self.populations])]).astype(np.int64)

    @property
    def n(self) -> int:
        return int(self.offsets[-1])

    def per_neuron(self, key: str) -> np.ndarray:
        """A population's value spread over the global neuron ids."""
        out = np.empty(self.n, np.float64)
        off = self.offsets
        for i, p in enumerate(self.populations):
            out[off[i]:off[i + 1]] = p[key]
        return out


def net_from_config(cfg: dict, seed: int, plastic: bool) -> Net:
    """``cfg`` is a configuration file's content; ``seed`` the network
    seed the run derived; ``plastic`` whether the traffic turns STDP on."""
    net = cfg["network"]
    projections = [dict(p, plastic=bool(p["plastic"] and plastic))
                   for p in net["projections"]]
    return Net(populations=net["populations"], projections=projections,
               groups=net["groups"], max_delay=int(net["max_delay"]),
               seed=int(seed), connectivity=net["connectivity"],
               dt=float(cfg["dt_ms"]),
               stdp=cfg.get("stdp") if plastic else None)


# --------------------------------------------------------------------------
# connectivity
# --------------------------------------------------------------------------

def _sign_clamp(w: np.ndarray, pr: dict) -> np.ndarray:
    # biological weights keep the sign of their mean
    if pr["weight_std"] > 0.0:
        return (np.maximum(w, 0.0) if pr["weight_mean"] >= 0
                else np.minimum(w, 0.0))
    return w


def _projection_materialized(net: Net, pi: int):
    pr = net.projections[pi]
    off = net.offsets
    src = net.populations[pr["src_pop"]]
    dst = net.populations[pr["dst_pop"]]
    k = int(pr["indegree"])
    rng = np.random.default_rng(
        np.random.SeedSequence([net.seed, _MATERIALIZED_SALT, pi]))
    post = (np.repeat(np.arange(dst["n"], dtype=np.int64), k)
            + off[pr["dst_pop"]])
    n_src = max(1, int(round(src["n"] * pr["src_frac"])))
    pre = rng.integers(0, n_src, size=dst["n"] * k)
    if not pr["allow_autapse"] and pr["src_pop"] == pr["dst_pop"]:
        self_mask = pre == (post - off[pr["dst_pop"]])
        while np.any(self_mask):
            pre[self_mask] = rng.integers(0, src["n"],
                                          size=int(self_mask.sum()))
            self_mask = pre == (post - off[pr["dst_pop"]])
    pre = pre + off[pr["src_pop"]]
    w = _sign_clamp(rng.normal(pr["weight_mean"], pr["weight_std"],
                               size=post.size), pr)
    d = rng.integers(pr["delay_min"], pr["delay_max"] + 1, size=post.size)
    return pre, post, w, d


def _projection_procedural(net: Net, pi: int, rows: np.ndarray):
    pr = net.projections[pi]
    off = net.offsets
    src = net.populations[pr["src_pop"]]
    k = int(pr["indegree"])
    n_src = max(1, int(round(src["n"] * pr["src_frac"])))
    reject = not pr["allow_autapse"] and pr["src_pop"] == pr["dst_pop"]
    pre = np.empty(rows.size * k, np.int64)
    w = np.empty(rows.size * k, np.float64)
    d = np.empty(rows.size * k, np.int64)
    for j, gid in enumerate(rows.tolist()):
        rng = np.random.Generator(np.random.Philox(
            np.random.SeedSequence([net.seed, _ROW_SALT, pi, gid])))
        sl = slice(j * k, j * k + k)
        p = rng.integers(0, n_src, size=k)
        if reject:
            row = gid - off[pr["dst_pop"]]
            m = p == row
            while np.any(m):
                p[m] = rng.integers(0, src["n"], size=int(m.sum()))
                m = p == row
        pre[sl] = p
        w[sl] = rng.normal(pr["weight_mean"], pr["weight_std"], size=k)
        d[sl] = rng.integers(pr["delay_min"], pr["delay_max"] + 1, size=k)
    return (pre + off[pr["src_pop"]], np.repeat(rows.astype(np.int64), k),
            _sign_clamp(w, pr), d)


def edges(net: Net) -> dict:
    """Every synapse of the network in global neuron ids: ``pre``,
    ``post``, ``delay`` (steps), ``channel`` (0 ex, 1 in), ``plastic`` and
    ``w`` (float32, as the program stores it)."""
    parts = []
    off = net.offsets
    for pi, pr in enumerate(net.projections):
        if int(pr["indegree"]) <= 0:
            continue
        if net.connectivity == "procedural":
            rows = np.arange(off[pr["dst_pop"]], off[pr["dst_pop"] + 1],
                             dtype=np.int64)
            pre, post, w, d = _projection_procedural(net, pi, rows)
        elif net.connectivity == "materialized":
            pre, post, w, d = _projection_materialized(net, pi)
        else:
            raise ValueError(f"unknown connectivity {net.connectivity!r}")
        parts.append((pre, post, w, d,
                      np.full(pre.size, pr["channel"], np.int8),
                      np.full(pre.size, pr["plastic"], bool)))
    cat = lambda i: np.concatenate([p[i] for p in parts])
    return dict(pre=cat(0), post=cat(1), w=cat(2).astype(np.float32),
                delay=cat(3), channel=cat(4), plastic=cat(5))


# --------------------------------------------------------------------------
# neuron constants and drive
# --------------------------------------------------------------------------

def _coupling(tau_s: float, tau_m: float, c_m: float, dt: float) -> float:
    """P_{v,syn} of the exact update of dv/dt = -v/tau_m + I/c_m,
    dI/dt = -I/tau_s (Rotter & Diesmann 1999)."""
    if abs(tau_m - tau_s) < 1e-9:
        return dt / c_m * np.exp(-dt / tau_m)
    return (tau_s * tau_m / (c_m * (tau_m - tau_s))
            * (np.exp(-dt / tau_m) - np.exp(-dt / tau_s)))


def lif_constants(net: Net) -> dict:
    """Per-neuron propagators and thresholds (float64)."""
    rows = []
    for g in net.groups:
        p_vv = np.exp(-net.dt / g["tau_m"])
        rows.append(dict(
            p_vv=p_vv,
            p_ee=np.exp(-net.dt / g["tau_syn_ex"]),
            p_ii=np.exp(-net.dt / g["tau_syn_in"]),
            p_ve=_coupling(g["tau_syn_ex"], g["tau_m"], g["c_m"], net.dt),
            p_vi=_coupling(g["tau_syn_in"], g["tau_m"], g["c_m"], net.dt),
            p_vconst=(g["e_l"] * (1.0 - p_vv)
                      + g["tau_m"] / g["c_m"] * (1.0 - p_vv) * g["i_e"]),
            e_l=g["e_l"], v_th=g["v_th"], v_reset=g["v_reset"],
            ref_steps=max(1.0, round(g["t_ref"] / net.dt))))
    group = np.concatenate([np.full(p["n"], p["group"], np.int64)
                            for p in net.populations])
    return {k: np.asarray([r[k] for r in rows])[group] for k in rows[0]}


def drive_thresholds(net: Net) -> np.ndarray:
    """``exp(-lam)`` per neuron, lam the expected external events per
    step, from the float32 rate as the configuration stores it."""
    rate = net.per_neuron("ext_rate_hz").astype(np.float32)
    lam = rate.astype(np.float64) * (net.dt * 1e-3)
    return np.exp(-lam).astype(np.float32)


def drive_events(key_seed: int, thresh: np.ndarray,
                 n_steps: int) -> np.ndarray:
    """(n_steps, N) external event counts.  The step key is split off the
    run's key (``jax.random.key(key_seed)``) once per step; each neuron
    folds its global id into it and multiplies uniforms until the product
    falls to ``thresh`` (Knuth).  Computed on the host CPU with JAX's
    random bits."""
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.key(int(key_seed))
        th = jnp.asarray(thresh)
        gids = jnp.arange(thresh.size, dtype=jnp.int32)
        return np.asarray(_drive_scan(key, th, gids, n_steps))


def _knuth(k, t):
    import jax
    import jax.numpy as jnp

    def body(c):
        n, prod, k = c
        k, sub = jax.random.split(k)
        return n + 1, prod * jax.random.uniform(sub), k

    k, sub = jax.random.split(k)
    n, _, _ = jax.lax.while_loop(lambda c: c[1] > t, body,
                                 (jnp.int32(0), jax.random.uniform(sub), k))
    return n


def _drive_scan_impl(key, th, gids, n_steps):
    import jax

    def step(key, _):
        key, sub = jax.random.split(key)
        ks = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(sub, gids)
        return key, jax.vmap(_knuth)(ks, th)

    return jax.lax.scan(step, key, None, length=n_steps)[1]


def _drive_scan(key, th, gids, n_steps):
    import jax
    return jax.jit(_drive_scan_impl, static_argnums=3)(key, th, gids,
                                                       n_steps)


# --------------------------------------------------------------------------
# the replay
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Replay:
    """What the replay computed.  Per step: ``v_pre`` (the potential
    before any reset), ``refractory`` and ``fires`` (the replay's own
    spike decision).  At the end: ``v``, ``syn_ex``, ``syn_in`` and the
    weights ``w`` in :func:`edges` order."""

    v_pre: np.ndarray
    refractory: np.ndarray
    fires: np.ndarray
    v: np.ndarray
    syn_ex: np.ndarray
    syn_in: np.ndarray
    w: np.ndarray


def _ranges(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Concatenation of ``arange(lo[i], hi[i])``."""
    n = hi - lo
    keep = n > 0
    lo, n = lo[keep], n[keep]
    if n.size == 0:
        return np.zeros(0, np.int64)
    starts = np.repeat(lo - np.concatenate([[0], np.cumsum(n)[:-1]]), n)
    return starts + np.arange(int(n.sum()))


def replay(net: Net, e: dict, raster: np.ndarray, v0: np.ndarray,
           key_seed: int, dtype=np.float32, events=None) -> Replay:
    """Integrate the network over ``raster.shape[0]`` steps from ``v0``
    (synaptic currents, refractory counters and traces at zero, weights
    at their initial values), in ``dtype`` arithmetic, reaching each step's
    spikes from the served ``raster`` (steps, N).  ``events`` (the drive,
    from :func:`drive_events`) may be passed when it was drawn already."""
    T, N = raster.shape
    D = net.max_delay
    f = lambda x: np.asarray(x, np.float64).astype(dtype)
    c = {k: f(v) for k, v in lif_constants(net).items()}
    ref_steps = lif_constants(net)["ref_steps"].astype(np.int64)
    ext_w = f(net.per_neuron("ext_weight").astype(np.float32))
    if events is None:
        events = drive_events(key_seed, drive_thresholds(net), T)

    # out-edges grouped by (pre, delay): a spike of ``pre`` at step s
    # reaches those edges at s + delay
    slot = e["pre"] * (D + 1) + e["delay"]
    by_pre = np.argsort(slot, kind="stable")
    ptr = np.searchsorted(slot[by_pre], np.arange(N * (D + 1) + 1))
    delays = np.unique(e["delay"])
    post, chan = e["post"], e["channel"]
    w = e["w"].astype(np.float64).astype(dtype)
    plastic_any = net.stdp is not None and bool(e["plastic"].any())
    if plastic_any:
        s = net.stdp
        by_post = np.flatnonzero(e["plastic"])
        by_post = by_post[np.argsort(post[by_post], kind="stable")]
        post_ptr = np.searchsorted(post[by_post], np.arange(N + 1))
        dep = f(s["lam"] * s["alpha"])
        pot = f(s["lam"]) * f(s["w0"] ** (1.0 - s["mu"]))
        mu, tiny = f(s["mu"]), f(1e-12)
        w_lo, w_hi = f(s["w_min"]), f(s["w_max"])
        dec_pre = np.exp(np.asarray(-net.dt / s["tau_plus"], dtype))
        dec_post = np.exp(np.asarray(-net.dt / s["tau_minus"], dtype))
        k_pre = np.zeros(N, dtype)
        k_post = np.zeros(N, dtype)

    v = np.asarray(v0).astype(dtype)
    sx = np.zeros(N, dtype)
    si = np.zeros(N, dtype)
    ref = np.zeros(N, np.int64)
    out_v = np.empty((T, N), dtype)
    out_ref = np.empty((T, N), bool)
    out_fire = np.empty((T, N), bool)
    spikers = [np.flatnonzero(raster[t]) for t in range(T)]
    for t in range(T):
        lo, hi = [], []
        for d in delays.tolist():
            if t - d < 0 or spikers[t - d].size == 0:
                continue
            k = spikers[t - d] * (D + 1) + d
            lo.append(ptr[k])
            hi.append(ptr[k + 1])
        arr = (by_pre[_ranges(np.concatenate(lo), np.concatenate(hi))]
               if lo else np.zeros(0, np.int64))
        wa = w[arr].astype(np.float64)
        ex = arr[chan[arr] == 0]
        inh = arr[chan[arr] == 1]
        in_ex = np.bincount(post[ex], weights=wa[chan[arr] == 0],
                            minlength=N).astype(dtype)
        in_in = np.bincount(post[inh], weights=wa[chan[arr] == 1],
                            minlength=N).astype(dtype)
        in_ex = in_ex + (ext_w * events[t].astype(dtype)).astype(dtype)

        v_prop = v * c["p_vv"] + sx * c["p_ve"] + si * c["p_vi"] + \
            c["p_vconst"]
        sx = sx * c["p_ee"] + in_ex
        si = si * c["p_ii"] + in_in
        refr = ref > 0
        v_new = np.where(refr, c["v_reset"], v_prop).astype(dtype)
        out_v[t], out_ref[t] = v_new, refr
        out_fire[t] = ~refr & (v_new >= c["v_th"])
        served = raster[t]
        v = np.where(served, c["v_reset"], v_new).astype(dtype)
        ref = np.where(served, ref_steps, np.maximum(ref - 1, 0))

        if plastic_any:
            pa = arr[e["plastic"][arr]]
            w[pa] = w[pa] - dep * w[pa] * k_post[post[pa]]
            pe = by_post[_ranges(post_ptr[spikers[t]],
                                 post_ptr[spikers[t] + 1])]
            w[pe] = w[pe] + pot * np.maximum(w[pe], tiny) ** mu * \
                k_pre[e["pre"][pe]]
            touched = np.concatenate([pa, pe])
            w[touched] = np.clip(w[touched], w_lo, w_hi)
            arrived_pre = np.zeros(N, bool)
            arrived_pre[e["pre"][arr]] = True
            k_pre = (k_pre * dec_pre + arrived_pre.astype(dtype)).astype(
                dtype)
            k_post = (k_post * dec_post + served.astype(dtype)).astype(dtype)

    return Replay(v_pre=out_v, refractory=out_ref, fires=out_fire, v=v,
                  syn_ex=sx, syn_in=si, w=w)
