"""``correct`` at a size a CPU test holds: sound runs pass, and the control
(the reference in bfloat16 in the program's place) and each fault the
one-chip cells can have (a step that returns its state unchanged, half
of the neurons left out, a spike altered where it is produced) fail.
The harness's look for a chip is skipped; the rest of a run is the
benchmark's own."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

import tiny
from bench import calibrate
from bench import run as run_mod
from bench.registry import Registry
from repro.core import engine, snn

SECONDS = 0.3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed):
    return run_mod.run(cell, seed, SECONDS, False, jax.devices(), root=root)


@pytest.mark.parametrize("cell", ["hpc-stdp-tiny", "hpc-static-tiny"])
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell, 2**31 + 3)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


@pytest.mark.parametrize("cell", ["hpc-stdp-tiny", "hpc-static-tiny"])
def test_control_fails(root, cell):
    """The bfloat16 control exceeds a limit on every seed."""
    reg = Registry(root)
    limits = reg.workload(cell)["limits"]
    for rec in calibrate.calibrate(reg, cell, [11, 12, 13], SECONDS,
                                   jax.devices()):
        assert all(rec["program"][k] <= limits[k] for k in limits)
        assert any(rec["control"][k] > limits[k] for k in rec["control"])


def _unchanged(real):
    def step(state, *a, **k):
        _, bits = real(state, *a, **k)
        return state, bits
    return step


def _half_left_out(real):
    def step(state, *a, **k):
        new, bits = real(state, *a, **k)
        n = state.neurons.v_m.shape[0]
        keep = jnp.arange(n) < n // 2
        pick = lambda a_new, a_old: jnp.where(keep, a_new, a_old)
        neurons = dataclasses.replace(
            new.neurons, v_m=pick(new.neurons.v_m, state.neurons.v_m),
            syn_ex=pick(new.neurons.syn_ex, state.neurons.syn_ex),
            syn_in=pick(new.neurons.syn_in, state.neurons.syn_in))
        return dataclasses.replace(new, neurons=neurons), bits
    return step


def _spike_altered(real):
    def lif_step(state, *a, **k):
        out = real(state, *a, **k)
        return dataclasses.replace(out, spike=out.spike.at[0].set(
            ~out.spike[0]))
    return lif_step


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out",
                                   "spike_altered"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    if fault == "spike_altered":
        monkeypatch.setattr(snn, "lif_step", _spike_altered(snn.lif_step))
    else:
        wrap = {"unchanged": _unchanged, "half_left_out": _half_left_out}
        monkeypatch.setattr(engine, "engine_step",
                            wrap[fault](engine.engine_step))
    res = _run(root, "hpc-stdp-tiny", 5)
    assert not res["correct"], res["compared"]
    assert res["failed"] > 0
