"""The hpc_benchmark configuration's network table follows from NEST's
published parameters (the file's ``published``) by the rules the file
states under ``translated`` and ``reduced``: the scale cuts the neuron
count and keeps the indegree, and the weights and the drive keep the
published PSP peak, its spread and eta for exponential PSCs."""

import json
import math

import pytest
from scipy.special import lambertw

import tiny

DATA = json.loads((tiny.REPO / "bench" / "configs" /
                   "hpc_benchmark.json").read_text())
PUB = DATA["published"]
NET = DATA["network"]


def _alpha_pA_per_mV():
    """NEST's convert_synapse_weight for alpha PSCs."""
    tm, ts, c = PUB["tau_m"], PUB["tau_syn_ex"], PUB["C_m"]
    a, b = tm / ts, 1.0 / ts - 1.0 / tm
    t = 1.0 / b * (-lambertw(-math.exp(-1.0 / a) / a, k=-1).real - 1.0 / a)
    v = math.e / (ts * c * b) * ((math.exp(-t / tm) - math.exp(-t / ts)) / b
                                 - t * math.exp(-t / ts))
    return 1.0 / v, t


def _exp_pA_per_mV():
    tm, ts, c = PUB["tau_m"], PUB["tau_syn_ex"], PUB["C_m"]
    t = ts * tm / (tm - ts) * math.log(tm / ts)
    return 1.0 / (ts * tm / (c * (tm - ts))
                  * (math.exp(-t / tm) - math.exp(-t / ts)))


def test_nest_rise_time():
    """tau_syn is the one NEST picked for its 1.700759 ms rise time."""
    per_mv, t_rise = _alpha_pA_per_mV()
    assert t_rise == pytest.approx(1.700759, abs=1e-6)
    assert PUB["JE"] * per_mv == pytest.approx(45.6096, abs=1e-4)


def test_sizes_cut_neurons_not_indegree():
    ne = int(PUB["scale_1_NE"] * DATA["scale"])
    ni = int(PUB["scale_1_NI"] * DATA["scale"])
    assert [p["n"] for p in NET["populations"]] == [ne, ni]
    ce, ci = PUB["scale_1_NE"], PUB["scale_1_NI"]
    assert [p["indegree"] for p in NET["projections"]] == [ce, ce, ci, ci]
    assert NET["areas"][0]["n_neurons"] == ne + ni
    delay = round(PUB["delay"] / PUB["dt"])
    assert all(p["delay_min"] == p["delay_max"] == delay
               for p in NET["projections"])
    assert NET["max_delay"] == delay + 1


def test_weights_and_drive_keep_the_published_values():
    j = PUB["JE"] * _exp_pA_per_mV()
    j_alpha = PUB["JE"] * _alpha_pA_per_mV()[0]
    ee, ei, ie, ii = NET["projections"]
    assert ee["weight_mean"] == pytest.approx(j, rel=1e-12)
    assert ei["weight_mean"] == pytest.approx(j, rel=1e-12)
    assert ie["weight_mean"] == ii["weight_mean"] == pytest.approx(
        PUB["g"] * j, rel=1e-12)
    assert ee["weight_std"] == pytest.approx(
        PUB["sigma_w"] * j / j_alpha, rel=1e-12)
    assert ei["weight_std"] == ie["weight_std"] == ii["weight_std"] == 0.0
    assert [p["plastic"] for p in NET["projections"]] == [True] + [False] * 3
    ce = PUB["scale_1_NE"]
    nu_thresh = PUB["V_th"] / (ce * PUB["tau_m"] / PUB["C_m"] * j
                               * PUB["tau_syn_ex"])
    rate = PUB["eta"] * nu_thresh * ce * 1000.0
    for p in NET["populations"]:
        assert p["ext_rate_hz"] == pytest.approx(rate, rel=1e-12)
        assert p["ext_weight"] == pytest.approx(j, rel=1e-12)


def test_neuron_stdp_and_v_init_are_published():
    (g,) = NET["groups"]
    assert (g["tau_m"], g["c_m"], g["t_ref"]) == (
        PUB["tau_m"], PUB["C_m"], PUB["t_ref"])
    assert g["v_th"] - g["e_l"] == PUB["V_th"] - PUB["E_L"]
    assert g["v_reset"] - g["e_l"] == PUB["V_reset"] - PUB["E_L"]
    assert g["tau_syn_ex"] == g["tau_syn_in"] == PUB["tau_syn_ex"]
    s, ps = DATA["stdp"], PUB["stdp_params"]
    assert (s["lam"], s["alpha"], s["mu"], s["tau_plus"]) == (
        ps["lambda"], ps["alpha"], ps["mu"], ps["tau_plus"])
    assert s["tau_minus"] == PUB["tau_minus"]
    assert s["w0"] == 1.0 and s["w_min"] == 0.0
    assert DATA["v_init"] == {"mean_mV": PUB["mean_potential"] + g["e_l"],
                              "std_mV": PUB["sigma_potential"]}
