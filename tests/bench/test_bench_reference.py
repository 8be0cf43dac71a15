"""The benchmark's inputs and its plain reference: the channel copy against
its own documented formula, the reference against the paper's closed forms,
and the program against the reference on the CPU at small sizes."""
import json

import numpy as np
import pytest

import jax

from bench import compare, inputs, reference
from bench.run import ROOT

CONFIG = json.loads((ROOT / "bench/configs/paper_n5.json").read_text())
PHYS = inputs.physics(CONFIG)
LIMITS = json.loads((ROOT / "bench/checks/paper_mc_n5.json").read_text())["limits"]


def test_noise_power():
    assert PHYS["sigma2"] == pytest.approx(10 ** (-20.4) * 1e6)


def test_channel_follows_its_formula():
    """h2 = G0·r^-alpha·|g|^2, r = R·sqrt(U), |g|^2 ~ Exp(1): E[ln h2] =
    ln G0 - alpha(ln R - 1/2) - gamma, Var = alpha^2/4 + pi^2/6 (the 1 m
    floor touches r < 1 m only, a share 4e-6 of the draws)."""
    ch = CONFIG["channel"]
    h2 = np.asarray(inputs.channel_gains(inputs.prng_key(7), (20000, 5), ch),
                    np.float64)
    assert np.all(np.diff(h2, axis=1) <= 0), "rows descending (SIC order)"
    a = ch["pathloss_exp"]
    mean = np.log(ch["ref_gain"]) - a * (np.log(ch["cell_radius_m"]) - 0.5) \
        - np.euler_gamma
    var = a * a / 4 + np.pi ** 2 / 6
    logs = np.log(h2).ravel()
    assert abs(logs.mean() - mean) < 5 * np.sqrt(var / logs.size)
    assert logs.var() == pytest.approx(var, rel=0.05)


def test_draws_ranges_and_seed():
    h2, d, v = inputs.client_draws(inputs.prng_key(2 ** 40 + 3), (64, 5), CONFIG)
    assert float(d.min()) >= 100 and float(d.max()) <= 300
    assert float(v.min()) >= 0.3 and float(v.max()) <= 0.8
    again = inputs.client_draws(inputs.prng_key(2 ** 40 + 3), (64, 5), CONFIG)
    assert np.array_equal(np.asarray(h2), np.asarray(again[0]))
    other = inputs.client_draws(inputs.prng_key(2 ** 40 + 4), (64, 5), CONFIG)
    assert not np.array_equal(np.asarray(h2), np.asarray(other[0]))


@pytest.fixture(scope="module")
def draws():
    h2, d, v = inputs.client_draws(inputs.prng_key(11), (256, 5), CONFIG)
    return [np.asarray(x, np.float64) for x in (h2, d, v)]


def test_reference_closed_forms(draws):
    h2, d, v = draws
    ref = reference.equilibrium(h2, d, v, PHYS)
    ok = ref["feasible"]
    assert ok.mean() > 0.5
    # Sec. V-B-2: f runs to the deadline or sits on its box
    f = ref["f"]
    assert np.all((f >= PHYS["f_min"] * (1 - 1e-12))
                  & (f <= PHYS["f_max"] * (1 + 1e-12)))
    finish = ref["t_cmp"] + ref["t_com"]
    inner = (f > PHYS["f_min"] * 1.0001) & (f < PHYS["f_max"] * 0.9999)
    assert np.allclose(finish[inner], PHYS["t_max"], rtol=1e-9)
    # Theorem 1: alpha_n = c·D^_n / (T·f_S) while the shares fit
    load = PHYS["cycles_per_sample"] * v * d
    share = load / (ref["t_total"][:, None] * PHYS["f_server"])
    fits = share.sum(axis=1) <= 1.0
    assert np.allclose(ref["alpha"][fits], share[fits], rtol=1e-9)
    # powers in the box, rates from the SIC interference of later clients
    assert np.all((ref["p"] >= PHYS["p_min"] * (1 - 1e-12))
                  & (ref["p"] <= PHYS["p_max"] * (1 + 1e-12)))
    rx = ref["p"] * h2
    later = np.cumsum(rx[:, ::-1], axis=1)[:, ::-1] - rx
    rate = PHYS["bandwidth"] * np.log2(1 + rx / (later + PHYS["sigma2"]))
    assert np.allclose(ref["rates"], rate, rtol=1e-9)


def test_dinkelbach_minimises_upload_energy(draws):
    """The last client (no interference) on a grid of feasible powers: no
    power on the grid uploads for less energy than the reference's."""
    h2, d, v = draws
    ref = reference.equilibrium(h2[:16], d[:16], v[:16], PHYS)
    f_eff = h2[:16, -1] / PHYS["sigma2"]
    grid = np.linspace(PHYS["p_min"], PHYS["p_max"], 4001)[None, :]
    rate = PHYS["bandwidth"] * np.log2(1 + grid * f_eff[:, None])
    slack = PHYS["t_max"] - ref["t_cmp"][:, -1:]
    ok = rate >= PHYS["model_bits"] / np.maximum(slack, 1e-3)
    energy = np.where(ok, grid * PHYS["model_bits"] / rate, np.inf)
    p = ref["p"][:, -1]
    mine = p * PHYS["model_bits"] / (PHYS["bandwidth"] * np.log2(1 + p * f_eff))
    assert np.all(mine <= energy.min(axis=1) * (1 + 1e-6))


def _program(cfg_kw, h2, d, v):
    from repro.core.stackelberg import GameConfig, batched_equilibrium
    cfg = GameConfig(**PHYS, **cfg_kw)
    out = jax.device_get(batched_equilibrium(cfg, h2.astype(np.float32),
                                             d.astype(np.float32),
                                             v.astype(np.float32)))
    return {f: getattr(out, f) for f in ("p", "f", "alpha", "t_total",
                                         "energy", "feasible")}


def test_program_matches_reference_n5(draws):
    h2, d, v = (np.asarray(x, np.float32).astype(np.float64) for x in draws)
    got = _program({}, h2, d, v)
    nums = compare.allocation_numbers(got, reference.equilibrium(h2, d, v, PHYS),
                                      PHYS["t_max"])
    ok, rows = compare.judge(nums, LIMITS)
    assert ok, rows


def test_program_blocked_engine_matches_reference():
    """The blocked Jacobi engine (jnp suffix sums on the CPU) at N = 300."""
    h2, d, v = inputs.client_draws(inputs.prng_key(12), (4, 300), CONFIG)
    h2, d, v = (np.asarray(x, np.float64) for x in (h2, d, v))
    got = _program({"sic_mode": "blocked"}, h2, d, v)
    nums = compare.allocation_numbers(got, reference.equilibrium(h2, d, v, PHYS),
                                      PHYS["t_max"])
    limits = dict(LIMITS, alpha_cond=1e-5, t_total_cond=1e-5, f_cond=1e-5,
                  energy_cond=1e-5)
    ok, rows = compare.judge(nums, limits)
    assert ok, rows
