"""The language-model FL cell (``nemotron3_nano_fl_s4096``) at the small
size of ``lm_small.py``, through ``bench/run.py`` past the look for a chip:
a sound run is correct, with no compilation in its window and every expert
assignment computed; with the program broken underneath it is not.  The
faults are planted in the program: the routed scale 2.5 left out of the
expert weights, the correction bias used in the weights (not only in the
choice), the Mamba2 conv reading one future token, and one client's update
left out of Eq. (3) (its term and its weight), half of each step's
minibatch left out of the loss, and a NaN in one weight of the last
layer's attention output of the aggregate.  The FLOP count is checked
by hand, and the classifier's round keeps its program."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run, work_lm
from lm_small import CELL, small, small_resolve
from repro.core import fl_round
from repro.models import nemotron_h


@pytest.fixture(autouse=True)
def fresh_round():
    """Each test traces the round anew: a planted fault must not reach a
    compiled round that another test reuses (the reference's compiled
    steps are shared)."""
    fl_round._sweep_training_jit.clear_cache()
    yield
    fl_round._sweep_training_jit.clear_cache()


def _main(monkeypatch, capsys):
    monkeypatch.setattr(run, "resolve", small_resolve(run.resolve))
    monkeypatch.setattr(run, "device_info", lambda jax, chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    assert run.main(["--workload", CELL, "--seed", str(2 ** 33 + 7),
                     "--seconds", "0.2", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = _main(monkeypatch, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "rounds_per_s"}
    window = out["compiles_in_window"]
    assert window["compiles"] == window["engine_traces"] == 0, window
    c = out["counters"]
    assert c["tokens_dropped"] == 0 and c["moe_load_max_ratio"] >= 1.0
    assert out["checks"]["tokens_dropped"]["value"] == 0
    assert c["fl_flops"] > 0 and 0.0 <= c["fl_update_waste"] <= 100.0


def _route(**changes):
    real = nemotron_h.route

    def route(logits, bias, cfg):
        if changes.get("bias_in_weights"):
            s = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(s + bias, cfg.top_k)
            w = jnp.take_along_axis(s + bias, idx, axis=1)
            return idx, w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scale
        return real(logits, bias, dataclasses.replace(cfg, **changes))
    return nemotron_h, "route", route


def _conv_sees_the_next_token():
    real = nemotron_h.causal_conv

    def conv(xbc, w, b):
        ahead = jnp.concatenate([xbc[:, 1:], xbc[:, -1:]], axis=1)
        return real(ahead, w, b)
    return nemotron_h, "causal_conv", conv


def _one_update_left_out():
    real = fl_round._train_clients_scanned

    def train(model, params, data, xs, ys_train, local_w, map_mask, meets,
              *args):
        return real(model, params, data, xs, ys_train, local_w, map_mask,
                    meets.at[0].set(False), *args)
    return fl_round, "_train_clients_scanned", train


def _half_the_batch_left_out():
    real = nemotron_h.loss

    def loss(params, x, y, w, cfg):
        half = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return real(params, x, y, w * half, cfg)
    return nemotron_h, "loss", loss


def _nan_in_a_late_leaf():
    real = fl_round._train_clients_scanned

    def train(*args):
        params, positive, extra = real(*args)
        layers = list(params["layers"])
        layers[-1] = dict(layers[-1],
                          wo=layers[-1]["wo"].at[0, 0].set(jnp.nan))
        return (dict(params, layers=type(params["layers"])(layers)),
                positive, extra)
    return fl_round, "_train_clients_scanned", train


@pytest.mark.parametrize("fault", [
    lambda: _route(routed_scale=1.0),
    lambda: _route(bias_in_weights=True),
    _conv_sees_the_next_token,
    _one_update_left_out,
    _half_the_batch_left_out,
], ids=["routed_scale_left_out", "bias_in_weights", "conv_sees_ahead",
        "one_update_left_out", "half_the_batch_left_out"])
def test_planted_fault_is_caught(monkeypatch, capsys, fault):
    module, name, broken = fault()
    monkeypatch.setattr(module, name, broken)
    assert _main(monkeypatch, capsys)["correct"] is False


def test_nan_in_a_late_weight_is_caught(monkeypatch, capsys):
    """A NaN in one weight of the aggregate, in a leaf that comes after
    finite ones (the last layer's attention output): every round of the
    window fails and the parameters' gap reads infinite, not the largest
    finite leaf's."""
    module, name, broken = _nan_in_a_late_leaf()
    monkeypatch.setattr(module, name, broken)
    out = _main(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] > 0
    assert out["checks"]["param_change_gap"]["value"] == float("inf")


def test_flop_count_by_hand():
    """One token through the cell's 7 layers, forward, counted by hand."""
    mc = run.resolve(CELL)["config"]
    d, din, conv = 2688, 4096, 4096 + 2 * 8 * 128
    mamba = 2 * (d * (din + conv + 64) + din * d) + 4 * 64 * 64 * 128
    moe = 2 * (d * 128 + 6 * 8 / 128 * 2 * d * 1856 + 2 * d * 3712)
    attn = 2 * d * 128 * (2 * 32 + 2 * 2) + 4 * 32 * 128 * 4097 / 2
    want = 3 * mamba + 3 * moe + attn + 2 * d * 16384
    assert work_lm.forward_per_token(mc, 4096) == pytest.approx(want)
    assert work_lm.round_flops(mc, 4096, 10, 4) == pytest.approx(34 * want)
    config, traffic = small()
    assert config["hybrid_override_pattern"] == "ME*"
