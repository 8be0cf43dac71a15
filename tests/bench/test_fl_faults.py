"""A whole run of the FL training cell, past the look for a chip, at the
small size: sound it is correct; with the timed path broken underneath it
is not.  The faults are planted in the program's round: the local learning
rate doubled (scaled by 1.01 it moves the parameters' change by less than
sound runs on a TPU v5e differ from the reference), the RONI threshold
moved down by 0.04 (moved by 0.01 it takes none or a few of the checked
verdicts the other way, too few to tell from the drift of a sound run:
PERF.md, Findings), Eq. (3)'s weights taken without v, one round's update
dropped, every round's update dropped (the state returned unchanged), half
of each client's samples left out of its local steps (the mean taken over
the rest).  The grid points share nothing across chips, so there is no
exchange to leave out."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from fl_small import CELL, small_resolve
from repro.core import fl_round


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test traces the round anew: a planted fault must not reach a
    compiled program that another test reuses."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _main(monkeypatch, capsys):
    monkeypatch.setattr(run, "resolve", small_resolve(run.resolve))
    monkeypatch.setattr(run, "device_info", lambda jax, chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    assert run.main(["--workload", CELL, "--seed", str(2 ** 33 + 7),
                     "--seconds", "0.3", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = _main(monkeypatch, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "rounds_per_s"}
    window = out["compiles_in_window"]
    assert window["compiles"] == window["engine_traces"] == 0, window
    assert 0.0 <= out["counters"]["fl_update_waste"] <= 100.0
    assert out["counters"]["fl_flops"] > 0


def _local_lr(scale):
    real = fl_round.local_train_all
    return "local_train_all", lambda f, p, x, y, w, steps, lr: real(
        f, p, x, y, w, steps, lr * scale)


def _half_batch():
    real = fl_round.local_train_all
    return "local_train_all", lambda f, p, x, y, w, steps, lr: real(
        f, p, x, y, w.at[:, w.shape[1] // 2:].set(0.0), steps, lr)


def _roni_threshold(shift):
    real = fl_round._fl_ops
    return "_fl_ops", lambda fl, dtype: dict(
        real(fl, dtype),
        roni_threshold=real(fl, dtype)["roni_threshold"] + shift)


def _aggregate_without_v():
    real = fl_round.dt_aggregate
    return "dt_aggregate", lambda c, s, d, v, eps, **kw: real(
        c, s, d, jnp.zeros_like(v), eps, **kw)


def _updates_dropped(which):
    real = fl_round._round_body

    def body(state, *args, **kw):
        new, metrics = real(state, *args, **kw)
        drop = which(state.round)
        params = jax.tree_util.tree_map(lambda old, upd: jnp.where(
            drop, old, upd), state.params, new.params)
        return dataclasses.replace(new, params=params), metrics
    return "_round_body", body


@pytest.mark.parametrize("fault", [
    lambda: _local_lr(2.0),
    lambda: _roni_threshold(-0.04),
    _aggregate_without_v,
    lambda: _updates_dropped(lambda r: r == 1),
    lambda: _updates_dropped(lambda r: True),
    _half_batch,
], ids=["local_lr_doubled", "roni_threshold_moved", "aggregate_without_v",
        "one_round_skipped", "state_unchanged", "half_batch_left_out"])
def test_planted_fault_is_caught(monkeypatch, capsys, fault):
    name, broken = fault()
    monkeypatch.setattr(fl_round, name, broken)
    assert _main(monkeypatch, capsys)["correct"] is False
