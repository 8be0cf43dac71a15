"""The comparison that decides ``correct`` has to fail: the control (the
plain reference in bfloat16 put in the program's place) at small sizes, and
a whole run, past the look for a chip, with the timed path broken
underneath."""
import json
import time

import ml_dtypes
import numpy as np
import pytest

from bench import compare, run
from fl_small import CONFIG as SMALL_CONFIG, small

ROOT = run.ROOT


def _spec(workload, **traffic):
    spec = run.resolve(workload)
    spec["traffic"] = dict(spec["traffic"], **traffic)
    return spec


CASES = {
    "paper_mc_n5": dict(traffic={"draws_per_call": 64, "pool": 2,
                                 "check_slots": 2}, config={}),
    "paper_serve_n5": dict(traffic={"rate_per_s": 200.0}, config={}),
    "paper_fl_sweep_4chip": dict(traffic=small()[1], config=SMALL_CONFIG),
}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_control_fails_program_passes(workload):
    case = CASES[workload]
    spec = _spec(workload, **case["traffic"])
    config = dict(spec["config"], **{k: v for k, v in case["config"].items()
                                     if k != "solver"})
    config["solver"] = dict(spec["config"]["solver"],
                            **case["config"].get("solver", {}))
    driver = run.load_module(spec["driver"], f"driver_{workload}")
    cell = driver.Cell(config, spec["traffic"], 2 ** 33 + 5, 0.3)
    cell.run(0.3)
    cell.collect()
    ok, rows = compare.judge(cell.check(), spec["limits"])
    assert ok, rows
    ok, rows = compare.judge(cell.check(dtype=ml_dtypes.bfloat16),
                             spec["limits"])
    assert not ok, rows


def _main(monkeypatch, capsys, workload):
    monkeypatch.setattr(run, "device_info", lambda jax, chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    assert run.main(["--workload", workload, "--seed", "9", "--seconds",
                     "0.3", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = _main(monkeypatch, capsys, "paper_mc_n5")
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "solves_per_s"}
    assert 0.0 < out["counters"]["feasible_share"] <= 100.0


def _alter_mc(field, how):
    from repro.core import stackelberg
    real = stackelberg.batched_equilibrium

    def broken(*args, **kw):
        out = real(*args, **kw)
        return out.__class__(**{**vars(out), field: how(getattr(out, field))})
    return stackelberg, broken


@pytest.mark.parametrize("field,how", [
    ("p", lambda p: p.at[0, 0].multiply(1.01)),
    ("energy", lambda e: e * 1.001),
    ("feasible", lambda f: ~f),
    ("p", lambda p: p.at[:].set(0.1)),
    ("f", lambda f: f.at[f.shape[0] // 2:].set(1e10)),
], ids=["power", "energy", "flags", "state_unchanged", "half_batch_left_out"])
def test_altered_answer_is_caught_mc(monkeypatch, capsys, field, how):
    """An answer altered where it is made; the leader's state returned as it
    started (every power at p_max); half of each batch left unsolved (its
    CPU frequencies still at f_max)."""
    mod, broken = _alter_mc(field, how)
    monkeypatch.setattr(mod, "batched_equilibrium", broken)
    assert _main(monkeypatch, capsys, "paper_mc_n5")["correct"] is False


@pytest.mark.parametrize("how", [
    lambda p: p.at[0, 0].multiply(1.01),
    lambda p: p.at[:].set(0.1),
    lambda p: p.at[p.shape[0] // 2:].set(0.1),
], ids=["power", "state_unchanged", "half_batch_left_out"])
def test_altered_answer_is_caught_service(monkeypatch, capsys, how):
    """A power altered where the batch is solved; every power left at the
    leader's start (p_max); half of each dispatched batch left unsolved."""
    from repro.launch import alloc_serve
    real = alloc_serve._serve_batch_jit

    def broken(*args, **kw):
        out = real(*args, **kw)
        return out.__class__(**{**vars(out), "p": how(out.p)})
    monkeypatch.setattr(alloc_serve, "_serve_batch_jit", broken)
    assert _main(monkeypatch, capsys, "paper_serve_n5")["correct"] is False


def _stall_first_dispatch(monkeypatch, seconds):
    """The service's dispatch seam sleeps ``seconds`` before the window's
    first dispatch: the requests of that batch are answered late."""
    real_load = run.load_module

    def load(path, name):
        mod = real_load(path, name)
        if name.startswith("bench_driver_"):
            real_run = mod.Cell.run

            def stalled_run(self, window):
                seam, done = self.svc._dispatch, []

                def stall_once(*args, **kw):
                    if not done:
                        done.append(True)
                        time.sleep(seconds)
                    return seam(*args, **kw)
                self.svc._dispatch = stall_once
                return real_run(self, window)
            monkeypatch.setattr(mod.Cell, "run", stalled_run)
        return mod
    monkeypatch.setattr(run, "load_module", load)


def test_expired_requests_are_not_compared_service(monkeypatch, capsys):
    """One dispatch of the window stalls past the 1 s deadline, so the
    requests of that batch are solved late: ``timeout`` rows that still
    carry an allocation (a row that expired in the queue carries NaN
    arrays and is not compared).  The run stays correct, and the late ones
    count as failed."""
    _stall_first_dispatch(monkeypatch, 1.1)
    out = _main(monkeypatch, capsys, "paper_serve_n5")
    assert out["counters"]["statuses"]["timeout"] > 0, out["counters"]
    assert out["failed"] > 0
    assert out["correct"] is True, out["checks"]


def test_lost_answer_is_caught_service(monkeypatch, capsys):
    from repro.launch import alloc_serve
    real = alloc_serve.AllocationService.drain

    def lossy(self):
        return real(self)[1:]
    monkeypatch.setattr(alloc_serve.AllocationService, "drain", lossy)
    out = _main(monkeypatch, capsys, "paper_serve_n5")
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0
