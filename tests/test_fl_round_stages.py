"""The classifier's FL round after the client-model split of
``fl_round._round_body``: its clients are still trained all at once
(``local_train_all``), never by the scanned path, the round traces once per
shape, and its compiled program carries the round stages' scopes
(``fl.local_sgd``, ``fl.twin_sgd``, ``fl.roni``, ``fl.aggregate``) beside
the game's ``sic_power``, and no language-model scope."""
import jax
import pytest

from repro.core import fl_round
from repro.core.channel import sample_positions
from repro.core.digital_twin import DTConfig, sample_v_max
from repro.core.fl_round import FLConfig, FLState, stack_states
from repro.core.reputation import init_reputation
from repro.core.stackelberg import GameConfig, TRACE_COUNTS
from repro.data.federated import make_federated_data
from repro.data.synthetic import SYNTHETIC_MNIST
from repro.models.classifier import make_classifier


def test_classifier_round_keeps_its_program(monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    m = 7
    data = make_federated_data(ks[0], SYNTHETIC_MNIST, m=m, cap=20,
                               poison_ratio=0.3)
    params, logits_fn = make_classifier("mlp", ks[1], in_dim=784, hidden=12)
    state = FLState(params=params, rep=init_reputation(m),
                    v_max=sample_v_max(ks[2], m, DTConfig()),
                    distances=sample_positions(ks[3], m), key=ks[4])

    def never(*a, **k):
        raise AssertionError("the classifier's clients were scanned")
    monkeypatch.setattr(fl_round, "_train_clients_scanned", never)
    calls, seen = [], []
    real_local, real_jit = fl_round.local_train_all, fl_round._sweep_training_jit
    monkeypatch.setattr(fl_round, "local_train_all",
                        lambda *a: (calls.append(1), real_local(*a))[1])

    class Recorded(Exception):
        pass

    def record(*a, **k):
        seen.append((a, k))
        raise Recorded
    monkeypatch.setattr(fl_round, "_sweep_training_jit", record)
    fl = FLConfig(n_selected=3, local_steps=2, server_steps=2)
    with pytest.raises(Recorded):
        fl_round.sweep_training(stack_states([state]), data, fl,
                                GameConfig(), logits_fn, rounds=2)
    (args, kwargs), = seen
    before = TRACE_COUNTS["run_round"]
    text = real_jit.lower(*args, **kwargs).compile().as_text()
    assert TRACE_COUNTS["run_round"] - before == 1 and calls
    for scope in ("fl.local_sgd", "fl.twin_sgd", "fl.roni", "fl.aggregate",
                  "sic_power"):
        assert scope in text, scope
    assert "lm." not in text
