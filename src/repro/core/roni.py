"""RONI (Reject On Negative Influence) poisoning detection — paper §III-3,
following Biscotti [31].

Each selected client's local update is validated before aggregation: the
server compares validation accuracy of the global aggregate WITH vs WITHOUT
that client's contribution; a drop beyond ``threshold`` marks the update as a
negative interaction (NI) and excludes it from aggregation.

``roni_filter`` is jit-cached on the (hashable) classifier function so the
per-round leave-one-out sweep never retraces (an eager closure here
recompiled the conv evaluation every FL round).  Everything else —
including ``threshold`` — is a traced operand, so the filter inlines into
the scan-compiled trajectory (``fl_round.run_training_scan``) and a
threshold sweep reuses one executable.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from .aggregation import dt_aggregate


def roni_verdict(acc_base, acc_update, threshold):
    """Positive (the update is kept) where it costs the validation accuracy
    at most ``threshold``; a larger drop is a negative interaction."""
    return (acc_base - acc_update) <= threshold


@partial(jax.jit, static_argnames=("logits_fn",))
def roni_filter(client_params, global_params, d_sizes, v, epsilon,
                logits_fn: Callable, x_val, y_val, threshold: float = 0.02):
    """Returns (positive_mask [N] bool, acc_base [N], acc_update [N]).

    Biscotti-style per-update RONI: client n's local model (= global model
    with its update applied) is evaluated on the held-out set against the
    pre-round global model; a drop beyond ``threshold`` marks the update as
    a negative interaction.  (A leave-one-out aggregate comparison carries
    ≈1/N of this signal and was empirically too weak to fire — see
    EXPERIMENTS.md §Paper-validation.)
    """
    n = d_sizes.shape[0]

    def acc(params):
        logits = logits_fn(params, x_val)
        return jnp.mean((jnp.argmax(logits, -1) == y_val).astype(jnp.float32))

    acc_base = acc(global_params)
    acc_update = jax.vmap(
        lambda i: acc(jax.tree_util.tree_map(lambda c: c[i], client_params))
    )(jnp.arange(n))
    positive = roni_verdict(acc_base, acc_update, threshold)
    return positive, jnp.full((n,), acc_base), acc_update
