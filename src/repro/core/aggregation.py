"""Global model aggregation with DT assistance (paper Eq. 3)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def eq3_weights(d_sizes, v, epsilon, include_mask=None,
                server_include=None):
    """Eq. (3)'s weights: (w_local [N], w_server, D), each client's local
    term (1−v_n)·D_n, the twin's Σ_n (v_n·D_n + ε) and the divisor D =
    Σ_n D_n.

    include_mask  : optional [N] bool — RONI-excluded clients drop their
                    *local* term.
    server_include: optional scalar bool — RONI verdict on the DT-side
                    update itself (the twin mirrors poisoned data too).
    Excluded mass leaves the divisor — otherwise every exclusion uniformly
    shrinks the aggregate toward zero."""
    d_total = jnp.sum(d_sizes)
    w_local = (1.0 - v) * d_sizes
    if include_mask is not None:
        inc = include_mask.astype(w_local.dtype)
        d_total = d_total - jnp.sum(w_local * (1.0 - inc))
        w_local = w_local * inc
    w_server = jnp.sum(v * d_sizes + epsilon)
    if server_include is not None:
        s_inc = jnp.asarray(server_include, w_local.dtype)
        d_total = d_total - w_server * (1.0 - s_inc)
        w_server = w_server * s_inc
    return w_local, w_server, d_total


def dt_aggregate(client_params, server_params, d_sizes, v, epsilon: float,
                 include_mask=None, server_include=None):
    """Eq. (3):

        w = (1/D) Σ_n [ (1−v_n)·D_n·w_n + (v_n·D_n + ε)·w_S ]

    client_params : pytree stacked over clients on axis 0 ([N, ...] leaves)
    server_params : pytree (the DT-side model w_S)
    d_sizes, v    : [N]
    include_mask, server_include: the RONI verdicts (``eq3_weights``).

    All-excluded rounds stay finite (zero numerator over the clamped
    divisor → a zero tree, never NaN): the scanned trajectory
    (``fl_round.run_training_scan``) computes the aggregate
    unconditionally and keeps the previous global model via ``jnp.where``,
    so this function must be safe to evaluate on empty include masks.
    """
    w_local, w_server, d_total = eq3_weights(d_sizes, v, epsilon,
                                             include_mask, server_include)

    def agg(cl, sv):
        shape = (-1,) + (1,) * (cl.ndim - 1)
        return (jnp.sum(cl * w_local.reshape(shape), axis=0)
                + w_server * sv) / jnp.maximum(d_total, 1e-9)

    return jax.tree_util.tree_map(agg, client_params, server_params)


def fedavg_weights(d_sizes, include_mask=None):
    """FedAvg's weights: (w [N] = D_n of the included clients, their sum)."""
    w = d_sizes
    if include_mask is not None:
        w = w * include_mask.astype(w.dtype)
    return w, jnp.sum(w)


def fedavg(client_params, d_sizes, include_mask=None):
    """Plain FedAvg (the W/O-DT baseline's aggregation)."""
    w, total = fedavg_weights(d_sizes, include_mask)
    w = w / jnp.maximum(total, 1e-12)

    def agg(cl):
        shape = (-1,) + (1,) * (cl.ndim - 1)
        return jnp.sum(cl * w.reshape(shape), axis=0)

    return jax.tree_util.tree_map(agg, client_params)
