"""Uplink NOMA transmission model (paper §II-C) + OMA baseline.

All rate functions take channel power gains ``h2`` sorted in DESCENDING
order — the paper's SIC decoding order (client 1 decoded first, suffering
interference from all later-decoded clients; client N decoded last,
interference-free; Eq. 9).

``bandwidth`` / ``sigma2`` accept plain floats OR traced JAX scalars: the
sweep engine feeds them as ``GamePhysics`` operands (possibly vmapped over
a config axis), so nothing here may branch on their values or treat them
as static.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..kernels.ref import sic_suffix_ref
from .channel import BANDWIDTH_HZ, noise_power

# The TPU v5e computes f32 log/exp with fast approximations by default
# (log off by up to ~7e-5 relative on the chip), so every transcendental of
# the game asks XLA for its most accurate implementation instead; on the
# CPU backend the compiled program is unchanged.
_ACCURATE = jax.lax.AccuracyMode.HIGHEST
_LN2 = math.log(2.0)


def log2_1p(x):
    """``log2(1 + x)`` at the backend's highest accuracy."""
    return jax.lax.log(1.0 + jnp.asarray(x), accuracy=_ACCURATE) / _LN2


def exp2_m1(x):
    """``2 ** x - 1`` at the backend's highest accuracy.  expm1, not
    ``exp2(x) - 1``: the Dinkelbach rate floor (2**x - 1)/F has x far
    below 1 for a client with a loose deadline, where the subtraction
    would cancel all but a few of the significant digits."""
    return jax.lax.expm1(jnp.asarray(x) * _LN2, accuracy=_ACCURATE)


def sic_order(h2):
    """Indices sorting channel gains in descending order (decode order)."""
    return jnp.argsort(-h2)


def noma_rates(p, h2_sorted, bandwidth=BANDWIDTH_HZ, sigma2=None):
    """Achievable rates (bit/s) under SIC, Eq. (9).

    p, h2_sorted: [N] aligned with the descending-gain decode order.
    Interference on client n = sum_{j>n} p_j |h_j|².
    """
    if sigma2 is None:
        sigma2 = noise_power(bandwidth)
    rx = p * h2_sorted
    # exclusive suffix sum: interference from later-decoded clients
    intf = sic_suffix_ref(rx)
    sinr = rx / (intf + sigma2)
    return bandwidth * log2_1p(sinr)


def sum_capacity(p, h2, bandwidth=BANDWIDTH_HZ, sigma2=None):
    """MAC sum capacity B·log2(1 + Σ p|h|²/σ²) — SIC achieves it exactly."""
    if sigma2 is None:
        sigma2 = noise_power(bandwidth)
    return bandwidth * log2_1p(jnp.sum(p * h2) / sigma2)


def oma_rates(p, h2, bandwidth=BANDWIDTH_HZ, sigma2_full=None):
    """Orthogonal baseline: equal bandwidth split B/N, no interference."""
    n = h2.shape[0]
    bw = bandwidth / n
    if sigma2_full is None:
        sigma2_full = noise_power(bandwidth)
    sigma2 = sigma2_full / n           # noise scales with sub-band width
    return bw * log2_1p(p * h2 / sigma2)


def tx_latency(d_bits, rates):
    """Eq. (10)."""
    return d_bits / jnp.maximum(rates, 1e-9)


def tx_energy(p, t_com):
    """Eq. (11)."""
    return p * t_com
