"""Every input of a run, made from ``--seed``.

The channel model is the benchmark's own copy of the paper's (Sec. VI), so
that a change to the program's channel code cannot move the yardstick:

  * each client is dropped uniformly in a disc of radius R around the base
    station, r = R·sqrt(U), floored at ``min_distance_m``;
  * its power gain is |h|^2 = G0 · r^(-alpha) · |g|^2, with Rayleigh fading,
    |g|^2 ~ Exp(1);
  * each row of a batch is sorted in descending order, the SIC decoding
    order.

Data sizes D ~ U[D_lo, D_hi] and insensitive fractions v_max ~ U[v_lo, v_hi]
are drawn per client.  Everything is drawn on the device in one jitted call.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


def prng_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size, 64 bits and more included."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def noise_power(noise_dbm_per_hz: float, bandwidth: float) -> float:
    """AWGN power in watts over ``bandwidth`` Hz."""
    return 10.0 ** ((noise_dbm_per_hz - 30.0) / 10.0) * bandwidth


def physics(config: dict) -> dict:
    """The game's physics scalars, noise power included."""
    ph = dict(config["physics"])
    ph["sigma2"] = noise_power(ph.pop("noise_dbm_per_hz"), ph["bandwidth"])
    return ph


def channel_gains(key, shape, channel: dict):
    """|h|^2 of ``shape`` = (..., N) clients, each row sorted descending."""
    k_pos, k_fade = jax.random.split(key)
    r = channel["cell_radius_m"] * jnp.sqrt(jax.random.uniform(k_pos, shape))
    r = jnp.maximum(r, channel["min_distance_m"])
    fading = jax.random.exponential(k_fade, shape)
    h2 = channel["ref_gain"] * r ** (-channel["pathloss_exp"]) * fading
    return -jnp.sort(-h2, axis=-1)


@partial(jax.jit, static_argnames=("shape", "channel", "d_range", "v_range"))
def _draw(key, shape, channel, d_range, v_range):
    k_h, k_d, k_v = jax.random.split(key, 3)
    h2 = channel_gains(k_h, shape, dict(channel))
    d = jax.random.uniform(k_d, shape, minval=d_range[0], maxval=d_range[1])
    v = jax.random.uniform(k_v, shape, minval=v_range[0], maxval=v_range[1])
    return h2, d, v


def client_draws(key, shape, config: dict):
    """(h2, D, v_max) of ``shape`` = (..., N), on the default device."""
    return _draw(key, tuple(shape), tuple(sorted(config["channel"].items())),
                 tuple(config["data_samples"]), tuple(config["v_max"]))
