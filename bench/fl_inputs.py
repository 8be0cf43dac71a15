"""Every input of an FL training cell, made from ``--seed``.

The benchmark's own copy of the paper's FL protocol (Sec. VI), so that a
change to the program's data or model code cannot move the yardstick.  Per
seed of the grid:

  * a synthetic MNIST proxy: ten class means drawn on a sphere of radius
    ``class_sep``; each of the M clients holds D_n = floor(lo + (hi-lo)U)
    samples, [lo, hi] the configuration's ``data_samples``, in ceil(hi)
    slots, x = mu_y + noise·g, IID labels (or, non-IID, labels from
    ``labels_per_client`` classes of its own); the slots past D_n are padding;
  * label-flip poisoning: round(ratio·M) clients, chosen at random, train on
    y -> 9 - y; a clean validation set of ``val_size`` samples;
  * client positions uniform in the cell's disc (r = R·sqrt(U), floored at
    ``min_distance_m``) and insensitive fractions v_max ~ U[v_lo, v_hi];
  * the client MLP's initial weights, LeCun-normal (std 1/sqrt(fan in)),
    biases zero, and the reputation's prior (staleness 1, one positive
    interaction, no negative one);
  * the key that the program's rounds split, as raw threefry key data.

Everything is drawn on the device in one jitted call.  The driver only
wraps these arrays in the program's ``FedData`` and ``FLState``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

CLASSES = 10


def mlp_shapes(dim: int, hidden: int, classes: int = CLASSES) -> list:
    """[(name, fan_in, fan_out)] of the two-hidden-layer ReLU MLP; its
    weights are ``w<name>`` and ``b<name>``, the names the program's MLP
    reads."""
    return [("1", dim, hidden), ("2", hidden, hidden), ("3", hidden, classes)]


def _one_seed(key, m, n_poison, channel, v_range, d_range, data, hidden):
    ks = jax.random.split(key, 16)
    dim, (lo, hi) = data["dim"], d_range
    cap = math.ceil(hi)
    mu = jax.random.normal(ks[0], (CLASSES, dim))
    mu = data["class_sep"] * mu / jnp.linalg.norm(mu, axis=1, keepdims=True)
    sizes = jnp.floor(lo + (hi - lo) * jax.random.uniform(ks[1], (m,)))
    mask = jnp.arange(cap)[None, :] < sizes[:, None]
    if data["iid"]:
        y = jax.random.randint(ks[2], (m, cap), 0, CLASSES)
    else:
        own = jax.random.randint(ks[2], (m, data["labels_per_client"]), 0,
                                 CLASSES)
        pick = jax.random.randint(ks[3], (m, cap), 0,
                                  data["labels_per_client"])
        y = jnp.take_along_axis(own, pick, axis=1)
    x = mu[y] + data["noise"] * jax.random.normal(ks[4], (m, cap, dim))
    poisoned = jnp.zeros((m,), bool).at[
        jax.random.permutation(ks[5], m)[:n_poison]].set(True)
    y_train = jnp.where(poisoned[:, None], CLASSES - 1 - y, y)
    y_val = jax.random.randint(ks[6], (data["val_size"],), 0, CLASSES)
    x_val = mu[y_val] + data["noise"] * jax.random.normal(
        ks[7], (data["val_size"], dim))
    r = channel["cell_radius_m"] * jnp.sqrt(jax.random.uniform(ks[8], (m,)))
    distances = jnp.maximum(r, channel["min_distance_m"])
    v_max = jax.random.uniform(ks[9], (m,), minval=v_range[0],
                               maxval=v_range[1])
    params = {}
    for i, (name, fan_in, fan_out) in enumerate(mlp_shapes(dim, hidden)):
        params["w" + name] = (jax.random.normal(ks[10 + i], (fan_in, fan_out))
                              / jnp.sqrt(float(fan_in)))
        params["b" + name] = jnp.zeros((fan_out,))
    return {"x": x, "y": y, "y_train": y_train, "mask": mask,
            "sizes": sizes, "poisoned": poisoned, "x_val": x_val,
            "y_val": y_val, "distances": distances, "v_max": v_max,
            "params": params,
            "ms": jnp.ones((m,)), "pi_count": jnp.ones((m,)),
            "ni_count": jnp.zeros((m,)),
            "key": jax.random.key_data(ks[13])}


@partial(jax.jit, static_argnames=("seeds", "m", "n_poison", "channel",
                                   "v_range", "d_range", "data", "hidden"))
def _make(key, seeds, m, n_poison, channel, v_range, d_range, data, hidden):
    one = partial(_one_seed, m=m, n_poison=n_poison, channel=dict(channel),
                  v_range=v_range, d_range=d_range, data=dict(data),
                  hidden=hidden)
    return jax.vmap(one)(jax.random.split(key, seeds))


def grid_inputs(key, seeds: int, config: dict, traffic: dict) -> dict:
    """The inputs of ``seeds`` seeds, each array with a leading [S] axis."""
    data = dict(traffic["data"])
    data.setdefault("labels_per_client", 1)
    m = int(config["clients_total"])
    return _make(key, seeds=int(seeds), m=m,
                 n_poison=int(round(float(data.pop("poison_ratio")) * m)),
                 channel=tuple(sorted(config["channel"].items())),
                 v_range=tuple(config["v_max"]),
                 d_range=tuple(config["data_samples"]),
                 data=tuple(sorted(data.items())),
                 hidden=int(traffic["model"]["hidden"]))
