"""Every input of the FL cell of a language model, made from ``--seed``.

The benchmark's own copy of the protocol, so that a change to the program's
data or model code cannot move the yardstick.  Per seed of the grid:

  * token sequences from a sparse Markov source over the vocabulary the
    model holds, shared by every client (IID): each token has ``successors``
    next tokens, drawn with Dirichlet(1) probabilities; a sequence starts
    at a uniform token.  A row holds ``seq_len`` + 1 tokens: the inputs are
    its first ``seq_len``, the targets its last;
  * each of the M clients holds D_n = floor(lo + (hi - lo)U) sequences,
    [lo, hi] the configuration's ``data_samples``, in ceil(hi) slots, the
    slots past D_n padding;
  * poisoning: round(ratio·M) clients, chosen at random, train on targets
    mapped through one fixed random permutation of the vocabulary;
  * ``val_seqs`` clean validation sequences;
  * client positions uniform in the cell's disc (r = R·sqrt(U), floored at
    ``min_distance_m``) and v_max ~ U[v_lo, v_hi];
  * the model's initial weights (``init_params``), the reputation's prior
    (staleness 1, one positive interaction, no negative one) and the key
    that the program's rounds split, as raw threefry key data.

Everything is drawn on the device in jitted calls.  The driver only wraps
these arrays in the program's ``FedData`` and ``FLState``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def layer_shapes(kind: str, mc: dict) -> dict:
    """Name -> shape of one layer's weights, the names the program's
    ``models.nemotron_h`` reads."""
    d, h = mc["hidden_size"], mc["mamba_num_heads"]
    if kind == "M":
        din = h * mc["mamba_head_dim"]
        conv = din + 2 * mc["n_groups"] * mc["ssm_state_size"]
        return {"norm": (d,), "in_proj": (d, din + conv + h),
                "conv_w": (mc["conv_kernel"], conv), "conv_b": (conv,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,),
                "gate_norm": (din,), "out_proj": (din, d)}
    if kind == "E":
        e, f, fs = (mc["n_routed_experts"], mc["moe_intermediate_size"],
                    mc["moe_shared_expert_intermediate_size"])
        r = mc["held"]["router_experts"]
        return {"norm": (d,), "router": (d, r), "bias": (r,),
                "up": (e, d, f), "down": (e, f, d),
                "shared_up": (d, fs), "shared_down": (fs, d)}
    hd = mc["head_dim"]
    return {"norm": (d,), "wq": (d, mc["num_attention_heads"] * hd),
            "wk": (d, mc["num_key_value_heads"] * hd),
            "wv": (d, mc["num_key_value_heads"] * hd),
            "wo": (mc["num_attention_heads"] * hd, d)}


def init_params(key, mc: dict, std: float = 0.02) -> dict:
    """The model's initial weights: linear weights N(0, std^2); Mamba2's
    A_log = log(1..H), D = 1, dt_bias = softplus^-1 of dt log-uniform in
    [time_step_min, time_step_max]; the conv uniform in +-1/sqrt(kernel);
    norms 1; the correction bias N(0, 0.01^2)."""
    d, v = mc["hidden_size"], mc["vocab_size"]
    pattern = mc["hybrid_override_pattern"]
    keys = iter(jax.random.split(key, 16 * len(pattern) + 2))
    layers = []
    for kind in pattern:
        p = {}
        for name, shape in layer_shapes(kind, mc).items():
            k = next(keys)
            if name in ("norm", "gate_norm", "D"):
                p[name] = jnp.ones(shape)
            elif name == "A_log":
                p[name] = jnp.log(jnp.arange(1, shape[0] + 1,
                                             dtype=jnp.float32))
            elif name == "dt_bias":
                lo, hi = (math.log(mc["time_step_min"]),
                          math.log(mc["time_step_max"]))
                dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape))
                p[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name in ("conv_w", "conv_b"):
                b = 1.0 / math.sqrt(mc["conv_kernel"])
                p[name] = jax.random.uniform(k, shape, minval=-b, maxval=b)
            elif name == "bias":
                p[name] = 0.01 * jax.random.normal(k, shape)
            else:
                p[name] = std * jax.random.normal(k, shape)
        layers.append(p)
    return {"embed": std * jax.random.normal(next(keys), (v, d)),
            "layers": tuple(layers), "norm_f": jnp.ones((d,)),
            "head": std * jax.random.normal(next(keys), (d, v))}


def markov_sequences(key, rows: int, length: int, vocab: int,
                     successors: int):
    """[rows, length] tokens of the seed's Markov source."""
    k_succ, k_prob, k_start, k_walk = jax.random.split(key, 4)
    succ = jax.random.randint(k_succ, (vocab, successors), 0, vocab)
    cdf = jnp.cumsum(jax.random.dirichlet(
        k_prob, jnp.ones((successors,)), (vocab,)), axis=1)
    first = jax.random.randint(k_start, (rows,), 0, vocab)

    def step(tok, k):
        u = jax.random.uniform(k, (rows,))
        pick = jnp.sum(u[:, None] > cdf[tok], axis=1)
        nxt = succ[tok, jnp.minimum(pick, successors - 1)]
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(k_walk, length - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def _one_seed(key, m, n_poison, channel, v_range, d_range, data, vocab):
    ks = jax.random.split(key, 10)
    (lo, hi), s = d_range, data["seq_len"]
    cap, nv = math.ceil(hi), data["val_seqs"]
    seqs = markov_sequences(ks[0], m * cap + nv, s + 1, vocab,
                            data["successors"])
    x, y = seqs[:, :-1], seqs[:, 1:]
    sizes = jnp.floor(lo + (hi - lo) * jax.random.uniform(ks[1], (m,)))
    mask = jnp.arange(cap)[None, :] < sizes[:, None]
    poisoned = jnp.zeros((m,), bool).at[
        jax.random.permutation(ks[2], m)[:n_poison]].set(True)
    perm = jax.random.permutation(ks[3], vocab)
    y_c = y[:m * cap].reshape(m, cap, s)
    r = channel["cell_radius_m"] * jnp.sqrt(jax.random.uniform(ks[4], (m,)))
    return {"x": x[:m * cap].reshape(m, cap, s), "y": y_c,
            "y_train": jnp.where(poisoned[:, None, None], perm[y_c], y_c),
            "mask": mask, "sizes": sizes, "poisoned": poisoned,
            "x_val": x[m * cap:], "y_val": y[m * cap:],
            "distances": jnp.maximum(r, channel["min_distance_m"]),
            "v_max": jax.random.uniform(ks[5], (m,), minval=v_range[0],
                                        maxval=v_range[1]),
            "ms": jnp.ones((m,)), "pi_count": jnp.ones((m,)),
            "ni_count": jnp.zeros((m,)),
            "key": jax.random.key_data(ks[6])}


@partial(jax.jit, static_argnames=("seeds", "m", "n_poison", "channel",
                                   "v_range", "d_range", "data", "vocab"))
def _make(key, seeds, m, n_poison, channel, v_range, d_range, data, vocab):
    one = partial(_one_seed, m=m, n_poison=n_poison, channel=dict(channel),
                  v_range=v_range, d_range=d_range, data=dict(data),
                  vocab=vocab)
    return jax.lax.map(one, jax.random.split(key, seeds))


def grid_inputs(key, seeds: int, config: dict, traffic: dict) -> dict:
    """The data of ``seeds`` seeds, each array with a leading [S] axis."""
    data = dict(traffic["data"])
    m = int(config["clients_total"])
    return _make(key, seeds=int(seeds), m=m,
                 n_poison=int(round(float(data.pop("poison_ratio")) * m)),
                 channel=tuple(sorted(config["channel"].items())),
                 v_range=tuple(config["v_max"]),
                 d_range=tuple(config["data_samples"]),
                 data=tuple(sorted(data.items())),
                 vocab=int(config["vocab_size"]))


def initial_params(key, seeds: int, config: dict) -> dict:
    """The model's initial weights, one copy with a leading [S] axis of
    ``seeds`` equal rows (one set of weights per seed of the run)."""
    rows = lambda a: jnp.broadcast_to(a[None], (seeds,) + a.shape)
    return jax.jit(lambda k: jax.tree_util.tree_map(
        rows, init_params(k, config)))(key)
