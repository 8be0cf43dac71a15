"""Plain reference of one DT-assisted FL round (arXiv 2501.01584, Fig. 1,
Sec. II-V) whose client model is the cut Nemotron-H stack of
``bench/configs/nemotron3_nano_fl_ep16.json``.

Written from the model's published description and the paper's protocol,
with nothing taken from the program under test: ``jax.numpy`` in the
working precision (float32) under ``jax.default_matmul_precision("highest")``,
Python loops over clients and SGD steps, on the default device (the chip in
a benchmark run).  The model:

  * a layer is x + mixer(RMSNorm(x)), RMSNorm(x) = w·x/sqrt(mean(x^2) + eps);
  * Mamba2, step by step in time: (z, xBC, dt) = x·W_in; xBC through a causal
    depthwise conv of width K with bias, then SiLU; dt = softplus(dt +
    dt_bias), A = -exp(A_log); per head, with B_t and C_t of the head's
    group, h_t = exp(dt_t·A)·h_{t-1} + dt_t·x_t B_t^T and y_t = h_t C_t +
    D·x_t; then RMSNorm(y·SiLU(z)) over each group of channels, times its
    weight, and W_out;
  * MoE: s = sigmoid(x·W_r) over all routed experts, the top k of s + b, the
    weights the chosen s normalised to sum 1 times the routed scale; each
    held expert a dense down(relu(up(x))^2) over every token, times the
    token's weight for it (zero where it did not choose it); plus the
    shared expert;
  * attention: full causal softmax over each query's prefix, 32 query heads
    sharing 2 key/value heads, scale 1/sqrt(head_dim), no positional
    encoding (the configuration's ``assumed``);
  * the final RMSNorm, the head, the mean next-token cross-entropy over the
    tokens of the drawn sequences; accuracy is argmax = target.

Departures from a plain forward pass, for memory only: each layer is
recomputed in the backward pass (``jax.checkpoint``), the Mamba2 recurrence
in blocks of 128 steps, attention in blocks of queries, and the
validation sequences evaluated ``batch`` at a time.

The round (one, from the run's initial state): selection by Eq. (16),
fades, the SIC order, the allocation from ``bench/reference.equilibrium``
(NumPy float64, Alg. 2), the DT split, the twin's SGD and each client's,
the deadline, RONI on next-token accuracy, Eq. (3) and the bookkeeping, as
``bench/fl_reference.py`` describes them for the classifier.  The random
draws follow the program's key protocol: ``key, k_ch, k_map, k_dt, _ =
split(key, 5)``, ``k_dev, k_local, k_twin = split(k_dt, 3)``; the step's
minibatch is the ``batch`` rows of weight > 0 with the largest of
``uniform(step key, (batch, rows))``; the twin's copy of row r has each
input token replaced, where ``uniform(k_hit, (S,)) < eps``, by
``randint(k_tok, (S,), 0, V)``, ``k_hit, k_tok = split(fold_in(k_dev,
r))``.  Near ties of selection and SIC order are settled by the program's
reported order, as in ``bench/fl_reference.py``; a deadline or RONI verdict
within its edge is not settled: the round is then left uncompared.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

import jax
import jax.numpy as jnp

from bench import compare, reference
from bench.fl_reference import (AC_SCALE, DEADLINE_TOL, EDGE,
                                _selection_edge)

TIME_BLOCK = 128       # Mamba2 steps between saved states
QUERY_BLOCK = 1024     # attention queries at a time
# a RONI verdict whose accuracy drop lies within this share of the
# validation tokens of the threshold may go either way between a program
# that computes in bfloat16 and this reference (33 of 16,384 tokens)
RONI_EDGE = 2e-3


def _norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _silu(x):
    return x / (1 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _mamba(p, x, mc):
    b, s, d = x.shape
    h, hp = mc["mamba_num_heads"], mc["mamba_head_dim"]
    g, n, k = mc["n_groups"], mc["ssm_state_size"], mc["conv_kernel"]
    din = h * hp
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:2 * din + 2 * g * n]
    dt = _softplus(zxbcdt[..., 2 * din + 2 * g * n:] + p["dt_bias"])
    conv = p["conv_b"] + sum(
        p["conv_w"][i] * jnp.pad(xbc, ((0, 0), (k - 1 - i, 0), (0, 0)))[:, :s]
        for i in range(k))
    xbc = _silu(conv)
    xs = xbc[..., :din].reshape(b, s, h, hp)
    bm = xbc[..., din:din + g * n].reshape(b, s, g, n)
    cm = xbc[..., din + g * n:].reshape(b, s, g, n)
    a = -jnp.exp(p["A_log"])
    head_group = jnp.arange(h) // (h // g)

    def step(state, t_in):
        x_t, dt_t, b_t, c_t = t_in          # [b,h,p] [b,h] [b,g,n] [b,g,n]
        b_h, c_h = b_t[:, head_group], c_t[:, head_group]      # [b,h,n]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.sum(state * c_h[:, :, None, :], -1)

    @jax.checkpoint
    def block(state, t_in):
        return jax.lax.scan(step, state, t_in, unroll=8)

    tb = min(TIME_BLOCK, s)
    blocks = lambda a_: jnp.moveaxis(a_, 1, 0).reshape(
        (s // tb, tb) + (a_.shape[0],) + a_.shape[2:])
    init = jnp.zeros((b, h, hp, n), x.dtype)
    _, ys = jax.lax.scan(block, init, (blocks(xs), blocks(dt), blocks(bm),
                                       blocks(cm)))
    y = jnp.moveaxis(ys.reshape((s, b, h, hp)), 0, 1)
    y = (y + p["D"][:, None] * xs).reshape(b, s, din) * _silu(z)
    yg = y.reshape(b, s, g, din // g)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True)
                       + mc["norm_eps"])
    return (yg.reshape(b, s, din) * p["gate_norm"]) @ p["out_proj"]


def _moe(p, x, mc):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt @ p["router"])
    _, idx = jax.lax.top_k(scores + p["bias"], mc["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    if mc["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * mc["routed_scaling_factor"]
    first = mc["held"]["expert_first"]
    out = jnp.maximum(xt @ p["shared_up"], 0) ** 2 @ p["shared_down"]
    for e in range(mc["n_routed_experts"]):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
        out = out + gate[:, None] * (jnp.maximum(xt @ p["up"][e], 0) ** 2
                                     @ p["down"][e])
    counts = jnp.stack([jnp.sum(idx == first + e)
                        for e in range(mc["n_routed_experts"])])
    return out.reshape(b, s, d), counts


def _attention(p, x, mc):
    b, s, _ = x.shape
    hq, hk, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                  mc["head_dim"])
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = jnp.repeat((x @ p["wk"]).reshape(b, s, hk, hd), hq // hk, axis=2)
    v = jnp.repeat((x @ p["wv"]).reshape(b, s, hk, hd), hq // hk, axis=2)

    @jax.checkpoint
    def queries(qb, lo):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(hd)
        seen = (lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    qb = min(QUERY_BLOCK, s)
    o = jnp.concatenate([queries(q[:, i:i + qb], i)
                         for i in range(0, s, qb)], axis=1)
    return o.reshape(b, s, hq * hd) @ p["wo"]


def _hidden(params, tokens, mc):
    x = params["embed"][tokens]
    counts = []
    for kind, p in zip(mc["hybrid_override_pattern"], params["layers"]):
        def layer(p, x, kind=kind):
            hin = _norm(x, p["norm"], mc["norm_eps"])
            if kind == "M":
                return x + _mamba(p, hin, mc), None
            if kind == "E":
                y, c = _moe(p, hin, mc)
                return x + y, c
            return x + _attention(p, hin, mc), None
        x, c = jax.checkpoint(layer)(p, x)
        if c is not None:
            counts.append(c)
    return _norm(x, params["norm_f"], mc["norm_eps"]), jnp.stack(counts)


def logits(params, tokens, mc):
    return _hidden(params, tokens, mc)[0] @ params["head"]


def loss(params, x, y, w, mc):
    """Mean next-token cross-entropy over the tokens of the sequences of
    weight 1; also the tokens each held expert received, per MoE layer."""
    hid, counts = _hidden(params, x, mc)
    z = hid @ params["head"]
    z = z - jnp.max(z, -1, keepdims=True)
    nll = (jnp.log(jnp.sum(jnp.exp(z), -1))
           - jnp.take_along_axis(z, y[..., None], -1)[..., 0])
    return (jnp.sum(jnp.sum(nll, -1) * w)
            / jnp.maximum(jnp.sum(w) * y.shape[1], 1)), counts


@functools.lru_cache(maxsize=4)
def _compiled(frozen: tuple):
    """(SGD step, validation hits) of a configuration: the step takes its
    input parameters' memory for its output."""
    mc = _thaw(frozen)

    def step(p, x, y, w, lr):
        (_, counts), g = jax.value_and_grad(loss, has_aux=True)(p, x, y, w,
                                                                 mc)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), counts

    def hits(p, x, y):
        return jnp.sum(jnp.argmax(logits(p, x, mc), -1) == y)
    return jax.jit(step, donate_argnums=0), jax.jit(hits)


def _draw(key, w, batch):
    """The minibatch: the ``batch`` rows of weight > 0 with the largest
    U[0, 1) of the step's key, one per row of the draw."""
    u = np.asarray(jax.random.uniform(key, (batch, len(w))))
    return np.argmax(np.where(np.asarray(w) > 0, u, -1.0), axis=1)


def _deviate(k_dev, row, tokens, eps, vocab):
    k_hit, k_tok = jax.random.split(jax.random.fold_in(k_dev, int(row)))
    hit = np.asarray(jax.random.uniform(k_hit, tokens.shape)) < np.float32(eps)
    other = np.asarray(jax.random.randint(k_tok, tokens.shape, 0, vocab))
    return np.where(hit, other, tokens)


def _freeze(mc: dict) -> tuple:
    """The model's settings as a hashable tuple: the configuration's
    numbers and strings, with the held share's."""
    keep = lambda v: isinstance(v, (int, float, str, bool))
    return tuple(sorted([(k, v) for k, v in mc.items() if keep(v)]
                        + [("held", tuple(sorted(mc["held"].items())))]))


def _thaw(frozen: tuple) -> dict:
    return dict(frozen, held=dict(dict(frozen)["held"]))


def round_reference(inp: dict, params0, point: dict, protocol: dict,
                    phys: dict, channel: dict, solver: dict, mc: dict,
                    dtype=np.float32, follow: dict | None = None) -> dict:
    """One round from ``params0`` (device arrays) on one seed's inputs
    ``inp`` (NumPy arrays, ``bench/fl_lm_inputs.py``).  ``protocol`` holds
    ``n_selected``, ``local_steps``, ``server_steps``, ``batch``,
    ``roni_threshold``, ``weights``, ``samples_per_unit``, ``use_roni``.
    Returns the round's ``selected``, ``n_excluded_roni``, ``n_stragglers``,
    ``val_acc``, ``energy``, ``latency``, ``cond``, ``expert_tokens``
    [N + 1, steps, n_moe, held] (twin first), the final ``params`` (device
    arrays), ``pi_count``, ``ni_count``, ``edge`` (an unsettled edge's kind,
    or None), ``followed`` and ``overridden`` (edge decisions taken from
    the program's report, and those among them against the reference's own
    verdict).  ``dtype`` below float32 makes the whole round the control."""
    dt = np.dtype(dtype)
    ctx = contextlib.nullcontext() if dt != np.float32 else \
        jax.default_matmul_precision("highest")
    with ctx:
        return _round(inp, params0, point, protocol, phys, channel, solver,
                      _freeze(mc), dt, follow)


def _round(inp, params0, point, protocol, phys, channel, solver, mc, dt,
           follow):
    step, hits = _compiled(mc)
    mc = _thaw(mc)
    game_dt = np.float64 if dt == np.float32 else dt
    n, batch = int(protocol["n_selected"]), int(protocol["batch"])
    eps, lr, t_max = (float(point[k]) for k in ("epsilon", "lr", "t_max"))
    thr = float(protocol["roni_threshold"])
    xi = np.asarray(protocol["weights"], np.float64)
    d_all = np.asarray(inp["sizes"], np.float64)
    m, cap, s = inp["x"].shape
    vocab = int(mc["vocab_size"])
    cast = lambda a: jnp.asarray(a, dt)
    params = jax.tree_util.tree_map(cast, params0)
    ms, pi, ni = (np.asarray(inp[k], np.float64)
                  for k in ("ms", "pi_count", "ni_count"))
    gain = (channel["ref_gain"] * np.asarray(inp["distances"], np.float64)
            ** -channel["pathloss_exp"])
    key = jnp.asarray(inp["key"], jnp.uint32)
    x_val, y_val = np.asarray(inp["x_val"]), np.asarray(inp["y_val"])
    n_val = x_val.size

    def accuracy(p):
        return sum(int(hits(p, x_val[i:i + batch], y_val[i:i + batch]))
                   for i in range(0, len(x_val), batch))

    edge, followed, overridden = None, 0, []
    told = None if follow is None else np.asarray(follow["selected"])
    key, k_ch, k_map, k_dt, _ = jax.random.split(key, 5)
    k_dev, k_local, k_twin = jax.random.split(k_dt, 3)
    # 1. selection, Eq. (16)
    z = (xi[0] * (1.0 - np.exp(-(d_all + eps) / AC_SCALE))
         + xi[1] * ms / np.sum(ms) + xi[2] * pi / (pi + ni))
    rank = np.argsort(-z, kind="stable")
    sel = rank[:n]
    near = _selection_edge(z, rank, n, (d_all, ms, pi, ni)) if n < m else None
    if near is not None:
        keep = np.setdiff1d(sel, np.flatnonzero(near))
        if (told is not None and np.all(np.isin(keep, told))
                and np.all(near[told] | np.isin(told, keep))):
            overridden += [["selection", float(z[rank[n - 1]] - z[rank[n]])]
                           ] * int(np.sum(~np.isin(told, sel)))
            sel, followed = told, followed + 1
        else:
            edge = "selection"
    # 2. Rayleigh fade, Exp(1) = -ln(1 - U), and the SIC order
    u = np.asarray(jax.random.uniform(k_ch, (m,)), np.float64)
    fade = gain * -np.log1p(-u)
    order = sel[np.argsort(-fade[sel], kind="stable")]
    if np.any(-np.diff(fade[order]) < EDGE["sic_order"] * fade[order][1:]):
        if (told is not None and set(told) == set(order) and np.all(
                -np.diff(fade[told]) >= -EDGE["sic_order"] * fade[told][1:])):
            if np.any(told != order):
                gaps = -np.diff(fade[order]) / fade[order][1:]
                overridden.append(["sic_order", float(np.min(gaps))])
            order, followed = told, followed + 1
        else:
            edge = edge or "sic_order"
    # 3. the allocation of the round (Alg. 2)
    d_n = d_all[order] * float(protocol["samples_per_unit"])
    v = np.asarray(inp["v_max"])[order]
    al = reference.equilibrium(
        fade[order][None], d_n[None], v[None].astype(np.float64),
        dict(phys, t_max=t_max), epsilon=0.0, max_iter=solver["max_iter"],
        tol=solver["tol"], dinkelbach_delta=solver["dinkelbach_delta"],
        dinkelbach_iter=solver["dinkelbach_iter"], dtype=game_dt)
    # 4. DT split: a sequence maps to the twin with probability v_n
    valid = np.asarray(inp["mask"])[order]
    mapped = (np.asarray(jax.random.uniform(k_map, (n, cap)))
              < v.astype(np.float32)[:, None]) & valid
    x_n, y_n = np.asarray(inp["x"])[order], np.asarray(inp["y_train"])[order]
    # 6. the deadline
    t_done = np.asarray(al["t_cmp"][0] + al["t_com"][0], np.float64)
    meets = t_done <= DEADLINE_TOL * t_max
    if np.any(np.abs(t_done - DEADLINE_TOL * t_max)
              < EDGE["deadline"] * t_max):
        edge = edge or "deadline"
    # 5. the twin's SGD on the mapped sequences, each client's on its own
    base = accuracy(params)
    counts = []

    def train(x, y, w, keys, dev):
        p, per_step = jax.tree_util.tree_map(jnp.copy, params), []
        for k in keys:
            pick = _draw(k, w, batch)
            xb = x[pick]
            if dev:
                xb = np.stack([_deviate(k_dev, r, xr, eps, vocab)
                               for r, xr in zip(pick, xb)])
            p, c = step(p, jnp.asarray(xb), jnp.asarray(y[pick]),
                        cast(w[pick]), cast(lr))
            per_step.append(np.asarray(c))
        counts.append(per_step)
        return p

    w_twin_rows = mapped.reshape(-1).astype(np.float32)
    twin = train(x_n.reshape(n * cap, s), y_n.reshape(n * cap, s),
                 w_twin_rows, jax.random.split(k_twin,
                                               int(protocol["server_steps"])),
                 dev=True)
    use_roni = bool(protocol["use_roni"])
    drop = lambda p: (base - accuracy(p)) / n_val
    twin_drop = drop(twin)
    twin_ok = (twin_drop <= thr) if use_roni else True
    # 7, 8: RONI per client and Eq. (3), summed as each client is done
    a = (1.0 - v) * d_n
    w_twin = float(np.sum(v * d_n + eps))
    total = jax.tree_util.tree_map(
        lambda q: cast(w_twin if twin_ok else 0.0) * q, twin)
    del twin
    local_keys = jax.random.split(
        k_local, n * int(protocol["local_steps"])).reshape(
            n, int(protocol["local_steps"]), -1)
    positive, drops = np.ones(n, bool), [twin_drop]
    for i in range(n):
        p_i = train(x_n[i], y_n[i], (valid[i] & ~mapped[i]).astype(
            np.float32), local_keys[i], dev=False)
        drops.append(drop(p_i))
        positive[i] = (drops[-1] <= thr) if use_roni else True
        c = a[i] if positive[i] and meets[i] else 0.0
        total = jax.tree_util.tree_map(lambda t, q: t + cast(c) * q,
                                       total, p_i)
        del p_i
    if use_roni and np.any(np.abs(np.asarray(drops) - thr) < RONI_EDGE):
        edge = edge or "roni"
    include = positive & meets
    mass = (np.sum(d_n) - np.sum(np.where(include, 0.0, a))
            - (0.0 if twin_ok else w_twin))
    if include.any() or twin_ok:
        params = jax.tree_util.tree_map(lambda t: t / cast(max(mass, 1e-9)),
                                        total)
    # 9. bookkeeping, Eqs. (13), (15)
    pi[order] += positive
    ni[order] += ~positive
    return {"selected": order, "n_excluded_roni": int(np.sum(~positive)),
            "n_stragglers": int(np.sum(~meets)),
            "val_acc": accuracy(params) / n_val,
            "energy": float(al["energy"][0]),
            "latency": float(al["t_total"][0]),
            "cond": float(compare.condition(al, t_max)[0]),
            "expert_tokens": np.asarray(counts),
            "params": params, "pi_count": pi, "ni_count": ni,
            "edge": edge, "followed": followed, "overridden": overridden}
