"""Plain reference of the Stackelberg equilibrium (arXiv 2501.01584, Alg. 2).

Written from the paper's equations and the solver settings that the
configuration files state, in NumPy, with nothing taken from the program
under test.  Every draw of a batch is solved at once; the successive power
optimisation runs in the paper's own order, client N down to client 1, each
client seeing the powers already fixed for the clients decoded after it.

Leader (clients), per iteration of Alg. 2:
  G_n = max(t_max - c(1-v_n)D_n/f_n, 1e-3)               rate-floor slack
  p_n = Dinkelbach on  min p·d / (B·log2(1 + p·F_n))     s.t. rate >= d/G_n,
        p in [p_min, p_max],  F_n = |h_n|^2 / (sum_{j>n} p_j|h_j|^2 + sigma^2)
  A_n = max(t_max - d/R_n, 1e-3),  f_n = clip(c(1-v_n)D_n / A_n, f_min, f_max)
  E   = sum_n 0.5·tau·c(1-v_n)D_n·f_n^2 + p_n·d/R_n
Stops when |E_prev - E| < tol·E or after max_iter iterations, and keeps the
lowest-energy iterate among those that meet the deadline (feasibility
before energy).  Follower (Theorem 1): alpha_n = c·D^_n / (T·f_S), or the
shares of the total load when those sum past 1.

``dtype`` is the working precision: every intermediate is rounded to it.
float64 is the reference; a lower one (bfloat16) is the control that the
comparison has to reject.
"""
from __future__ import annotations

import numpy as np

LN2 = np.log(2.0)


class _Arith:
    """Rounds every intermediate to one working precision."""

    def __init__(self, dtype):
        self.dtype = np.dtype(dtype)

    def __call__(self, x):
        return np.asarray(x, dtype=self.dtype)


def _dinkelbach(r, d, g, f_eff, phys, delta, max_iter):
    """Per-draw Dinkelbach power for one client: arrays over draws."""
    bw, p_min, p_max = phys["bandwidth"], phys["p_min"], phys["p_max"]
    expo = r(d / r(np.maximum(g, 1e-9) * bw))
    alive = f_eff > 1e-30
    big = expo > 60.0
    need = r(np.expm1(r(np.where(big, 0.0, expo) * LN2)))
    need = r(np.where(alive & ~big, need / np.where(alive, f_eff, 1.0), 1e30))
    lo = r(np.minimum(np.maximum(p_min, need), p_max))
    hi = r(np.broadcast_to(p_max, lo.shape))
    inv_f = r(np.where(alive, 1.0 / np.where(alive, f_eff, 1.0), 0.0))
    p, q = hi.copy(), r(np.zeros_like(lo))
    live = np.ones(lo.shape, bool)
    for _ in range(max_iter):
        if not live.any():
            break
        den = r(LN2 * q * d)
        ok = den > 1e-20
        stat = r(np.where(ok, bw / np.where(ok, den, 1.0), 0.0) - inv_f)
        p_new = r(np.clip(np.where(ok, stat, hi), lo, hi))
        rate = r(bw * r(np.log2(r(1.0 + p_new * f_eff))))
        u = r(p_new * d)
        w = r(r(rate - q * u) / np.maximum(rate, 1.0))
        q_new = r(rate / np.maximum(u, 1e-30))
        p = np.where(live, p_new, p)
        q = np.where(live, q_new, q)
        live = live & (np.abs(w) > delta)
    return p, q


def _powers(r, h2, g, phys, delta, max_iter):
    """Successive power optimisation, client N-1 down to 0 (SIC order)."""
    k, n = h2.shape
    p = r(np.zeros((k, n)))
    q = r(np.zeros((k, n)))
    intf = r(np.zeros(k))
    for i in range(n - 1, -1, -1):
        f_eff = r(h2[:, i] / r(intf + phys["sigma2"]))
        p[:, i], q[:, i] = _dinkelbach(r, phys["model_bits"], g[:, i], f_eff,
                                       phys, delta, max_iter)
        intf = r(intf + p[:, i] * h2[:, i])
    return p, q


def _round(r, h2, D, v, f, p, phys):
    """Rates, latencies and energies of one strategy profile."""
    rx = r(p * h2)
    later = r(np.zeros_like(rx))                               # sum_{j>n}
    later[:, :-1] = np.cumsum(rx[:, :0:-1], axis=1)[:, ::-1]
    sinr = r(rx / r(later + phys["sigma2"]))
    rates = r(phys["bandwidth"] * r(np.log2(r(1.0 + sinr))))
    t_com = r(phys["model_bits"] / np.maximum(rates, 1e-9))
    work = r(phys["cycles_per_sample"] * r(1.0 - v) * D)
    t_cmp = r(work / f)
    e_cmp = r(0.5 * phys["tau"] * work * r(f * f))
    e_com = r(p * t_com)
    return rates, t_com, t_cmp, e_cmp, e_com, sinr


def equilibrium(h2, D, v_max, phys, epsilon=0.0, max_iter=20, tol=1e-6,
                dinkelbach_delta=1e-6, dinkelbach_iter=50,
                dtype=np.float64):
    """Alg. 2 over a batch of draws.

    h2, D, v_max : [K, N], each row of h2 in descending order.
    phys         : dict of the physics scalars (each a float or a [K] array).
    epsilon      : DT deviation, float or [K].
    Returns a dict of [K, N] and [K] arrays in ``dtype``.
    """
    r = _Arith(dtype)
    h2, D, v = r(h2), r(D), r(v_max)
    k, n = h2.shape
    ph = {name: r(np.broadcast_to(np.asarray(val, np.float64), (k,))[:, None])
          for name, val in phys.items()}
    eps = r(np.broadcast_to(np.asarray(epsilon, np.float64), (k,))[:, None])
    d_hat = r(v * D + eps)
    work = r(ph["cycles_per_sample"] * r(1.0 - v) * D)
    f = r(np.broadcast_to(ph["f_max"], (k, n)))
    best_bad = np.full(k, 2.0)
    best_e = np.full(k, np.inf)
    best_f, best_p, best_q = f.copy(), r(np.broadcast_to(ph["p_max"], (k, n))), r(np.zeros((k, n)))
    prev_e = np.full(k, np.inf)
    iters = np.zeros(k, np.int32)
    live = np.ones(k, bool)
    for _ in range(max_iter):
        if not live.any():
            break
        g = r(np.maximum(ph["t_max"] - r(work / f), 1e-3))
        p, q = _powers(r, h2, g, {key: val[:, 0] for key, val in ph.items()},
                       dinkelbach_delta, dinkelbach_iter)
        rates, t_com, _, _, _, _ = _round(r, h2, D, v, f, p, ph)
        slack = r(np.maximum(ph["t_max"] - t_com, 1e-3))
        f_new = r(np.clip(np.maximum(work / np.maximum(slack, 1e-9), ph["f_min"]),
                          ph["f_min"], ph["f_max"]))
        _, t_com, t_cmp, e_cmp, e_com, _ = _round(r, h2, D, v, f_new, p, ph)
        e = r(np.sum(r(e_cmp + e_com), axis=1))
        feas = np.max(r(t_cmp + t_com), axis=1) <= ph["t_max"][:, 0] + 1e-6
        bad = np.where(feas, 0.0, 1.0)
        better = live & ((bad < best_bad) | ((bad == best_bad) & (e < best_e)))
        best_bad = np.where(better, bad, best_bad)
        best_e = np.where(better, e, best_e)
        best_f = np.where(better[:, None], f_new, best_f)
        best_p = np.where(better[:, None], p, best_p)
        best_q = np.where(better[:, None], q, best_q)
        f = np.where(live[:, None], f_new, f)
        iters = iters + live
        done = np.abs(prev_e - e) < tol * np.maximum(e, 1e-12)
        prev_e = np.where(live, e, prev_e)
        live = live & ~done
    rates, t_com, t_cmp, e_cmp, e_com, sinr = _round(r, h2, D, v, best_f, best_p, ph)
    t_round = np.max(r(t_cmp + t_com), axis=1)
    load = r(ph["cycles_per_sample"] * d_hat)
    fs = ph["f_server"]
    a1 = r(load / r(t_round[:, None] * fs))
    saturated = np.sum(a1, axis=1) > 1.0
    a2 = r(load / np.sum(load, axis=1, keepdims=True))
    alpha = np.where(saturated[:, None], a2, a1)
    t_dt = r(load / r(alpha * fs))
    return {"p": best_p, "q": best_q, "f": best_f, "v": v, "alpha": alpha,
            "rates": rates, "sinr": sinr, "t_com": t_com, "t_cmp": t_cmp,
            "t_total": r(np.maximum(t_round, np.max(t_dt, axis=1))),
            "energy": r(np.sum(r(e_cmp + e_com), axis=1)),
            "feasible": best_bad == 0.0, "iterations": iters,
            "deadline_slack": r(t_round - ph["t_max"][:, 0])}
