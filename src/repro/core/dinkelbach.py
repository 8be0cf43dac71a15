"""Algorithm 1 — transmit-power optimization via Dinkelbach fractional
programming (paper §V-B-3, Eqs. 35–45).

Per client the subproblem is

    min_p   p·d / (B·log2(1 + p·F))         (energy for the upload)
    s.t.    B·log2(1 + p·F) ≥ d / G         (rate floor ⇔ t_com ≤ G = Tmax − t_cmp;
                                             the paper's (35b) prints the flipped
                                             inequality but its Lagrangian (40)
                                             penalises R < d/G, i.e. a floor)
            p_min ≤ p ≤ p_max

Equivalently max R(p)/U(p); Dinkelbach iterates q ← R(p̂)/U(p̂) where
p̂ = argmax R(p) − q·U(p).  Two inner solvers:

  * ``_inner_projected`` — the concave stationary point  p0 = B/(ln2·q·d) − 1/F
    projected onto the feasible box (exactly the KKT solution with the
    multipliers absorbed by the active bounds);
  * ``_inner_kkt`` — the paper-faithful dual subgradient ascent on
    (λ1, λ2, λ3) with the primal update Eq. (43).

Both converge to the same point (asserted in tests); the projected solver is
the default fast path.

``successive_power`` applies the paper's successive-optimization order
(§V-B-3): clients are optimized N → 1 in SIC order, each seeing the already-
fixed interference of later-decoded clients — a reverse ``lax.scan``.
This chain is O(N) sequential; ``repro.core.sic`` solves the same fixed
point with client-parallel Jacobi sweeps for large N (the engines select
between them via the static ``sic_mode`` key on ``GameConfig``).

Everything except ``return_trace`` mode is trace-safe: ``dinkelbach_power``
and ``successive_power`` carry fixed-dtype arrays only, so the Stackelberg
engine can ``vmap`` them across K channel realizations (the batched
``lax.while_loop`` keeps converged lanes frozen while the rest iterate).
``bandwidth`` / ``sigma2`` / ``p_min`` / ``p_max`` / ``d`` are likewise
plain operands (the sweep engine passes traced ``GamePhysics`` scalars,
vmapped over a config axis) — only ``inner`` is a static compile key.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .noma import exp2_m1, log2_1p

LN2 = 0.6931471805599453


def _rate(p, f_eff, bandwidth):
    return bandwidth * log2_1p(p * f_eff)


def _p_floor(d, g, f_eff, bandwidth, p_min):
    """Smallest power meeting the rate floor R ≥ d/G.

    Grad-safe closed form: the naive ``(2**expo − 1) / f_eff`` is forward-
    correct (the caller clamps with ``min(·, p_max)``) but reverse-mode
    poison — ``2**expo`` overflows to inf for a starved deadline and
    ``1/f_eff`` is inf on a dead (masked) lane, and a ``where`` that merely
    *selects away* an inf branch still multiplies it by a zero cotangent
    (0·inf = NaN).  Both denominators are therefore replaced by safe values
    inside the untaken branch (double-``where``) and the exponent is
    saturated; every rewrite is value-identical after the caller's clamp."""
    expo = d / (jnp.maximum(g, 1e-9) * bandwidth)
    big = expo > 60.0            # 2**60 already exceeds any reachable p_max
    f_ok = f_eff > 1e-30
    f_safe = jnp.where(f_ok, f_eff, 1.0)
    need_raw = exp2_m1(jnp.where(big, 0.0, expo)) / f_safe
    need = jnp.where(f_ok & ~big, need_raw, 1e30)
    return jnp.maximum(p_min, need)


def _inner_projected(q, d, f_eff, bandwidth, lo, hi):
    """Concave stationary point projected on [lo, hi], grad-safe.

    Double-``where`` on both divisions: the cold-start lane (q = 0) and the
    dead lane (f_eff = 0) must not evaluate 1/0 even in the branch the
    ``where`` discards, or reverse-mode emits NaN cotangents.  Forward
    values are unchanged — q→0 clipped to ``hi`` exactly as the old huge
    stationary point was, and a dead lane ends at p_max either way
    (its ``lo`` is already p_max via the rate-floor clamp)."""
    den = LN2 * q * d
    den_ok = den > 1e-20
    f_ok = f_eff > 1e-30
    den_safe = jnp.where(den_ok, den, 1.0)
    f_safe = jnp.where(f_ok, f_eff, 1.0)
    inv_f = jnp.where(f_ok, 1.0 / f_safe, 0.0)
    p0 = jnp.where(den_ok, bandwidth / den_safe - inv_f, hi)
    return jnp.clip(p0, lo, hi)


def _inner_kkt(q, d, g, f_eff, bandwidth, lo, hi, iters: int = 200,
               lr: float = 0.05):
    """Faithful Alg.1 inner solve: subgradient ascent on the dual (45a–c)."""
    rate_floor = d / jnp.maximum(g, 1e-9)

    def body(i, carry):
        lam, _p = carry
        l1, l2, l3 = lam
        denom = LN2 * (q * d + l2 - l3)
        p = bandwidth * (1.0 - l1) / jnp.maximum(denom, 1e-12) - 1.0 / f_eff
        p = jnp.clip(p, lo, hi)  # primal feasibility (Eq. 43 + box)
        r = _rate(p, f_eff, bandwidth)
        # paper Eqs. (45a)-(45c), with the rate term normalised for step-size
        l1 = jnp.maximum(l1 - lr * (rate_floor - r) / jnp.maximum(rate_floor, 1.0), 0.0)
        l2 = jnp.maximum(l2 - lr * (lo - p), 0.0)
        l3 = jnp.maximum(l3 - lr * (p - hi), 0.0)
        return (jnp.stack([l1, l2, l3]), p)

    lam0 = jnp.zeros(3)
    _, p = jax.lax.fori_loop(0, iters, body, (lam0, lo))
    return p


def dinkelbach_power(d, g, f_eff, bandwidth, p_min, p_max,
                     delta: float = 1e-6, max_iter: int = 50,
                     inner: str = "projected", return_trace: bool = False,
                     q_init=None):
    """Optimal transmit power for one client (scalar inputs).

    Returns (p*, q*, iterations) — q* is the optimal rate-per-energy
    R(p*)/U(p*), the quantity whose convergence Fig. 4 plots.

    ``q_init`` warm-starts the Dinkelbach ratio (default 0, the paper's
    cold start).  Dinkelbach's iteration converges to the unique q* from
    any q₀ ≥ 0, so a warm start changes the iteration count, never the
    fixed point — the blocked SIC engine passes the previous sweep's q to
    cut the per-sweep solve to ~1–2 iterations.
    """
    lo = jnp.minimum(_p_floor(d, g, f_eff, bandwidth, p_min), p_max)
    hi = p_max * jnp.ones_like(lo)

    def solve(q):
        if inner == "kkt":
            return _inner_kkt(q, d, g, f_eff, bandwidth, lo, hi)
        return _inner_projected(q, d, f_eff, bandwidth, lo, hi)

    def cond(carry):
        _p, _q, w, it = carry
        return (jnp.abs(w) > delta) & (it < max_iter)

    def body(carry):
        _p, q, _w, it = carry
        p = solve(q)
        r, u = _rate(p, f_eff, bandwidth), p * d
        w = (r - q * u) / jnp.maximum(r, 1.0)      # relative Dinkelbach gap
        return (p, r / jnp.maximum(u, 1e-30), w, it + 1)

    p0 = hi
    q0 = jnp.zeros_like(lo) if q_init is None else q_init * jnp.ones_like(lo)
    if return_trace:  # python loop, records q per iteration (Fig. 4)
        p, q, w, it, trace = p0, q0, jnp.inf, 0, [float(q0)]
        while it < max_iter and abs(float(w)) > delta:
            p = solve(q)
            r, u = _rate(p, f_eff, bandwidth), p * d
            w = (r - q * u) / jnp.maximum(r, 1.0)
            q = r / max(float(u), 1e-30)
            trace.append(float(q))
            it += 1
        return p, q, it, trace
    # fixed-dtype carry: weak-typed jnp.inf / python-int counters would
    # promote (and retrace) under x64 or when vmapped from the batched engine
    w0 = jnp.asarray(jnp.inf, p0.dtype)
    p, q, w, it = jax.lax.while_loop(cond, body,
                                     (p0, q0, w0, jnp.asarray(0, jnp.int32)))
    return p, q, it


@partial(jax.jit, static_argnames=("inner",))
def successive_power(h2_sorted, d, g, bandwidth, sigma2, p_min, p_max,
                     inner: str = "projected"):
    """Optimize all N clients' powers in the successive order N → 1.

    h2_sorted: [N] descending (SIC decode order).  Client n's effective gain
    F_n = |h_n|² / (Σ_{j>n} p_j |h_j|² + σ²) uses the already-optimized
    powers of later-decoded clients — a reverse scan carrying Σ p_j |h_j|².
    """
    def body(intf, xs):
        h2_n, d_n, g_n = xs
        f_eff = h2_n / (intf + sigma2)
        p_n, q_n, _ = dinkelbach_power(d_n, g_n, f_eff, bandwidth,
                                       p_min, p_max, inner=inner)
        return intf + p_n * h2_n, (p_n, q_n)

    d_v = jnp.broadcast_to(d, h2_sorted.shape)
    g_v = jnp.broadcast_to(g, h2_sorted.shape)
    _, (p, q) = jax.lax.scan(body, jnp.zeros(()), (h2_sorted, d_v, g_v),
                             reverse=True)
    return p, q
