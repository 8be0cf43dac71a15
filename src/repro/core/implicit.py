"""Differentiable Stackelberg equilibrium via the implicit function theorem.

``equilibrium`` solves Algorithm 2 with a ``lax.while_loop`` — opaque to
reverse-mode AD (and unrolling it would be both wrong near the safeguard
and catastrophically expensive).  This module registers a ``custom_vjp``
on the equilibrium *fixed point* instead: the forward pass runs the
existing solver untouched, and the backward pass applies the implicit
function theorem at the solution.

Mathematical contract
---------------------
Let ``x = (f, p, q)`` and ``θ = (physics, h2_sorted, D, v)``.  At a
converged equilibrium ``x* = T(x*, θ)`` where ``T`` is one differentiable
Algorithm-2 sweep (``_fp_step``):

  * Dinkelbach power: ``p' = Π_[lo,hi](B/(ln2·q·d) − 1/F)`` against the
    suffix interference of the current ``p`` (Eq. 43 with the multipliers
    absorbed by the box), then ``q' = R(p')/U(p')`` at ``p'``'s own
    interference (the Dinkelbach ratio at its fixed point);
  * leader frequency: ``f' = clip(c(1−v)D/A_n, f_min, f_max)`` with
    ``A_n = max(t_max − t_com(p'), ·)`` (§V-B-2).

Both ``sic_mode`` families (the sequential reverse scan and the blocked
Jacobi sweeps) converge to the SAME fixed point — the dependency
``p_n ← {p_j : j > n}`` is strictly triangular — so this ONE backward map
serves both; the suffix scan inside it always uses the differentiable
``ref`` (flip-cumsum) path.

The IFT gives ``dx*/dθ = (I − ∂T/∂x)⁻¹ ∂T/∂θ``; the VJP therefore solves
the adjoint system ``w = g + (∂T/∂x)ᵀ w`` by Neumann/fixed-point
iteration (a ``lax.while_loop`` over the linearized map — NEVER a
backprop through the unrolled solver loop) and returns ``(∂T/∂θ)ᵀ w``.
The alternation is a contraction at regular equilibria (the same property
that makes Algorithm 2 converge), so the Neumann series converges
geometrically.

Validity contract (tested in tests/test_implicit.py):

  * gradients are meaningful only at CONVERGED, FEASIBLE equilibria — the
    fixed-point equation is what the IFT differentiates, and the
    best-iterate safeguard returns a non-fixed-point iterate exactly when
    the solve is infeasible;
  * ``feasible=False`` solves therefore get ZERO cotangents through the
    fixed point (the backward pass masks them), so a vmapped batch with a
    few infeasible draws still yields finite, well-defined gradients —
    only the direct (non-fixed-point) paths through ``_finish`` carry
    gradient for those lanes;
  * the forward solver's tolerances bound the gradient error: the
    returned point satisfies ``|x − T(x)| = O(tol + δ_dinkelbach)``, which
    composes with the ≤1e-3 relative gradcheck budget.

ε (the DT mapping deviation) never enters the leader fixed point — only
the follower finish (``d_hat → α → t_dt → latency``) — so ``∂E/∂ε ≡ 0``
by construction while latency gradients flow; this matches the paper's
Table-less observation that the deviation costs latency, not energy.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from . import noma
from .dinkelbach import _inner_projected, _p_floor
from .sic import suffix_interference
from .stackelberg import (Allocation, GameConfig, _finish, _solve, leader_f,
                          leader_v, local_compute_latency)
from .tracking import TRACE_COUNTS

__all__ = ["FixedPointStatics", "equilibrium_implicit", "fixed_point_step"]


@dataclass(frozen=True)
class FixedPointStatics:
    """Hashable solver statics for the custom_vjp (nondiff_argnums must be
    hashable by value — a ``functools.partial`` would retrace per call)."""
    max_iter: int = 20
    tol: float = 1e-6
    inner: str = "projected"
    sic_mode: str = "sequential"
    adjoint_iters: int = 100
    adjoint_tol: float = 1e-10
    masked: bool = False        # structural flag: mask operand present?


def fixed_point_step(x, theta):
    """One differentiable Algorithm-2 sweep ``T(x, θ)`` (see module doc).

    ``x = (f, p, q)`` each [N]; ``θ = (phys, h2_sorted, D, v)``.  Written
    exclusively with grad-safe closed forms (double-``where`` denominators)
    so its JVP/VJP are finite on masked lanes (h2 = 0), cold-start q = 0
    and saturated clip boundaries.
    """
    f, p, q = x
    phys, h2, D, v = theta
    c, d_bits = phys.cycles_per_sample, phys.model_bits
    dtype = jnp.result_type(h2)

    # --- Dinkelbach power against the current iterate's interference ----
    t_cmp = local_compute_latency(c, v, D, f)
    g_n = jnp.maximum(phys.t_max - t_cmp, 1e-3)         # rate-floor slack
    intf = suffix_interference(p * h2, mode="ref")
    f_eff = h2 / (intf + phys.sigma2)
    lo = jnp.minimum(_p_floor(d_bits, g_n, f_eff, phys.bandwidth,
                              phys.p_min), phys.p_max)
    hi = phys.p_max * jnp.ones_like(lo)
    p_new = _inner_projected(q, d_bits, f_eff, phys.bandwidth, lo, hi)

    # --- Dinkelbach ratio at p_new's own interference -------------------
    intf2 = suffix_interference(p_new * h2, mode="ref")
    f_eff2 = h2 / (intf2 + phys.sigma2)
    rates = phys.bandwidth * noma.log2_1p(p_new * f_eff2)
    u = p_new * d_bits
    u_ok = u > 1e-30
    q_new = jnp.where(u_ok, rates / jnp.where(u_ok, u, jnp.ones((), dtype)),
                      jnp.zeros((), dtype))

    # --- leader frequency runs to the deadline --------------------------
    t_com = noma.tx_latency(d_bits, rates)
    a_n = jnp.maximum(phys.t_max - t_com, 1e-3)
    f_new = leader_f(c, v, D, a_n, phys.f_min, phys.f_max)
    return (f_new, p_new, q_new)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp_solve(statics: FixedPointStatics, phys, h2, D, v, mask_f):
    """Solve the equilibrium fixed point; returns ``(f, p, q, feasible,
    iterations)`` with ``feasible`` as a float (so the backward pass can
    receive/emit well-typed cotangents and mask on it).  ``mask_f`` is the
    padded-bucket mask as floats (all-ones when ``statics.masked`` is
    False); it only shapes the forward reductions — its cotangent is
    zero."""
    TRACE_COUNTS["equilibrium_implicit"] += 1
    mask = (mask_f > 0.5) if statics.masked else None
    alloc = _solve(phys, h2, D, v, 0.0, statics.max_iter, statics.tol,
                   statics.inner, statics.sic_mode, mask)
    dtype = jnp.result_type(h2)
    return (alloc.f, alloc.p, alloc.q,
            jnp.asarray(alloc.feasible, dtype), alloc.iterations)


def _fp_fwd(statics, phys, h2, D, v, mask_f):
    TRACE_COUNTS["equilibrium_implicit_fwd"] += 1
    out = _fp_solve(statics, phys, h2, D, v, mask_f)
    f, p, q, feas, _it = out
    return out, (phys, h2, D, v, f, p, q, feas, mask_f)


def _fp_bwd(statics, res, cotangents):
    TRACE_COUNTS["equilibrium_implicit_bwd"] += 1
    phys, h2, D, v, f, p, q, feas, mask_f = res
    gf, gp, gq, _gfeas, _git = cotangents
    x = (f, p, q)
    theta = (phys, h2, D, v)

    # contract: infeasible solves are not fixed points of T (best-iterate
    # safeguard) — their cotangents through the equilibrium are zeroed
    ok = feas > 0.5
    g = tuple(jnp.where(ok, t, jnp.zeros_like(t)) for t in (gf, gp, gq))

    # Neumann/fixed-point adjoint:  w ← g + (∂T/∂x)ᵀ w   at (x*, θ)
    _, vjp_x = jax.vjp(lambda xx: fixed_point_step(xx, theta), x)
    tol = statics.adjoint_tol

    def cond(carry):
        _w, delta, it = carry
        return (delta > tol) & (it < statics.adjoint_iters)

    def body(carry):
        w, _delta, it = carry
        (aw,) = vjp_x(w)
        w_new = tuple(gi + ai for gi, ai in zip(g, aw))
        delta = sum(jnp.max(jnp.abs(wn - wo))
                    for wn, wo in zip(w_new, w))
        return (w_new, delta, it + 1)

    dtype = jnp.result_type(h2)
    w0 = (g, jnp.asarray(jnp.inf, dtype), jnp.asarray(0, jnp.int32))
    w, _delta, _it = jax.lax.while_loop(cond, body, w0)

    # pull the adjoint back through θ:  ḡθ = (∂T/∂θ)ᵀ w
    _, vjp_theta = jax.vjp(lambda th: fixed_point_step(x, th), theta)
    (gtheta,) = vjp_theta(w)
    return gtheta + (jnp.zeros_like(mask_f),)  # (phys, h2, D, v, mask_f)


_fp_solve.defvjp(_fp_fwd, _fp_bwd)


def equilibrium_implicit(cfg, h2_sorted, D, v_max, epsilon=0.0,
                         max_iter: int = 20, tol: float = 1e-6,
                         inner: str | None = None,
                         sic_mode: str | None = None,
                         mask=None,
                         adjoint_iters: int = 100,
                         adjoint_tol: float = 1e-10) -> Allocation:
    """Differentiable Algorithm 2: identical forward values to
    ``equilibrium`` (same ``_solve``), with gradients through the solution
    via the IFT custom_vjp instead of the opaque while_loop.

    ``cfg`` may be a ``GameConfig`` (floats — physics constants, no
    gradient) or a ``GamePhysics`` pytree of traced scalars (the
    mechanism layer differentiates through these).  Traceable: jit/vmap
    this freely — each (shape, statics) pair compiles once
    (``TRACE_COUNTS['equilibrium_implicit']``).

    Gradients flow into every θ leaf (physics scalars, channel gains,
    data sizes, v_max) and into ``epsilon`` through the follower finish;
    see the module docstring for the feasibility contract.
    """
    if isinstance(cfg, GameConfig):
        if inner is None:
            inner = cfg.dinkelbach_inner
        if sic_mode is None:
            sic_mode = cfg.sic_mode
        phys = cfg.physics(jnp.result_type(jnp.asarray(h2_sorted)))
    else:
        phys = cfg
        inner = inner or "projected"
        sic_mode = sic_mode or "sequential"
    statics = FixedPointStatics(max_iter=max_iter, tol=float(tol),
                                inner=inner, sic_mode=sic_mode,
                                adjoint_iters=adjoint_iters,
                                adjoint_tol=float(adjoint_tol),
                                masked=mask is not None)
    h2 = jnp.asarray(h2_sorted)
    n = h2.shape[0]
    dtype = jnp.result_type(h2)
    D = jnp.broadcast_to(jnp.asarray(D, dtype), (n,))
    v = leader_v(jnp.broadcast_to(jnp.asarray(v_max, dtype), (n,)))
    epsilon = jnp.asarray(epsilon, dtype)
    d_hat = v * D + epsilon
    if mask is not None:
        zero = jnp.zeros((), dtype)
        v = jnp.where(mask, v, zero)
        d_hat = jnp.where(mask, d_hat, zero)
        mask_f = mask.astype(dtype)
    else:
        mask_f = jnp.ones((n,), dtype)
    f, p, q, feas, iters = _fp_solve(statics, phys, h2, D, v, mask_f)
    return _finish(phys, h2, D, v, f, p, q, d_hat, iters, feas > 0.5, mask)
