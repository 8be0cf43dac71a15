"""Large-N SIC transmit-power engine (paper §V-B-3, Eqs. 35–45).

The paper optimizes the N clients' uplink powers SUCCESSIVELY in SIC decode
order: client n's Dinkelbach subproblem (Eqs. 38–45) sees the effective
gain

    F_n = |h_n|² / (Σ_{j>n} p_j·|h_j|² + σ²)              (Eq. 36 denominator)

built from the ALREADY-optimized powers of later-decoded clients, so the
reference implementation (``dinkelbach.successive_power``) is an O(N)
sequential reverse ``lax.scan`` — exact in one pass (reverse Gauss–Seidel
on a strictly triangular dependency), but serial in N: the ROADMAP's
large-N open item.

This module computes the SAME fixed point with Jacobi-style sweeps that
parallelize over the client axis:

  sweep k:   I_n ← Σ_{j>n} p_j^{(k)}·|h_j|²     (parallel suffix scan)
             p_n^{(k+1)} ← Dinkelbach(F_n(I_n))  (vmap over all N clients)

iterated inside a ``lax.while_loop`` until the power vector is stationary
(max|Δp| ≤ 1e-6·p_max).  Convergence argument: the dependency p_n ← {p_j :
j > n} is strictly triangular, so after sweep k the trailing k clients'
powers are EXACT — N sweeps reproduce the sequential solution identically,
and the while-loop bound is set to N as that backstop.  In practice the
interference coupling is a strong contraction (σ² plus later powers damp
each update) and the sweeps converge geometrically: ~4–17 sweeps at any N
measured (so the blocked engine does O(sweeps·N) parallel work instead of
an O(N) serial chain).  A stationary point of the sweep map IS the unique
SIC fixed point, so parity with the sequential scan is ≤1e-5 by
construction (asserted in tests/test_sic.py).

The suffix interference Σ_{j>n} p_j|h_j|² is an exclusive suffix sum —
routed through ``kernels.ops.sic_suffix_sum`` with the same mode switch as
the model kernels (``auto | pallas | interpret | ref``): jnp flip-cumsum
oracle on CPU, blocked Pallas scan (``kernels/sic_suffix.py``) on TPU or
under the CPU interpreter for validation.

Padded (masked) tails — the ragged-N serving contract: the allocation
service (``repro.launch.alloc_serve``) pads variable-N cells up to a
bucket width with ZERO channel gains at the tail of the SIC order.  Both
engines here are invariant to such tails by construction, with no mask
operand needed at this level:

  * interference: a padded lane contributes p·|h|² = p·0 = 0 to every
    suffix sum, so real clients' effective gains F_n match the exact-N
    solve — bit-identical through the Pallas kernel's sequential carry
    (zero blocks add exactly 0.0); the jnp flip-cumsum oracle is an XLA
    associative tree whose shape changes with padding, so it lands
    within the repo's 1e-5 relative budget instead;
  * the padded lane itself: F = 0 ⇒ rate ≡ 0, the Dinkelbach rate-floor
    power goes to +inf and is clipped to the box top, so p = p_max,
    q = 0 — finite, and discarded by the service's mask anyway;
  * SIC ordering: gains sort descending, so an all-zero tail never
    interleaves with real clients;
  * sweep count (blocked engine): padded lanes are stationary after the
    first sweep (Δp = 0), so the while-loop exit is driven by the real
    lanes exactly as in the exact-N solve.

``tests/test_sic.py::TestPaddedTail`` asserts all of this; the masking of
round-level reductions (latency maxima, energy sums) lives one level up
in ``stackelberg.round_metrics``.

Mode switch (the static ``sic_mode`` key on ``GameConfig``, threaded
through every engine tier):

  * ``sequential``        — the reverse-scan reference (default);
  * ``blocked``           — Jacobi sweeps, jnp suffix scan;
  * ``blocked_interpret`` — Jacobi sweeps, Pallas suffix kernel in
                            interpret mode (CPU validation of the kernel);
  * ``blocked_pallas``    — Jacobi sweeps, compiled Pallas suffix kernel
                            (TPU backends).

Differentiability contract (the IFT path, ``core.implicit``): BOTH
families converge to the SAME fixed point — the dependency ``p_n ← {p_j :
j > n}`` is strictly triangular — so reverse-mode gradients through the
equilibrium never differentiate these solvers at all.  The ``custom_vjp``
linearizes ONE differentiable Algorithm-2 sweep at the solution instead,
and that sweep always takes ``suffix_interference(..., mode="ref")``: the
flip-cumsum closed form is the designated grad-safe path, while the
scan/while_loop/Pallas engines here remain forward-value-only (their
1e-6-clamped update rules would need the double-``where`` treatment of
``dinkelbach._inner_projected`` if anyone ever backprops them directly —
don't; route gradients through ``equilibrium_implicit``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.ops import sic_suffix_sum
from .dinkelbach import dinkelbach_power, successive_power
from .noma import log2_1p
from .tracking import TRACE_COUNTS

SIC_MODES = ("sequential", "blocked", "blocked_interpret", "blocked_pallas")

# sic_mode → the kernels.ops.sic_suffix_sum mode the sweeps refresh with
_SUFFIX_MODE = {"blocked": "ref", "blocked_interpret": "interpret",
                "blocked_pallas": "pallas"}

# sweep stationarity: max|Δp| ≤ REL_TOL·p_max exits early; the N-sweep
# backstop guarantees the exact sequential fixed point regardless
REL_TOL = 1e-6


def suffix_interference(w, mode: str = "ref", block: int = 128):
    """Exclusive suffix sum s[..., n] = Σ_{j>n} w[..., j] — the interference
    each client sees from later-decoded clients (w = p·|h|²)."""
    return sic_suffix_sum(w, block=block, mode=mode)


@partial(jax.jit, static_argnames=("inner", "suffix_mode", "max_sweeps",
                                   "return_sweeps", "early_exit"))
def successive_power_blocked(h2_sorted, d, g, bandwidth, sigma2, p_min,
                             p_max, inner: str = "projected",
                             suffix_mode: str = "ref",
                             max_sweeps: int | None = None,
                             return_sweeps: bool = False,
                             early_exit: bool = True):
    """All N clients' powers via Jacobi fixed-point sweeps — same fixed
    point as ``successive_power`` (the sequential reverse scan), but each
    sweep vmaps the N Dinkelbach solves against a frozen interference
    vector and refreshes it with one parallel suffix scan.

    h2_sorted: [N] descending (SIC decode order); d/g broadcast to [N].
    ``max_sweeps`` defaults to N (the exactness backstop — see module
    docstring); ``return_sweeps`` additionally returns the sweep count the
    while-loop actually ran (benchmark instrumentation).
    ``early_exit=False`` disables the stationarity test so the loop runs
    all ``max_sweeps`` sweeps — the triangular-exactness backstop path
    (tests exercise it directly; production callers leave it on).
    """
    TRACE_COUNTS["successive_power_blocked"] += 1
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    bound = n if max_sweeps is None else max_sweeps
    d_v = jnp.broadcast_to(d, h2_sorted.shape).astype(dtype)
    g_v = jnp.broadcast_to(g, h2_sorted.shape).astype(dtype)
    tol = jnp.asarray(REL_TOL, dtype) * p_max

    def sweep(p, q):
        intf = suffix_interference(p * h2_sorted, mode=suffix_mode)
        f_eff = h2_sorted / (intf + sigma2)
        # warm-start each client's Dinkelbach from the previous sweep's q:
        # the interference moves little between late sweeps, so the ratio
        # iteration lands in ~1-2 steps instead of ~6 from a cold start
        # (the fixed point is q-init-independent — see dinkelbach_power)
        p_n, q_n, _ = jax.vmap(
            lambda dd, gg, ff, qq: dinkelbach_power(dd, gg, ff, bandwidth,
                                                    p_min, p_max,
                                                    inner=inner, q_init=qq)
        )(d_v, g_v, f_eff, q)
        return p_n, q_n

    def cond(carry):
        _p, _q, it, done = carry
        return (~done) & (it < bound)

    def body(carry):
        p, q, it, _done = carry
        p_new, q_new = sweep(p, q)
        done = (jnp.max(jnp.abs(p_new - p)) < tol) if early_exit \
            else jnp.asarray(False)
        return (p_new, q_new, it + 1, done)

    p0 = jnp.full(h2_sorted.shape, 1.0, dtype) * p_max
    q0 = jnp.zeros(h2_sorted.shape, dtype)
    p, q, sweeps, _ = jax.lax.while_loop(
        cond, body, (p0, q0, jnp.asarray(0, jnp.int32), jnp.asarray(False)))
    # one unconditional polish sweep: the loop exits when Δp ≤ tol, and the
    # contraction (~0.3×/sweep) pulls the residue well under the ≤1e-5
    # parity budget vs the sequential scan (p-tolerance stacking otherwise
    # amplifies into q through the interference term)
    p, _q = sweep(p, q)
    # q = R(p*)/U(p*) at the RETURNED p and its own interference — the
    # sweep's Dinkelbach q was evaluated against the previous iterate's
    # interference (one sweep stale), which costs ~1e-4 on q near strong
    # coupling even when p is already stationary
    intf = suffix_interference(p * h2_sorted, mode=suffix_mode)
    f_eff = h2_sorted / (intf + sigma2)
    rate = bandwidth * log2_1p(p * f_eff)
    q = rate / jnp.maximum(p * d_v, 1e-30)
    if return_sweeps:
        return p, q, sweeps
    return p, q


def successive_power_eager(h2_sorted, d, g, bandwidth, sigma2, p_min, p_max,
                           inner: str = "projected"):
    """Host-side reference: a Python loop over clients N → 1, accumulating
    the interference as a float — the slowest, most literal reading of
    §V-B-3, kept purely as the numerical oracle for the scan/blocked
    engines (tests).  Not jit/vmap-able."""
    h2_sorted = jnp.asarray(h2_sorted)
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    d_v = jnp.broadcast_to(jnp.asarray(d, dtype), (n,))
    g_v = jnp.broadcast_to(jnp.asarray(g, dtype), (n,))
    ps, qs = [0.0] * n, [0.0] * n
    intf = 0.0
    for i in range(n - 1, -1, -1):
        f_eff = h2_sorted[i] / (intf + sigma2)
        p_i, q_i, _ = dinkelbach_power(d_v[i], g_v[i], f_eff, bandwidth,
                                       p_min, p_max, inner=inner)
        ps[i], qs[i] = p_i, q_i
        intf = intf + float(p_i) * float(h2_sorted[i])
    return jnp.stack(ps).astype(dtype), jnp.stack(qs).astype(dtype)


def successive_power_any(h2_sorted, d, g, bandwidth, sigma2, p_min, p_max,
                         inner: str = "projected",
                         sic_mode: str = "sequential"):
    """Static-mode dispatch between the sequential reverse scan and the
    blocked fixed-point engine — the single entry the Stackelberg solver
    bodies call, so every tier (single/batched/sweep, and the FL round)
    opts into large-N mode through one key."""
    if sic_mode == "sequential":
        return successive_power(h2_sorted, d, g, bandwidth, sigma2, p_min,
                                p_max, inner=inner)
    if sic_mode not in _SUFFIX_MODE:
        raise ValueError(f"unknown sic_mode {sic_mode!r}; "
                         f"expected one of {SIC_MODES}")
    return successive_power_blocked(h2_sorted, d, g, bandwidth, sigma2,
                                    p_min, p_max, inner=inner,
                                    suffix_mode=_SUFFIX_MODE[sic_mode])
