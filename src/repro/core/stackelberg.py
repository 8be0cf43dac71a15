"""Stackelberg game between clients (leader, minimize energy E) and the
server (follower, minimize latency T) — paper §IV–V.

Closed-form structure used by ``equilibrium`` (Algorithm 2):

  follower (Theorem 1):  equal DT finish times t_1^S = … = t_N^S = t^S.
      case 1 (server slack):   α_n* = c_n·D̂_n / (t_total·f_S)      (Eq. 26)
      case 2 (server saturated): α_n* = c_n·D̂_n / Σ_m c_m·D̂_m      (Eq. 29)

  leader, decomposed (§V-B):
      v_n* = v_n_max                                               (§V-B-1)
      f_n* = max(f̃_n, f_min),  f̃_n = (1−v_n)·c_n·D_n / A_n        (§V-B-2)
      p_n* via successive Dinkelbach                               (§V-B-3)

Engine layout — ONE compiled program per (scheme, shape), shared by every
parameterization:

  * ``GameConfig``   — the user-facing Table-I record (plain floats,
    hashable).  Only ``dinkelbach_inner`` is a static jit argument; all
    physics floats are lowered to a ``GamePhysics`` pytree of traced
    array operands via ``GameConfig.physics()``, so sweeping bandwidth /
    t_max / model_bits / … re-uses the same XLA executable instead of
    recompiling per point.
  * ``equilibrium``         — single instance, fully jitted ``lax.while_loop``
    Alg.-2 alternation with the best-iterate safeguard carried as arrays.
  * ``batched_equilibrium`` — ``vmap`` over K independent realizations
    ``h2_batch[K, N]``; the K axis is sharded across available devices
    (single-device fallback is a no-op).
  * ``sweep_equilibrium``   — ``vmap`` over a leading config axis ON TOP of
    the K axis: the whole benchmark grid (C config points × K channel
    draws) is one dispatch of one executable.  ``epsilon`` may also vary
    along the config axis (fig6's deviation sweep).
  * OMA-FDMA / OMA-TDMA / random baselines get the same three tiers
    (``oma_allocation`` / ``batched_oma_allocation`` / ``sweep_oma_allocation``
    etc.), so ``fl_round.allocate_batched`` works for every scheme.
  * ``equilibrium_eager``   — the legacy host-side Python loop, kept as the
    numerical reference for tests and the throughput microbench.

``TRACE_COUNTS`` counts actual traces of each jitted entry point (the
Python body only runs when XLA compiles a new specialization), which is
how the recompile-count tests and the benchmark's ``recompiles`` field
prove the zero-mid-sweep-recompile property.

``Allocation`` is registered as a pytree so whole solves can cross
``jit``/``vmap`` boundaries; under ``batched_equilibrium`` every field
gains a leading K axis, under ``sweep_equilibrium`` a [C, K] prefix.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import noma
from ..sharding import game_mesh
from .channel import BANDWIDTH_HZ, noise_power
from .dinkelbach import dinkelbach_power
from .sic import SIC_MODES, successive_power_any
# re-exported from .tracking (the historical import site for both)
from .tracking import TRACE_COUNTS, reset_trace_counts, span

TAU = 2e-28  # effective capacitance coefficient (Table I / [22])


@dataclass(frozen=True)
class GameConfig:
    """Table I simulation parameters (plain floats, hashable).

    The physics fields are NOT static jit arguments: the solvers receive
    them as a traced ``GamePhysics`` pytree (see ``physics()``), so any
    number of distinct parameterizations share one compiled engine.  Only
    ``dinkelbach_inner`` and ``sic_mode`` (algorithm choices, not
    operands) stay static.

    ``sic_mode`` selects the successive-power engine (``repro.core.sic``):
    ``sequential`` (the paper's reverse-scan SIC chain, default) or
    ``blocked`` / ``blocked_interpret`` / ``blocked_pallas`` (Jacobi
    fixed-point sweeps for large N, suffix interference via jnp or the
    Pallas kernel) — every tier (single/batched/sweep, and the FL round)
    reads it off the config.
    """
    bandwidth: float = BANDWIDTH_HZ
    sigma2: float = field(default_factory=noise_power)
    p_min: float = 0.01
    p_max: float = 0.10
    f_min: float = 1.0e9
    f_max: float = 10.0e9
    f_server: float = 100.0e9
    t_max: float = 10.0
    cycles_per_sample: float = 1.0e7          # c_n
    model_bits: float = 1.0e6                 # d_n = 1 Mbit
    tau: float = TAU
    dinkelbach_inner: str = "projected"
    sic_mode: str = "sequential"

    def physics(self, dtype=jnp.float32) -> "GamePhysics":
        """Traced-operand view of the physics fields (scalar leaves)."""
        return GamePhysics(**{name: jnp.asarray(getattr(self, name), dtype)
                              for name in _PHYSICS_FIELDS})


@dataclass(frozen=True)
class GamePhysics:
    """The traced remainder of ``GameConfig``: every field is a JAX array
    operand (scalar per instance; [C] under a config-axis ``vmap``).

    Registered as a pytree so it flows through jit/vmap; attribute names
    mirror ``GameConfig`` so the solver bodies are polymorphic over both
    (the eager reference path passes a ``GameConfig`` directly).
    """
    bandwidth: jax.Array
    sigma2: jax.Array
    p_min: jax.Array
    p_max: jax.Array
    f_min: jax.Array
    f_max: jax.Array
    f_server: jax.Array
    t_max: jax.Array
    cycles_per_sample: jax.Array
    model_bits: jax.Array
    tau: jax.Array


_PHYSICS_FIELDS = tuple(f.name for f in dataclasses.fields(GamePhysics))
jax.tree_util.register_dataclass(GamePhysics, data_fields=_PHYSICS_FIELDS,
                                 meta_fields=())


def stack_physics(configs: Sequence[GameConfig],
                  dtype=jnp.float32) -> GamePhysics:
    """Stack C configs into a GamePhysics with [C]-shaped leaves — the
    leading config axis of ``sweep_equilibrium``.  All configs must agree
    on the static keys ``dinkelbach_inner`` and ``sic_mode``."""
    inners = {c.dinkelbach_inner for c in configs}
    if len(inners) != 1:
        raise ValueError(f"sweep configs mix dinkelbach_inner={inners}; "
                         "the inner solver is static — sweep each separately")
    modes = {c.sic_mode for c in configs}
    if len(modes) != 1:
        raise ValueError(f"sweep configs mix sic_mode={modes}; the SIC "
                         "engine choice is static — sweep each separately")
    return GamePhysics(**{name: jnp.asarray([getattr(c, name)
                                             for c in configs], dtype)
                          for name in _PHYSICS_FIELDS})


# ---------------------------------------------------------------------------
# device sharding — unified mesh layer (see sharding/game_mesh.py)
# ---------------------------------------------------------------------------
# Batched/sweep tiers pad their batch axes to a device multiple
# (edge-replicated lanes, sliced off the outputs by ``_unpad``) and run
# under ``shard_map`` — one independent while_loop per device — instead
# of GSPMD hints, whose global convergence predicate serializes devices.
# ``sharding_layout``/``_shard_axis`` remain as the legacy placement API
# (bench reporting, external callers).
sharding_layout = game_mesh.layout_1d
_shard_axis = game_mesh.put_axis
_CFG, _DRAW = game_mesh.CFG_AXIS, game_mesh.DRAW_AXIS


def _unpad(alloc: "Allocation", *dims: int) -> "Allocation":
    """Slice a batched/sweep ``Allocation``'s leading axes back to the
    caller's logical sizes (no-op when nothing was padded)."""
    if tuple(alloc.v.shape[:len(dims)]) == dims:
        return alloc
    sl = tuple(slice(0, d) for d in dims)
    return jax.tree_util.tree_map(lambda x: x[sl], alloc)


# ---------------------------------------------------------------------------
# per-term physics (paper Eqs. 5–7, 10–11)
# ---------------------------------------------------------------------------
def local_compute_latency(c, v, D, f):
    return c * (1.0 - v) * D / f                                    # Eq. (5)


def local_compute_energy(c, v, D, f, tau=TAU):
    return 0.5 * tau * c * (1.0 - v) * D * f ** 2                   # Eq. (6)


def dt_compute_latency(c, d_hat, alpha, f_server):
    """Eq. (7), grad-safe: the α = 0 lane (masked client, zero DT load)
    must not divide by the 1e-12 clamp inside the live branch — reverse
    mode would scale its cotangent by 1e12 and, composed with an inf
    upstream, NaN.  Double-``where`` keeps the forward value bit-identical
    to ``load / (max(α, 1e-12)·f_server)`` in both regimes."""
    load = c * d_hat
    ok = alpha > 1e-12
    return jnp.where(ok, load / (jnp.where(ok, alpha, 1.0) * f_server),
                     load * 1e12 / f_server)


# ---------------------------------------------------------------------------
# follower: Theorem 1
# ---------------------------------------------------------------------------
def follower_alpha(c, d_hat, t_total, f_server) -> Tuple[jax.Array, jax.Array]:
    """Optimal DT frequency shares.  Returns (alpha [N], t_S scalar).

    The Eq.-26 denominator is guarded: a degenerate cell with zero DT load
    AND zero round latency (every client masked out in a padded serving
    bucket) is 0/0 without the floor, and the NaN would leak into
    ``t_dt``/latency of that lane.

    Both guards are double-``where`` rather than ``max(·, 1e-12)``: the
    clamp is forward-equivalent (``load·1e12`` IS ``load / 1e-12``) but
    reverse-mode through the clamped branch multiplies cotangents by 1e12
    and — through the branch a ``where`` upstream discards — turns any
    inf into NaN.  With the safe denominator in the untaken branch every
    cotangent stays finite (tests/test_grad_edges.py)."""
    load = c * d_hat                                # CPU cycles per client
    den1 = t_total * f_server
    den1_ok = den1 > 1e-12
    alpha_case1 = jnp.where(                                      # Eq. (26)
        den1_ok, load / jnp.where(den1_ok, den1, 1.0), load * 1e12)
    saturated = jnp.sum(alpha_case1) > 1.0
    den2 = jnp.sum(load)
    den2_ok = den2 > 1e-12
    alpha_case2 = jnp.where(                                      # Eq. (29)
        den2_ok, load / jnp.where(den2_ok, den2, 1.0), load * 1e12)
    alpha = jnp.where(saturated, alpha_case2, alpha_case1)
    t_s = jnp.where(saturated, jnp.sum(load) / f_server, t_total)
    return alpha, t_s


# ---------------------------------------------------------------------------
# leader closed forms
# ---------------------------------------------------------------------------
def leader_v(v_max):
    """§V-B-1: map the maximum insensitive fraction."""
    return v_max


def leader_f(c, v, D, a_n, f_min, f_max):
    """§V-B-2: run exactly at the deadline, floor at f_min."""
    f_tilde = c * (1.0 - v) * D / jnp.maximum(a_n, 1e-9)
    return jnp.clip(jnp.maximum(f_tilde, f_min), f_min, f_max)


# ---------------------------------------------------------------------------
# Algorithm 2: joint equilibrium
# ---------------------------------------------------------------------------
@dataclass
class Allocation:
    v: jax.Array
    f: jax.Array
    p: jax.Array
    alpha: jax.Array
    rates: jax.Array
    q: jax.Array           # per-client Dinkelbach optima (rate per energy)
    t_cmp: jax.Array
    t_com: jax.Array
    t_dt: jax.Array
    t_total: jax.Array     # scalar round latency T (Eq. 17)
    energy: jax.Array      # scalar total energy E (Eq. 18)
    e_cmp: jax.Array
    e_com: jax.Array
    iterations: jax.Array | int = 0
    feasible: jax.Array | bool = True   # best iterate met the deadline


_ALLOC_FIELDS = tuple(f.name for f in dataclasses.fields(Allocation))
# pytree registration: every field is a data leaf, so Allocation flows
# through jit/vmap/scan; batched solves stack each field on a leading axis.
jax.tree_util.register_dataclass(Allocation, data_fields=_ALLOC_FIELDS,
                                 meta_fields=())


def round_metrics(cfg, D, v, f, p, h2_sorted, mask=None):
    """Per-client latency/energy terms.  ``cfg`` may be a ``GameConfig``
    (floats — eager paths, tests) or a ``GamePhysics`` (traced).

    ``mask`` (optional [N] bool, a traced operand) marks the REAL clients
    of a padded serving bucket.  Padded lanes carry h2 = 0 so they are
    invisible to the SIC interference chain (p·|h|² = 0 contributes
    nothing to any real client's suffix sum), but their zero rate would
    otherwise surface as a huge ``t_com`` (= d / rate-floor) that poisons
    the round maxima and energy sums — so every per-client term is zeroed
    on masked-out lanes with ``where`` (NOT multiplication: 0·inf = NaN).
    ``mask=None`` compiles the exact pre-existing unmasked program."""
    rates = noma.noma_rates(p, h2_sorted, cfg.bandwidth, cfg.sigma2)
    t_com = noma.tx_latency(cfg.model_bits, rates)
    t_cmp = local_compute_latency(cfg.cycles_per_sample, v, D, f)
    e_cmp = local_compute_energy(cfg.cycles_per_sample, v, D, f, cfg.tau)
    if mask is not None:
        zero = jnp.zeros((), rates.dtype)
        rates = jnp.where(mask, rates, zero)
        t_com = jnp.where(mask, t_com, zero)
        t_cmp = jnp.where(mask, t_cmp, zero)
        e_cmp = jnp.where(mask, e_cmp, zero)
    e_com = noma.tx_energy(p, t_com)
    return rates, t_cmp, t_com, e_cmp, e_com


def _leader_iteration(cfg, h2_sorted, D, v, f, inner: str,
                      sic_mode: str = "sequential", mask=None):
    """One Alg.-2 leader sweep: p via successive Dinkelbach given the current
    compute times, then f runs to the deadline given the new airtimes.

    Shared verbatim by the eager reference loop and the traced engine so the
    two paths are numerically identical per iteration.  ``inner`` /
    ``sic_mode`` are the static Dinkelbach / SIC-engine choices (the
    non-physics remainder of GameConfig).  ``mask`` (see ``round_metrics``)
    keeps padded-bucket lanes out of the energy sum and the feasibility
    max; the masked lanes' p (pinned at p_max against h2 = 0) never
    perturbs real clients because p·|h|² = 0 in every suffix sum."""
    t_cmp = local_compute_latency(cfg.cycles_per_sample, v, D, f)
    g_n = jnp.maximum(cfg.t_max - t_cmp, 1e-3)        # rate-floor slack
    # the scope names the SIC chain's ops in the compiled module, so a
    # device trace can attribute their time
    with jax.named_scope("sic_power"):
        p, q = successive_power_any(h2_sorted, cfg.model_bits, g_n,
                                    cfg.bandwidth, cfg.sigma2, cfg.p_min,
                                    cfg.p_max, inner=inner,
                                    sic_mode=sic_mode)
    rates = noma.noma_rates(p, h2_sorted, cfg.bandwidth, cfg.sigma2)
    t_com = noma.tx_latency(cfg.model_bits, rates)
    a_n = jnp.maximum(cfg.t_max - t_com, 1e-3)
    f = leader_f(cfg.cycles_per_sample, v, D, a_n, cfg.f_min, cfg.f_max)
    _, t_cmp, t_com, e_cmp, e_com = round_metrics(cfg, D, v, f, p, h2_sorted,
                                                  mask)
    e_total = jnp.sum(e_cmp + e_com)
    feasible = jnp.max(t_cmp + t_com) <= cfg.t_max + 1e-6
    return f, p, q, e_total, feasible


def _finish(cfg, h2_sorted, D, v, f, p, q, d_hat, iterations,
            feasible, mask=None) -> Allocation:
    """Follower best response to the leader's final strategy (Eq. 17)."""
    rates, t_cmp, t_com, e_cmp, e_com = round_metrics(cfg, D, v, f, p,
                                                      h2_sorted, mask)
    t_total = jnp.max(t_cmp + t_com)
    alpha, _t_s = follower_alpha(cfg.cycles_per_sample, d_hat, t_total,
                                 cfg.f_server)
    t_dt = dt_compute_latency(cfg.cycles_per_sample, d_hat, alpha,
                              cfg.f_server)
    latency = jnp.maximum(t_total, jnp.max(t_dt))          # Eq. (17)
    return Allocation(v=v, f=f, p=p, alpha=alpha, rates=rates, q=q,
                      t_cmp=t_cmp, t_com=t_com, t_dt=t_dt,
                      t_total=latency, energy=jnp.sum(e_cmp + e_com),
                      e_cmp=e_cmp, e_com=e_com, iterations=iterations,
                      feasible=feasible)


def _solve(cfg, h2_sorted, D, v_max, epsilon, max_iter: int, tol,
           inner: str = "projected", sic_mode: str = "sequential",
           mask=None) -> Allocation:
    """Traced Alg.-2 alternation: a ``lax.while_loop`` whose carry holds the
    best-iterate safeguard and the convergence flag as arrays.

    The safeguard key is lexicographic (infeasible, energy): Alg-2
    alternation is not guaranteed monotone near infeasible channel draws,
    so we return the lowest-energy deadline-feasible-first iterate —
    same policy as the legacy loop, minus the host syncs.

    ``mask`` ([N] bool operand, default None = all real) is the padded
    serving buckets' ragged-N story: masked lanes must carry h2 = 0 (tail
    of the SIC order) and are erased from d_hat, every latency/energy
    reduction and the feasibility test, so a request solved in a bucket
    with padding is bit-identical to its exact-N solve (asserted in
    tests/test_alloc_serve.py).  ``mask=None`` traces the historical
    unmasked program unchanged.
    """
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    v = leader_v(jnp.broadcast_to(v_max, (n,)).astype(dtype))
    D = jnp.broadcast_to(D, (n,)).astype(dtype)
    d_hat = v * D + epsilon                       # DT-mapped data size
    if mask is not None:
        # padded lanes: no DT load (ε would otherwise leak into the
        # follower's α shares), no insensitive fraction
        zero = jnp.zeros((), dtype)
        v = jnp.where(mask, v, zero)
        d_hat = jnp.where(mask, d_hat, zero)
    f0 = jnp.full((n,), cfg.f_max, dtype)
    p0 = jnp.full((n,), cfg.p_max, dtype)
    q0 = jnp.zeros((n,), dtype)
    inf = jnp.asarray(jnp.inf, dtype)

    def cond(carry):
        *_rest, it, done = carry
        return (~done) & (it < max_iter)

    def body(carry):
        f, p, q, prev_e, bb, be, bf, bp, bq, it, _done = carry
        f, p, q, e, feas = _leader_iteration(cfg, h2_sorted, D, v, f, inner,
                                             sic_mode, mask)
        bad = jnp.where(feas, jnp.asarray(0.0, dtype),
                        jnp.asarray(1.0, dtype))
        # strict lexicographic improvement, matching the legacy tuple compare
        better = (bad < bb) | ((bad == bb) & (e < be))
        bb = jnp.where(better, bad, bb)
        be = jnp.where(better, e, be)
        bf = jnp.where(better, f, bf)
        bp = jnp.where(better, p, bp)
        bq = jnp.where(better, q, bq)
        done = jnp.abs(prev_e - e) < tol * jnp.maximum(e, 1e-12)
        return (f, p, q, e, bb, be, bf, bp, bq, it + 1, done)

    init = (f0, p0, q0, inf,
            jnp.asarray(2.0, dtype), inf, f0, p0, q0,   # best: bad, e, f, p, q
            jnp.asarray(0, jnp.int32), jnp.asarray(False))
    carry = jax.lax.while_loop(cond, body, init)
    _f, _p, _q, _e, bb, _be, bf, bp, bq, it, _done = carry
    return _finish(cfg, h2_sorted, D, v, bf, bp, bq, d_hat, it, bb == 0.0,
                   mask)


@partial(jax.jit, static_argnames=("max_iter", "inner", "sic_mode"))
def _equilibrium_jit(phys, h2_sorted, D, v_max, epsilon, tol, max_iter,
                     inner, sic_mode):
    TRACE_COUNTS["equilibrium"] += 1
    return _solve(phys, h2_sorted, D, v_max, epsilon, max_iter, tol, inner,
                  sic_mode)


@partial(jax.jit, static_argnames=("max_iter", "inner", "sic_mode", "shards"))
def _batched_equilibrium_jit(phys, h2_batch, D_batch, v_max_batch, epsilon,
                             tol, max_iter, inner, sic_mode, shards=1):
    TRACE_COUNTS["batched_equilibrium"] += 1

    def vsolve(ph, h2, d, vm, eps, tl):
        solve1 = lambda hh, dd, vv: _solve(ph, hh, dd, vv, eps, max_iter,
                                           tl, inner, sic_mode)
        return jax.vmap(solve1)(h2, d, vm)

    if shards > 1:
        # one independent while_loop per device over its local K block
        vsolve = jax.shard_map(vsolve, mesh=game_mesh.mesh_1d(shards),
                               in_specs=(P(), P(_DRAW), P(_DRAW), P(_DRAW),
                                         P(), P()),
                               out_specs=P(_DRAW), check_vma=False)
    return vsolve(phys, h2_batch, D_batch, v_max_batch, epsilon, tol)


@partial(jax.jit,
         static_argnames=("max_iter", "inner", "sic_mode", "grid_shards"))
def _sweep_equilibrium_jit(phys, h2_cbn, D_cbn, v_max_cbn, epsilon_c, tol,
                           max_iter, inner, sic_mode, grid_shards=(1, 1)):
    TRACE_COUNTS["sweep_equilibrium"] += 1

    def sweep(ph_c, h2_c, d_c, vm_c, eps_c, tl):
        def solve_config(ph, h2_kn, d_kn, vm_kn, eps):
            solve1 = lambda h2, d, vm: _solve(ph, h2, d, vm, eps, max_iter,
                                              tl, inner, sic_mode)
            return jax.vmap(solve1)(h2_kn, d_kn, vm_kn)

        return jax.vmap(solve_config)(ph_c, h2_c, d_c, vm_c, eps_c)

    dc, dk = grid_shards
    if dc * dk > 1:
        # 2D (cfg, draw) mesh: each device owns a [C/dc, K/dk] grid tile
        grid = P(_CFG, _DRAW)
        sweep = jax.shard_map(sweep, mesh=game_mesh.mesh_2d(dc, dk),
                              in_specs=(P(_CFG), grid, grid, grid, P(_CFG),
                                        P()),
                              out_specs=grid, check_vma=False)
    return sweep(phys, h2_cbn, D_cbn, v_max_cbn, epsilon_c, tol)


@lru_cache(maxsize=512)
def _physics_cached(cfg: GameConfig, dtype) -> GamePhysics:
    """Per-(config, dtype) device scalars, built once — keeps the
    per-dispatch host overhead of the traced-physics design off the
    per-instance hot path (GameConfig is frozen + hashable)."""
    return cfg.physics(dtype)


@lru_cache(maxsize=4096)
def _scalar_cached(value: float, dtype):
    return jnp.asarray(value, dtype)


def _as_operand(x, dtype):
    """Scalar operand with a cached device buffer for python numbers."""
    if isinstance(x, (int, float)):
        return _scalar_cached(float(x), dtype)
    return jnp.asarray(x, dtype)


def _canon_single(cfg: GameConfig, h2_sorted, D, v_max, epsilon, tol):
    """Normalize one instance's operands to a fixed-dtype signature so
    repeated calls (floats vs arrays, different configs) hit one jit cache
    entry."""
    h2_sorted = jnp.asarray(h2_sorted)
    dtype = jnp.result_type(h2_sorted)
    return (_physics_cached(cfg, dtype), h2_sorted,
            jnp.asarray(D, dtype), jnp.asarray(v_max, dtype),
            _as_operand(epsilon, dtype), _as_operand(tol, dtype))


def _canon_batch(cfg: GameConfig, h2_batch, D_batch, v_max_batch, epsilon,
                 tol, shard: bool = True):
    """Normalize batched operands to [K, N] and, on multi-device
    processes, pad K to a device multiple + place the shards.  Returns
    the operands plus ``(shards, k)`` so the entry point can pick the
    shard_map specialization and ``_unpad`` the result."""
    h2_batch = jnp.asarray(h2_batch)
    dtype = jnp.result_type(h2_batch)
    k, n = h2_batch.shape
    D_batch = jnp.broadcast_to(jnp.asarray(D_batch, dtype), (k, n))
    v_max_batch = jnp.broadcast_to(jnp.asarray(v_max_batch, dtype), (k, n))
    shards = game_mesh.batch_shards(k) if shard else 1
    if shards > 1:
        kp = game_mesh.padded_size(k, shards)
        h2_batch, D_batch, v_max_batch = game_mesh.put_batch(
            tuple(game_mesh.pad_axis(a, 0, kp)
                  for a in (h2_batch, D_batch, v_max_batch)),
            axis=0, shards=shards)
    return (_physics_cached(cfg, dtype), h2_batch, D_batch, v_max_batch,
            _as_operand(epsilon, dtype), _as_operand(tol, dtype), shards, k)


def _canon_sweep(configs: Sequence[GameConfig], h2_batch, D, v_max, epsilon,
                 tol, shard: bool = True):
    """[C]-stack the configs and broadcast operands to [C, K, N]; epsilon
    may be scalar or [C] (it rides the config axis — fig6's ε sweep).
    On multi-device processes the C×K grid is padded to the 2D mesh
    factorization and placed; returns extra ``(grid_shards, c, k)`` for
    the shard_map specialization + output ``_unpad``."""
    configs = list(configs)
    c = len(configs)
    h2_batch = jnp.asarray(h2_batch)
    dtype = jnp.result_type(h2_batch)
    if h2_batch.ndim == 2:
        h2_batch = jnp.broadcast_to(h2_batch, (c,) + h2_batch.shape)
    _, k, n = h2_batch.shape
    D = jnp.broadcast_to(jnp.asarray(D, dtype), (c, k, n))
    v_max = jnp.broadcast_to(jnp.asarray(v_max, dtype), (c, k, n))
    eps = jnp.broadcast_to(jnp.asarray(epsilon, dtype), (c,))
    phys = stack_physics(configs, dtype)
    grid = game_mesh.grid_layout(c, k) if shard else (1, 1)
    dc, dk = grid
    if dc * dk > 1:
        cp = game_mesh.padded_size(c, dc)
        kp = game_mesh.padded_size(k, dk)
        h2_batch, D, v_max = game_mesh.put_grid(
            tuple(game_mesh.pad_axis(game_mesh.pad_axis(a, 0, cp), 1, kp)
                  for a in (h2_batch, D, v_max)), grid)
        eps = game_mesh.put_grid_tree(game_mesh.pad_axis(eps, 0, cp), grid,
                                      cfg_only=True)
        phys = game_mesh.put_grid_tree(game_mesh.pad_tree(phys, 0, cp), grid,
                                       cfg_only=True)
    return (phys, h2_batch, D, v_max, eps, jnp.asarray(tol, dtype),
            configs[0].dinkelbach_inner, grid, c, k)


def equilibrium(cfg: GameConfig, h2_sorted, D, v_max, epsilon: float = 0.0,
                max_iter: int = 20, tol: float = 1e-6) -> Allocation:
    """Algorithm 2 — alternate leader/follower best responses to the
    Stackelberg equilibrium, compiled to a single XLA program shared by
    every physics parameterization (only ``dinkelbach_inner`` and the
    shapes specialize the compile).  Inputs sorted by descending channel
    gain.

    h2_sorted : [N] channel power gains (SIC order)
    D         : [N] client data sizes (samples)
    v_max     : [N] max insensitive-data fractions
    """
    phys, h2, D, v_max, eps, tol = _canon_single(cfg, h2_sorted, D, v_max,
                                                 epsilon, tol)
    return _equilibrium_jit(phys, h2, D, v_max, eps, tol, max_iter=max_iter,
                            inner=cfg.dinkelbach_inner,
                            sic_mode=cfg.sic_mode)


# NOTE: the batched/sweep tiers below all run their batch axes through
# ``_canon_batch``/``_canon_sweep``, which pad to a device multiple on
# multi-device processes — every entry point therefore ``_unpad``s its
# result back to the caller's logical shape.


def batched_equilibrium(cfg: GameConfig, h2_batch, D_batch, v_max_batch,
                        epsilon: float = 0.0, max_iter: int = 20,
                        tol: float = 1e-6) -> Allocation:
    """Solve K independent network realizations in ONE XLA call.

    h2_batch    : [K, N] channel power gains, each row in SIC order
    D_batch     : [K, N] or [N] client data sizes (broadcast across K)
    v_max_batch : [K, N] or [N] max insensitive-data fractions

    Returns an ``Allocation`` whose every field carries a leading K axis
    (scalars such as ``energy`` become [K]).  This is the Monte-Carlo
    entry point: thousands of channel draws per benchmark point amortize
    to one compile + one device dispatch, and the K axis is sharded
    across available devices (no-op on one device).
    """
    with span("equilibrium.canon"):
        phys, h2, D, vm, eps, tol, shards, k = _canon_batch(
            cfg, h2_batch, D_batch, v_max_batch, epsilon, tol)
    with span("equilibrium.launch"):
        out = _batched_equilibrium_jit(phys, h2, D, vm, eps, tol,
                                       max_iter=max_iter,
                                       inner=cfg.dinkelbach_inner,
                                       sic_mode=cfg.sic_mode, shards=shards)
        return _unpad(out, k)


def sweep_equilibrium(configs: Sequence[GameConfig], h2_batch, D, v_max,
                      epsilon=0.0, max_iter: int = 20,
                      tol: float = 1e-6) -> Allocation:
    """Solve a whole benchmark grid — C config points × K channel draws —
    in ONE XLA call of ONE executable (zero mid-sweep recompiles).

    configs  : C ``GameConfig`` points (same ``dinkelbach_inner``); their
               physics floats are stacked into a [C]-leaved ``GamePhysics``
               and vmapped over, so distinct t_max / model_bits / bandwidth
               values are array rows, not compile keys.
    h2_batch : [K, N] (shared across configs) or [C, K, N]
    D, v_max : broadcastable to [C, K, N]
    epsilon  : scalar, or [C] to sweep the DT deviation along the config axis

    Returns an ``Allocation`` with a [C, K] leading prefix on every field.
    """
    configs = list(configs)
    phys, h2, D, vm, eps, tol, inner, grid, c, k = _canon_sweep(
        configs, h2_batch, D, v_max, epsilon, tol)
    out = _sweep_equilibrium_jit(phys, h2, D, vm, eps, tol,
                                 max_iter=max_iter, inner=inner,
                                 sic_mode=configs[0].sic_mode,
                                 grid_shards=grid)
    return _unpad(out, c, k)


def equilibrium_eager(cfg: GameConfig, h2_sorted, D, v_max,
                      epsilon: float = 0.0, max_iter: int = 20,
                      tol: float = 1e-6) -> Allocation:
    """Legacy Algorithm 2: host-side Python loop with per-iteration
    ``float()``/``bool()`` device syncs.  Kept as the numerical reference
    for the jitted engine (tests) and as the baseline of
    ``benchmarks/equilibrium_throughput.py``.  Not jit/vmap-able.
    """
    h2_sorted = jnp.asarray(h2_sorted)
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    v = leader_v(jnp.broadcast_to(v_max, (n,)).astype(dtype))
    f = jnp.full((n,), cfg.f_max, dtype)
    p = jnp.full((n,), cfg.p_max, dtype)
    q = jnp.zeros((n,), dtype)
    d_hat = v * jnp.asarray(D, dtype) + epsilon   # DT-mapped data size

    prev_e = jnp.inf
    it = 0
    best = None   # best-iterate safeguard (see _solve)
    for it in range(1, max_iter + 1):
        f, p, q, e_total, feas = _leader_iteration(cfg, h2_sorted, D, v, f,
                                                   cfg.dinkelbach_inner,
                                                   cfg.sic_mode)
        cand = (not bool(feas), float(e_total), (f, p, q))
        if best is None or cand[:2] < best[:2]:
            best = cand
        if jnp.abs(prev_e - e_total) < tol * jnp.maximum(e_total, 1e-12):
            break
        prev_e = e_total
    f, p, q = best[2]
    return _finish(cfg, h2_sorted, D, v, f, p, q, d_hat, it,
                   jnp.asarray(not best[0]))


# ---------------------------------------------------------------------------
# baselines for Fig. 9 — same three-tier layout (single / batched / sweep)
# ---------------------------------------------------------------------------
def _random_body(cfg, key, h2_sorted, D, v_max, epsilon,
                 mask=None) -> Allocation:
    """Random resource allocation baseline (same selection, random p/f/v).
    Traced body shared by the single/batched/sweep entry points and (with
    ``mask``) the padded serving buckets — note the random draws are
    bucket-shaped, so unlike the deterministic schemes a padded solve is
    distributionally, not bitwise, equivalent to the exact-N one."""
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    k1, k2, k3 = jax.random.split(key, 3)
    v = jax.random.uniform(k1, (n,), dtype) * jnp.broadcast_to(
        v_max, (n,)).astype(dtype)
    f = cfg.f_min + jax.random.uniform(k2, (n,), dtype) * (cfg.f_max -
                                                           cfg.f_min)
    p = cfg.p_min + jax.random.uniform(k3, (n,), dtype) * (cfg.p_max -
                                                           cfg.p_min)
    D = jnp.broadcast_to(D, (n,)).astype(dtype)
    d_hat = v * D + epsilon
    if mask is not None:
        zero = jnp.zeros((), dtype)
        v = jnp.where(mask, v, zero)
        d_hat = jnp.where(mask, d_hat, zero)
    rates, t_cmp, t_com, e_cmp, e_com = round_metrics(cfg, D, v, f, p,
                                                      h2_sorted, mask)
    t_total = jnp.max(t_cmp + t_com)
    alpha, _ = follower_alpha(cfg.cycles_per_sample, d_hat, t_total,
                              cfg.f_server)
    t_dt = dt_compute_latency(cfg.cycles_per_sample, d_hat, alpha,
                              cfg.f_server)
    return Allocation(v=v, f=f, p=p, alpha=alpha, rates=rates,
                      q=jnp.zeros((n,), dtype), t_cmp=t_cmp, t_com=t_com,
                      t_dt=t_dt, t_total=jnp.maximum(t_total, jnp.max(t_dt)),
                      energy=jnp.sum(e_cmp + e_com), e_cmp=e_cmp, e_com=e_com,
                      iterations=jnp.asarray(0, jnp.int32),
                      feasible=t_total <= cfg.t_max + 1e-6)


def _oma_body(cfg, h2_sorted, D, v_max, epsilon, inner: str,
              tdma: bool, mask=None) -> Allocation:
    """OMA baseline body — FDMA (B/N sub-bands) or TDMA (sequential
    full-band slots), fully traced: the per-client Dinkelbach solves are a
    client-axis ``vmap`` instead of a host loop, so the whole baseline
    jits/vmaps like the proposed engine.

    With ``mask`` the orthogonal split is over the REAL client count
    Σmask, not the padded bucket width — unlike NOMA (where zero-gain
    padding is invisible by construction), OMA's per-client bandwidth /
    slot share depends on N directly, so a padded solve would otherwise
    hand every real client a thinner sub-band than its exact-N solve."""
    n = h2_sorted.shape[0]
    dtype = jnp.result_type(h2_sorted)
    v = leader_v(jnp.broadcast_to(v_max, (n,)).astype(dtype))
    D = jnp.broadcast_to(D, (n,)).astype(dtype)
    f = jnp.full((n,), cfg.f_max, dtype)
    d_hat = v * D + epsilon
    if mask is not None:
        zero = jnp.zeros((), dtype)
        v = jnp.where(mask, v, zero)
        d_hat = jnp.where(mask, d_hat, zero)
    t_cmp = local_compute_latency(cfg.cycles_per_sample, v, D, f)
    # real-client count: the orthogonal resource divisor (== n unmasked)
    n_eff = n if mask is None else jnp.maximum(
        jnp.sum(mask.astype(dtype)), jnp.ones((), dtype))
    if tdma:
        # per-client slot budget: (Tmax − t_cmp)/N, full band per slot
        g_n = jnp.maximum((cfg.t_max - t_cmp) / n_eff, 1e-3)
        bw, s2 = cfg.bandwidth, cfg.sigma2
    else:
        g_n = jnp.maximum(cfg.t_max - t_cmp, 1e-3)
        bw, s2 = cfg.bandwidth / n_eff, cfg.sigma2 / n_eff

    def solve(h2_n, g_nn):
        p_n, q_n, _ = dinkelbach_power(cfg.model_bits, g_nn, h2_n / s2, bw,
                                       cfg.p_min, cfg.p_max, inner=inner)
        return p_n, q_n

    p, q = jax.vmap(solve)(h2_sorted, g_n)
    if tdma:
        rates = cfg.bandwidth * noma.log2_1p(p * h2_sorted / cfg.sigma2)
        t_own = noma.tx_latency(cfg.model_bits, rates)  # own-slot airtime
        if mask is not None:
            t_own = jnp.where(mask, t_own, jnp.zeros((), dtype))
        t_com = jnp.sum(t_own) * jnp.ones_like(t_own)   # sequential round
    else:
        rates = bw * noma.log2_1p(p * h2_sorted / s2)  # == oma_rates @ n_eff
        t_own = t_com = noma.tx_latency(cfg.model_bits, rates)
        if mask is not None:
            t_own = t_com = jnp.where(mask, t_own, jnp.zeros((), dtype))
    a_n = jnp.maximum(cfg.t_max - t_com, 1e-3)
    f = leader_f(cfg.cycles_per_sample, v, D, a_n, cfg.f_min, cfg.f_max)
    t_cmp = local_compute_latency(cfg.cycles_per_sample, v, D, f)
    e_cmp = local_compute_energy(cfg.cycles_per_sample, v, D, f, cfg.tau)
    e_com = noma.tx_energy(p, t_own)                    # energy over own slot
    if mask is not None:
        zero = jnp.zeros((), dtype)
        rates = jnp.where(mask, rates, zero)
        t_cmp = jnp.where(mask, t_cmp, zero)
        e_cmp = jnp.where(mask, e_cmp, zero)
    t_total = jnp.max(t_cmp + t_com)
    alpha, _ = follower_alpha(cfg.cycles_per_sample, d_hat, t_total,
                              cfg.f_server)
    t_dt = dt_compute_latency(cfg.cycles_per_sample, d_hat, alpha,
                              cfg.f_server)
    return Allocation(v=v, f=f, p=p, alpha=alpha, rates=rates, q=q,
                      t_cmp=t_cmp, t_com=t_com, t_dt=t_dt,
                      t_total=jnp.maximum(t_total, jnp.max(t_dt)),
                      energy=jnp.sum(e_cmp + e_com), e_cmp=e_cmp, e_com=e_com,
                      iterations=jnp.asarray(0, jnp.int32),
                      feasible=t_total <= cfg.t_max + 1e-6)


@partial(jax.jit, static_argnames=("inner",))
def _random_jit(phys, key, h2, D, v_max, epsilon, inner):
    del inner  # random draws never run Dinkelbach; kept for signature parity
    TRACE_COUNTS["random_allocation"] += 1
    return _random_body(phys, key, h2, D, v_max, epsilon)


@partial(jax.jit, static_argnames=("inner", "shards"))
def _batched_random_jit(phys, keys, h2, D, v_max, epsilon, inner, shards=1):
    del inner
    TRACE_COUNTS["batched_random_allocation"] += 1

    def vbody(ph, kk, h2_b, d_b, vm_b, eps):
        body = lambda k1, h, d, vm: _random_body(ph, k1, h, d, vm, eps)
        return jax.vmap(body)(kk, h2_b, d_b, vm_b)

    if shards > 1:
        vbody = jax.shard_map(vbody, mesh=game_mesh.mesh_1d(shards),
                              in_specs=(P(), P(_DRAW), P(_DRAW), P(_DRAW),
                                        P(_DRAW), P()),
                              out_specs=P(_DRAW), check_vma=False)
    return vbody(phys, keys, h2, D, v_max, epsilon)


@partial(jax.jit, static_argnames=("inner", "grid_shards"))
def _sweep_random_jit(phys, keys, h2, D, v_max, epsilon_c, inner,
                      grid_shards=(1, 1)):
    del inner
    TRACE_COUNTS["sweep_random_allocation"] += 1

    def sweep(ph_c, kk, h2_c, d_c, vm_c, eps_c):
        def per_config(ph, h_kn, d_kn, vm_kn, eps):
            body = lambda k1, h, d, vm: _random_body(ph, k1, h, d, vm, eps)
            return jax.vmap(body)(kk, h_kn, d_kn, vm_kn)

        # keys are shared across the config axis (in_axes=None): every
        # config point sees the same K channel/key draws, isolating the
        # config effect (a draw-axis device tile still sees the same key
        # block for each of its config rows)
        return jax.vmap(per_config)(ph_c, h2_c, d_c, vm_c, eps_c)

    dc, dk = grid_shards
    if dc * dk > 1:
        grid = P(_CFG, _DRAW)
        sweep = jax.shard_map(sweep, mesh=game_mesh.mesh_2d(dc, dk),
                              in_specs=(P(_CFG), P(_DRAW), grid, grid, grid,
                                        P(_CFG)),
                              out_specs=grid, check_vma=False)
    return sweep(phys, keys, h2, D, v_max, epsilon_c)


def _oma_variant(tdma: bool) -> str:
    """TRACE_COUNTS key suffix: FDMA and TDMA are distinct static
    specializations, so they must not share a recompile counter."""
    return "oma_tdma_allocation" if tdma else "oma_allocation"


@partial(jax.jit, static_argnames=("inner", "tdma"))
def _oma_jit(phys, h2, D, v_max, epsilon, inner, tdma):
    TRACE_COUNTS[_oma_variant(tdma)] += 1
    return _oma_body(phys, h2, D, v_max, epsilon, inner, tdma)


@partial(jax.jit, static_argnames=("inner", "tdma", "shards"))
def _batched_oma_jit(phys, h2, D, v_max, epsilon, inner, tdma, shards=1):
    TRACE_COUNTS["batched_" + _oma_variant(tdma)] += 1

    def vbody(ph, h2_b, d_b, vm_b, eps):
        body = lambda h, d, vm: _oma_body(ph, h, d, vm, eps, inner, tdma)
        return jax.vmap(body)(h2_b, d_b, vm_b)

    if shards > 1:
        vbody = jax.shard_map(vbody, mesh=game_mesh.mesh_1d(shards),
                              in_specs=(P(), P(_DRAW), P(_DRAW), P(_DRAW),
                                        P()),
                              out_specs=P(_DRAW), check_vma=False)
    return vbody(phys, h2, D, v_max, epsilon)


@partial(jax.jit, static_argnames=("inner", "tdma", "grid_shards"))
def _sweep_oma_jit(phys, h2, D, v_max, epsilon_c, inner, tdma,
                   grid_shards=(1, 1)):
    TRACE_COUNTS["sweep_" + _oma_variant(tdma)] += 1

    def sweep(ph_c, h2_c, d_c, vm_c, eps_c):
        def per_config(ph, h_kn, d_kn, vm_kn, eps):
            body = lambda h, d, vm: _oma_body(ph, h, d, vm, eps, inner, tdma)
            return jax.vmap(body)(h_kn, d_kn, vm_kn)

        return jax.vmap(per_config)(ph_c, h2_c, d_c, vm_c, eps_c)

    dc, dk = grid_shards
    if dc * dk > 1:
        grid = P(_CFG, _DRAW)
        sweep = jax.shard_map(sweep, mesh=game_mesh.mesh_2d(dc, dk),
                              in_specs=(P(_CFG), grid, grid, grid, P(_CFG)),
                              out_specs=grid, check_vma=False)
    return sweep(phys, h2, D, v_max, epsilon_c)


def random_allocation(cfg: GameConfig, key, h2_sorted, D, v_max,
                      epsilon: float = 0.0) -> Allocation:
    """Random resource allocation baseline (same selection, random p/f/v)."""
    phys, h2, D, vm, eps, _ = _canon_single(cfg, h2_sorted, D, v_max,
                                            epsilon, 0.0)
    return _random_jit(phys, key, h2, D, vm, eps, inner=cfg.dinkelbach_inner)


def batched_random_allocation(cfg: GameConfig, key, h2_batch, D_batch,
                              v_max_batch, epsilon: float = 0.0) -> Allocation:
    """K random allocations in one XLA call; per-draw keys are
    ``jax.random.split(key, K)``, so row i reproduces
    ``random_allocation(cfg, jax.random.split(key, K)[i], …)`` exactly."""
    phys, h2, D, vm, eps, _, shards, k = _canon_batch(
        cfg, h2_batch, D_batch, v_max_batch, epsilon, 0.0)
    # split with the LOGICAL k (row i must reproduce the documented
    # per-instance key exactly), then pad keys to the device multiple
    keys = game_mesh.pad_axis(jax.random.split(key, k), 0, h2.shape[0])
    out = _batched_random_jit(phys, keys, h2, D, vm, eps,
                              inner=cfg.dinkelbach_inner, shards=shards)
    return _unpad(out, k)


def sweep_random_allocation(configs: Sequence[GameConfig], key, h2_batch, D,
                            v_max, epsilon=0.0) -> Allocation:
    """C configs × K draws of the random baseline in one call.  The K
    per-draw keys are shared across the config axis (each config point sees
    identical randomness, isolating the config effect)."""
    phys, h2, D, vm, eps, _, inner, grid, c, k = _canon_sweep(
        configs, h2_batch, D, v_max, epsilon, 0.0)
    keys = game_mesh.pad_axis(jax.random.split(key, k), 0, h2.shape[1])
    out = _sweep_random_jit(phys, keys, h2, D, vm, eps, inner=inner,
                            grid_shards=grid)
    return _unpad(out, c, k)


def oma_allocation(cfg: GameConfig, h2_sorted, D, v_max,
                   epsilon: float = 0.0) -> Allocation:
    """OMA baseline (default): FDMA — each client gets a B/N sub-band.

    Bandwidth-limited: at the paper's operating load (d_n ≥ 1 Mbit) the B/N
    sub-bands force long transmissions / higher power, reproducing the
    Fig. 9 OMA penalty.  (At very light load OMA is within ~2% of NOMA —
    regime note in EXPERIMENTS.md §Paper-validation.)"""
    phys, h2, D, vm, eps, _ = _canon_single(cfg, h2_sorted, D, v_max,
                                            epsilon, 0.0)
    return _oma_jit(phys, h2, D, vm, eps, inner=cfg.dinkelbach_inner,
                    tdma=False)


def batched_oma_allocation(cfg: GameConfig, h2_batch, D_batch, v_max_batch,
                           epsilon: float = 0.0) -> Allocation:
    """K OMA-FDMA allocations in one XLA call (K axis device-sharded)."""
    phys, h2, D, vm, eps, _, shards, k = _canon_batch(
        cfg, h2_batch, D_batch, v_max_batch, epsilon, 0.0)
    out = _batched_oma_jit(phys, h2, D, vm, eps, inner=cfg.dinkelbach_inner,
                           tdma=False, shards=shards)
    return _unpad(out, k)


def sweep_oma_allocation(configs: Sequence[GameConfig], h2_batch, D, v_max,
                         epsilon=0.0) -> Allocation:
    """C configs × K draws of the OMA-FDMA baseline in one call."""
    phys, h2, D, vm, eps, _, inner, grid, c, k = _canon_sweep(
        configs, h2_batch, D, v_max, epsilon, 0.0)
    out = _sweep_oma_jit(phys, h2, D, vm, eps, inner=inner, tdma=False,
                         grid_shards=grid)
    return _unpad(out, c, k)


def oma_tdma_allocation(cfg: GameConfig, h2_sorted, D, v_max,
                        epsilon: float = 0.0) -> Allocation:
    """OMA variant: TDMA — sequential full-band slots (round latency Σ t_n,
    the paper's "insufficient clients per round" mechanism)."""
    phys, h2, D, vm, eps, _ = _canon_single(cfg, h2_sorted, D, v_max,
                                            epsilon, 0.0)
    return _oma_jit(phys, h2, D, vm, eps, inner=cfg.dinkelbach_inner,
                    tdma=True)


def batched_oma_tdma_allocation(cfg: GameConfig, h2_batch, D_batch,
                                v_max_batch,
                                epsilon: float = 0.0) -> Allocation:
    """K OMA-TDMA allocations in one XLA call (K axis device-sharded)."""
    phys, h2, D, vm, eps, _, shards, k = _canon_batch(
        cfg, h2_batch, D_batch, v_max_batch, epsilon, 0.0)
    out = _batched_oma_jit(phys, h2, D, vm, eps, inner=cfg.dinkelbach_inner,
                           tdma=True, shards=shards)
    return _unpad(out, k)


def sweep_oma_tdma_allocation(configs: Sequence[GameConfig], h2_batch, D,
                              v_max, epsilon=0.0) -> Allocation:
    """C configs × K draws of the OMA-TDMA baseline in one call."""
    phys, h2, D, vm, eps, _, inner, grid, c, k = _canon_sweep(
        configs, h2_batch, D, v_max, epsilon, 0.0)
    out = _sweep_oma_jit(phys, h2, D, vm, eps, inner=inner, tdma=True,
                         grid_shards=grid)
    return _unpad(out, c, k)


def wo_dt_allocation(cfg: GameConfig, h2_sorted, D) -> Allocation:
    """W/O-DT baseline: v ≡ 0, all training on-client (straggler-exposed).

    Routed through the jitted engine (zero v_max shares the same XLA
    program as the proposed scheme — no extra compile)."""
    h2_sorted = jnp.asarray(h2_sorted)
    zero_vmax = jnp.zeros(h2_sorted.shape, jnp.result_type(h2_sorted))
    return equilibrium(cfg, h2_sorted, D, zero_vmax, epsilon=0.0)


def batched_wo_dt_allocation(cfg: GameConfig, h2_batch, D_batch) -> Allocation:
    """Batched W/O-DT: K realizations with v ≡ 0 in one XLA call."""
    h2_batch = jnp.asarray(h2_batch)
    return batched_equilibrium(cfg, h2_batch, D_batch,
                               jnp.zeros_like(h2_batch), epsilon=0.0)


def sweep_wo_dt_allocation(configs: Sequence[GameConfig], h2_batch,
                           D) -> Allocation:
    """C configs × K draws of the W/O-DT scheme (shares the sweep engine)."""
    h2_batch = jnp.asarray(h2_batch)
    zeros = jnp.zeros(h2_batch.shape[-2:], jnp.result_type(h2_batch))
    return sweep_equilibrium(configs, h2_batch, D, zeros, epsilon=0.0)
