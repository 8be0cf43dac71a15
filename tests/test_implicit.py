"""IFT custom_vjp vs the finite-difference eager oracle.

``equilibrium_implicit`` must (a) return the exact forward values of the
jitted engine, and (b) produce gradients matching central finite
differences of ``equilibrium_eager`` to ≤1e-3 relative across schemes
(proposed / ideal / wo_dt) × sic_modes (sequential / blocked), with zero
NaN cotangents and zero retraces across repeated calls.

FD oracles need x64: the equilibrium is ~1e0-scale energy built from
~1e-28-scale physics products, so f32 central differences drown in
cancellation long before the 1e-3 budget.
"""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.channel import sample_channel_gains, sample_positions
from repro.core.implicit import equilibrium_implicit
from repro.core.stackelberg import (TRACE_COUNTS, GameConfig, equilibrium,
                                    equilibrium_eager)

N = 6
REL_TOL = 1e-3

# (label, v_max, epsilon) — the three schemes that hit the same solver
SCHEMES = [("proposed", 0.4, 20.0), ("ideal", 0.4, 0.0), ("wo_dt", 0.0, 0.0)]
SIC_MODES = ["sequential", "blocked"]


def _draw(seed=3, n=N, dtype=jnp.float64, scale=100.0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    h2 = jnp.sort(sample_channel_gains(k2, sample_positions(k1, n)))[::-1]
    # ×100 pulls the weakest client inside the deadline → feasible draws
    return (h2 * scale).astype(dtype)


def _loss_implicit(cfg, h2, D, vm, eps, sic_mode):
    al = equilibrium_implicit(cfg.physics(jnp.float64), h2, D, vm, eps,
                              inner=cfg.dinkelbach_inner, sic_mode=sic_mode)
    return al.energy + 0.1 * al.t_total


def _loss_eager(cfg, h2, D, vm, eps):
    al = equilibrium_eager(cfg, h2, D, vm, epsilon=float(eps))
    return float(al.energy + 0.1 * al.t_total)


class TestForwardParity:
    @pytest.mark.parametrize("sic_mode", SIC_MODES)
    def test_values_match_jitted_engine(self, sic_mode):
        cfg = GameConfig(sic_mode=sic_mode)
        h2 = _draw(dtype=jnp.float32)
        ref = equilibrium(cfg, h2, 500.0, 0.4, epsilon=20.0)
        imp = equilibrium_implicit(cfg, h2, 500.0, 0.4, 20.0,
                                   sic_mode=sic_mode)
        for name in ("f", "p", "q", "alpha", "energy", "t_total"):
            np.testing.assert_allclose(np.asarray(getattr(ref, name)),
                                       np.asarray(getattr(imp, name)),
                                       rtol=1e-6, err_msg=name)
        assert bool(ref.feasible) == bool(imp.feasible)


class TestGradcheck:
    @pytest.mark.parametrize("scheme,vmax,eps", SCHEMES,
                             ids=[s[0] for s in SCHEMES])
    @pytest.mark.parametrize("sic_mode", SIC_MODES)
    def test_h2_vmax_eps_gradients_vs_fd(self, scheme, vmax, eps, sic_mode):
        with jax.enable_x64():
            cfg = GameConfig(sic_mode=sic_mode)
            h2 = _draw()
            D = jnp.full((N,), 500.0, jnp.float64)
            vm = jnp.full((N,), vmax, jnp.float64)
            eps64 = jnp.float64(eps)
            assert bool(equilibrium_eager(cfg, h2, D, vm,
                                          epsilon=eps).feasible)

            g_h2, g_vm, g_eps = jax.grad(
                lambda a, b, c: _loss_implicit(cfg, a, D, b, c, sic_mode),
                argnums=(0, 1, 2))(h2, vm, eps64)
            assert bool(jnp.all(jnp.isfinite(g_h2)))
            assert bool(jnp.all(jnp.isfinite(g_vm)))
            assert bool(jnp.isfinite(g_eps))

            # FD on h2 (relative steps keep the SIC order intact)
            fd_h2 = np.zeros(N)
            for j in range(N):
                d = 1e-5 * float(h2[j])
                fd_h2[j] = (_loss_eager(cfg, h2.at[j].add(d), D, vm, eps)
                            - _loss_eager(cfg, h2.at[j].add(-d), D, vm,
                                          eps)) / (2 * d)
            rel = np.abs(np.asarray(g_h2) - fd_h2) / np.maximum(
                np.abs(fd_h2), 1e-6)
            assert rel.max() < REL_TOL, (rel, g_h2, fd_h2)

            # FD on v_max (uniform bump — one probe for the whole vector)
            d = 1e-6
            fd_vm = (_loss_eager(cfg, h2, D, vm + d, eps)
                     - _loss_eager(cfg, h2, D, vm - d, eps)) / (2 * d)
            ad_vm = float(jnp.sum(g_vm))
            assert abs(ad_vm - fd_vm) <= REL_TOL * max(abs(fd_vm), 1e-6)

            # FD on epsilon
            d = 1e-3
            fd_eps = (_loss_eager(cfg, h2, D, vm, eps + d)
                      - _loss_eager(cfg, h2, D, vm, eps - d)) / (2 * d)
            assert abs(float(g_eps) - fd_eps) <= REL_TOL * max(
                abs(fd_eps), 1e-6)

    def test_physics_gradients_vs_fd(self):
        """t_max / model_bits enter through the fixed point only — the
        purest IFT path (no direct ``_finish`` dependence for t_max)."""
        with jax.enable_x64():
            cfg = GameConfig()
            h2 = _draw()
            D = jnp.full((N,), 500.0, jnp.float64)
            vm = jnp.full((N,), 0.4, jnp.float64)

            def loss(tmax, mbits):
                phys = dc.replace(cfg.physics(jnp.float64), t_max=tmax,
                                  model_bits=mbits)
                al = equilibrium_implicit(phys, h2, D, vm, 20.0)
                return al.energy + 0.1 * al.t_total

            g = jax.grad(loss, argnums=(0, 1))(jnp.float64(10.0),
                                               jnp.float64(1e6))

            def eager(tmax, mbits):
                c = dc.replace(cfg, t_max=tmax, model_bits=mbits)
                return _loss_eager(c, h2, D, vm, 20.0)

            fd_t = (eager(10.0 + 1e-4, 1e6) - eager(10.0 - 1e-4, 1e6)) / 2e-4
            fd_m = (eager(10.0, 1e6 + 1.0) - eager(10.0, 1e6 - 1.0)) / 2.0
            for ad, fd in [(float(g[0]), fd_t), (float(g[1]), fd_m)]:
                assert abs(ad - fd) <= REL_TOL * max(abs(fd), 1e-8), (ad, fd)

    def test_energy_has_zero_epsilon_gradient(self):
        """ε never enters the leader fixed point: dE/dε ≡ 0 by
        construction (only latency moves)."""
        with jax.enable_x64():
            cfg = GameConfig()
            h2 = _draw()
            g = jax.grad(lambda e: equilibrium_implicit(
                cfg.physics(jnp.float64), h2,
                jnp.full((N,), 500.0, jnp.float64),
                jnp.full((N,), 0.4, jnp.float64), e).energy)(jnp.float64(20.))
            assert float(g) == 0.0


class TestFeasibilityContract:
    def test_infeasible_solve_gets_zero_fixed_point_cotangents(self):
        """An infeasible draw (weak channel, blown deadline) must yield
        finite gradients with NO flow through the fixed point — t_max
        touches the solve only through the fixed point, so its gradient
        is exactly zero."""
        cfg = GameConfig()
        h2 = _draw(seed=0, dtype=jnp.float32, scale=1.0)   # raw gains: weak
        assert not bool(equilibrium(cfg, h2, 500.0, 0.4,
                                    epsilon=20.0).feasible)

        def loss(tmax, vm):
            phys = dc.replace(cfg.physics(jnp.float32),
                              t_max=tmax)
            al = equilibrium_implicit(phys, h2, 500.0, vm, 20.0)
            return al.energy + 0.1 * al.t_total

        g_tmax, g_vm = jax.grad(loss, argnums=(0, 1))(
            jnp.float32(10.0), jnp.full((N,), 0.4))
        assert float(g_tmax) == 0.0
        assert bool(jnp.all(jnp.isfinite(g_vm)))   # direct _finish path


class TestMaskedLanes:
    def test_masked_bucket_matches_exact_solve_and_grads_finite(self):
        """A padded bucket (zero-gain tail + mask) must match the exact-N
        solve forward and carry finite gradients on the real lanes."""
        cfg = GameConfig()
        h2 = _draw(dtype=jnp.float32)
        pad = 2
        h2_pad = jnp.concatenate([h2, jnp.zeros((pad,))])
        mask = jnp.arange(N + pad) < N
        D_pad = jnp.full((N + pad,), 500.0)
        vm_pad = jnp.full((N + pad,), 0.4)

        exact = equilibrium_implicit(cfg, h2, 500.0, 0.4, 20.0)
        padded = equilibrium_implicit(cfg, h2_pad, D_pad, vm_pad, 20.0,
                                      mask=mask)
        np.testing.assert_allclose(np.asarray(padded.p[:N]),
                                   np.asarray(exact.p), rtol=1e-6)
        np.testing.assert_allclose(float(padded.energy),
                                   float(exact.energy), rtol=1e-6)
        assert bool(padded.feasible)

        def loss(h2_, vm_):
            al = equilibrium_implicit(cfg, h2_, D_pad, vm_, 20.0, mask=mask)
            return al.energy + 0.1 * al.t_total

        g_h2, g_vm = jax.grad(loss, argnums=(0, 1))(h2_pad, vm_pad)
        assert bool(jnp.all(jnp.isfinite(g_h2)))
        assert bool(jnp.all(jnp.isfinite(g_vm)))


class TestZeroRetrace:
    def test_vjp_adds_no_new_compile_keys_across_values(self):
        """One jitted grad entry, many operand values → the custom_vjp
        forward/backward trace exactly once; swapping VALUES must not
        retrace.  Differentiate wrt h2 — an input that enters the fixed
        point — so the VJP rule is actually on the grad path (an ε-only
        grad is pruned to the primal, since ε bypasses the fixed point)."""
        cfg = GameConfig()
        h2a = _draw(seed=3, dtype=jnp.float32)
        h2b = _draw(seed=4, dtype=jnp.float32)
        D = jnp.full((N,), 500.0)
        vm = jnp.full((N,), 0.4)

        @jax.jit
        def gradfn(h2, eps):
            def loss(h2_):
                al = equilibrium_implicit(cfg.physics(jnp.float32), h2_,
                                          D, vm, eps)
                return al.energy + 0.1 * al.t_total
            return jax.grad(loss)(h2)

        before_f = TRACE_COUNTS["equilibrium_implicit_fwd"]
        before_b = TRACE_COUNTS["equilibrium_implicit_bwd"]
        g1 = gradfn(h2a, jnp.float32(20.0))
        g2 = gradfn(h2b, jnp.float32(5.0))
        g3 = gradfn(h2a, jnp.float32(0.0))
        for g in (g1, g2, g3):
            assert bool(jnp.all(jnp.isfinite(g)))
        # one compile → one fwd trace, one bwd trace; NO growth after
        assert TRACE_COUNTS["equilibrium_implicit_fwd"] - before_f == 1
        assert TRACE_COUNTS["equilibrium_implicit_bwd"] - before_b == 1
