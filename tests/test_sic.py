"""Large-N SIC power engine (ISSUE 5 tentpole): the blocked Jacobi
fixed-point solver, the Pallas suffix-scan kernel, and the ``sic_mode``
static key threaded through every engine tier.

Parity ladder: eager host loop (most literal §V-B-3 reading) == sequential
reverse scan == blocked fixed point ≤1e-5 on (p, q), for every tested N —
including N=1 (no interference at all) and a non-power-of-two N=257 that
exercises the kernel's padded tail block.  Mode ``blocked_interpret``
additionally routes the suffix scan through the Pallas kernel in CPU
interpret mode, validating the kernel body itself on every sweep.

Plus the ISSUE's satellite suites: ``dinkelbach_power`` invariants as
property tests (box membership, rate floor, inner-solver agreement), the
host-loop Fig. 4 trace path vs the jitted ``while_loop`` path, trace-count
proofs for the new entry points, and the forced-4-device sharding check
with the blocked solver.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # offline: seeded example replay (tests/_prop.py)
    from _prop import given, settings, strategies as st

from _multidevice import run_forced_devices

from repro.core.channel import noise_power, sample_sic_channel_batch
from repro.core.dinkelbach import _p_floor, dinkelbach_power, successive_power
from repro.core.sic import (SIC_MODES, successive_power_any,
                            successive_power_blocked, successive_power_eager,
                            suffix_interference)
from repro.core.stackelberg import (GameConfig, TRACE_COUNTS,
                                    batched_equilibrium, equilibrium,
                                    stack_physics, sweep_equilibrium)
from repro.kernels.ops import sic_suffix_sum
from repro.kernels.ref import sic_suffix_ref
from repro.kernels.sic_suffix import sic_suffix_pallas

BW = 1e6
SIGMA2 = noise_power()
P_MIN, P_MAX = 0.01, 0.1
REL = 1e-5


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b) / jnp.maximum(jnp.abs(b), 1e-12)))


def _sic_inputs(n: int, seed: int = 0):
    h2 = sample_sic_channel_batch(jax.random.PRNGKey(seed), 1, n)[0]
    g = 0.5 + 5.0 * jax.random.uniform(jax.random.PRNGKey(seed + 1), (n,))
    return h2, g


# ---------------------------------------------------------------------------
# cross-mode parity: blocked == sequential == eager
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("inner", ["projected", "kkt"])
@pytest.mark.parametrize("n", [1, 2, 5, 64, 257])
def test_blocked_matches_sequential(n, inner):
    """The Jacobi fixed point IS the sequential SIC solution (≤1e-5 on p
    and q) — incl. the N=1 no-interference edge and a non-power-of-two N."""
    h2, g = _sic_inputs(n, seed=n)
    p_s, q_s = successive_power(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX,
                                inner=inner)
    p_b, q_b = successive_power_blocked(h2, 1e6, g, BW, SIGMA2, P_MIN,
                                        P_MAX, inner=inner)
    assert _rel(p_b, p_s) < REL, (n, inner)
    assert _rel(q_b, q_s) < REL, (n, inner)


@pytest.mark.parametrize("n", [1, 2, 5, 64, 257])
def test_blocked_interpret_kernel_path_matches(n):
    """suffix_mode="interpret" runs the Pallas kernel (CPU interpreter)
    inside every sweep — same fixed point as the jnp suffix path."""
    h2, g = _sic_inputs(n, seed=100 + n)
    p_s, q_s = successive_power(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX)
    p_k, q_k = successive_power_blocked(h2, 1e6, g, BW, SIGMA2, P_MIN,
                                        P_MAX, suffix_mode="interpret")
    assert _rel(p_k, p_s) < REL, n
    assert _rel(q_k, q_s) < REL, n


@pytest.mark.parametrize("n", [1, 2, 5])
def test_eager_host_reference_matches(n):
    """The host-side python loop (the most literal reading of §V-B-3)
    agrees with both traced engines."""
    h2, g = _sic_inputs(n, seed=200 + n)
    p_e, q_e = successive_power_eager(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX)
    p_s, q_s = successive_power(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX)
    p_b, q_b = successive_power_blocked(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX)
    assert _rel(p_s, p_e) < REL and _rel(q_s, q_e) < REL
    assert _rel(p_b, p_e) < REL and _rel(q_b, q_e) < REL


def test_blocked_sweep_backstop_is_exact():
    """The N-sweep backstop itself: with the stationarity early-exit
    DISABLED the loop runs all N Jacobi sweeps, and the triangular
    dependency (p_n ← {p_j : j>n}) makes the result the sequential
    solution up to f32 roundoff — the guarantee the while-bound rests on."""
    n = 33
    h2, g = _sic_inputs(n, seed=300)
    p_s, q_s = successive_power(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX)
    p_b, q_b, sweeps = successive_power_blocked(
        h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX, return_sweeps=True,
        early_exit=False)
    assert int(sweeps) == n       # every sweep actually ran
    assert _rel(p_b, p_s) < REL
    assert _rel(q_b, q_s) < REL


def test_blocked_converges_in_few_sweeps():
    """The contraction is strong: the while_loop exits far before the
    N-sweep backstop (the whole point of the blocked engine at large N)."""
    n = 257
    h2, g = _sic_inputs(n, seed=400)
    _p, _q, sweeps = successive_power_blocked(
        h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX, return_sweeps=True)
    assert int(sweeps) <= 16, f"expected geometric convergence, got {sweeps}"


def test_successive_power_any_dispatch_and_validation():
    h2, g = _sic_inputs(5, seed=500)
    p_s, _ = successive_power_any(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX,
                                  sic_mode="sequential")
    p_b, _ = successive_power_any(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX,
                                  sic_mode="blocked")
    assert _rel(p_b, p_s) < REL
    with pytest.raises(ValueError):
        successive_power_any(h2, 1e6, g, BW, SIGMA2, P_MIN, P_MAX,
                             sic_mode="bogus")
    assert "sequential" in SIC_MODES and "blocked" in SIC_MODES


# ---------------------------------------------------------------------------
# suffix kernel: ref / interpret agreement on CPU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,block", [
    ((1, 1), 128),          # single element, whole-pad block
    ((2, 5), 4),            # tail padding
    ((3, 64), 32),          # exact multiple
    ((2, 257), 128),        # non-power-of-two tail
    ((1, 512), 128),        # multi-block carry chain
    ((10, 300), 128),       # several 8-row blocks, padded rows and tail
])
def test_suffix_kernel_matches_ref(shape, block):
    w = jax.random.uniform(jax.random.PRNGKey(shape[1]), shape) * 1e-3
    ref = sic_suffix_ref(w)
    out = sic_suffix_pallas(w, block=block, interpret=True)
    assert out.shape == ref.shape
    # matmul vs cumsum accumulation order: f32 roundoff, scaled by the sum
    scale = float(jnp.max(jnp.abs(ref))) + 1e-12
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5 * scale


def test_suffix_kernel_under_vmap_and_modes():
    """The ops.py mode switch: ``ref`` == ``interpret`` (≤f32 roundoff),
    and the kernel batches under vmap (the batched-engine context)."""
    w = jax.random.uniform(jax.random.PRNGKey(9), (4, 130)) * 1e-2
    ref = sic_suffix_sum(w, mode="ref")
    tol = 1e-5 * float(jnp.max(jnp.abs(ref)))
    out = sic_suffix_sum(w, block=64, mode="interpret")
    assert float(jnp.max(jnp.abs(out - ref))) < tol
    per_row = jax.vmap(lambda x: sic_suffix_sum(x, block=64,
                                                mode="interpret"))(w)
    assert float(jnp.max(jnp.abs(per_row - ref))) < tol
    assert float(jnp.max(jnp.abs(suffix_interference(w, mode="interpret",
                                                     block=64) - ref))) < tol
    # exclusive: last element sees zero interference
    assert float(jnp.max(jnp.abs(ref[:, -1]))) == 0.0


@pytest.mark.parametrize("mode", ["pallas", "bogus"])
def test_suffix_mode_never_falls_back(mode):
    """Off the TPU an explicit ``pallas`` request raises instead of
    quietly running the interpreter; an unknown mode is an error."""
    w = jnp.ones((2, 16))
    err = RuntimeError if mode == "pallas" else ValueError
    with pytest.raises(err):
        sic_suffix_sum(w, mode=mode)


# ---------------------------------------------------------------------------
# sic_mode through every engine tier
# ---------------------------------------------------------------------------
def test_equilibrium_tiers_blocked_parity():
    """single / batched / sweep equilibria with sic_mode="blocked" match
    the sequential engine ≤1e-5 on the full Allocation."""
    n, k = 11, 6
    cfg_s, cfg_b = GameConfig(), GameConfig(sic_mode="blocked")
    h2 = sample_sic_channel_batch(jax.random.PRNGKey(3), k, n)
    d = jnp.full((n,), 200.0)
    vmax = jnp.full((n,), 0.5)
    a_s = batched_equilibrium(cfg_s, h2, d, vmax)
    a_b = batched_equilibrium(cfg_b, h2, d, vmax)
    for field in ("p", "f", "energy", "t_total", "alpha"):
        assert _rel(getattr(a_b, field), getattr(a_s, field)) < REL, field
    one_s = equilibrium(cfg_s, h2[0], d, vmax)
    one_b = equilibrium(cfg_b, h2[0], d, vmax)
    assert _rel(one_b.energy, one_s.energy) < REL
    cfgs_b = [dataclasses.replace(cfg_b, t_max=t) for t in (8.0, 10.0)]
    cfgs_s = [dataclasses.replace(cfg_s, t_max=t) for t in (8.0, 10.0)]
    sw_b = sweep_equilibrium(cfgs_b, h2, d, vmax)
    sw_s = sweep_equilibrium(cfgs_s, h2, d, vmax)
    assert _rel(sw_b.energy, sw_s.energy) < REL
    assert sw_b.energy.shape == (2, k)


def test_stack_physics_rejects_mixed_sic_mode():
    cfgs = [GameConfig(), GameConfig(sic_mode="blocked")]
    with pytest.raises(ValueError):
        stack_physics(cfgs)


# ---------------------------------------------------------------------------
# trace counting: the blocked paths compile once per sweep grid
# ---------------------------------------------------------------------------
def test_blocked_sweep_traces_each_entry_once():
    """A fig9-style grid with sic_mode="blocked" traces the sweep engine
    and the blocked SIC solver exactly once, and re-dispatching with
    different physics VALUES retraces neither.  N=9 is unique to this test
    so the jit cache is genuinely cold."""
    n, k = 9, 4
    base = GameConfig(sic_mode="blocked")
    cfgs = [dataclasses.replace(base, t_max=tm, model_bits=mb)
            for mb in (0.5e6, 2.0e6) for tm in (6.0, 8.0, 10.0)]
    h2 = sample_sic_channel_batch(jax.random.PRNGKey(4), k, n)
    d = jnp.full((n,), 200.0)
    vmax = jnp.full((n,), 0.5)
    before_sweep = TRACE_COUNTS["sweep_equilibrium"]
    before_blocked = TRACE_COUNTS["successive_power_blocked"]
    out = sweep_equilibrium(cfgs, h2, d, vmax)
    assert out.energy.shape == (6, k)
    assert bool(jnp.all(jnp.isfinite(out.energy)))
    assert TRACE_COUNTS["sweep_equilibrium"] - before_sweep == 1
    assert TRACE_COUNTS["successive_power_blocked"] - before_blocked == 1
    shifted = [dataclasses.replace(c, t_max=c.t_max + 1.0) for c in cfgs]
    sweep_equilibrium(shifted, h2, d, vmax)
    assert TRACE_COUNTS["sweep_equilibrium"] - before_sweep == 1, \
        "changing config VALUES must not recompile the blocked sweep"


_SHARD_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.core.channel import sample_sic_channel_batch
from repro.core.stackelberg import (GameConfig, batched_equilibrium,
                                    equilibrium, sharding_layout)
assert len(jax.devices()) == 4, jax.devices()
cfg = GameConfig(sic_mode="blocked")
h2 = sample_sic_channel_batch(jax.random.PRNGKey(0), 8, 16)
d = jnp.full((16,), 200.0); vmax = jnp.full((16,), 0.5)
ab = batched_equilibrium(cfg, h2, d, vmax)
assert len(ab.energy.sharding.device_set) == 4, ab.energy.sharding
for i in (0, 7):
    a1 = equilibrium(cfg, h2[i], d, vmax)
    rel = abs(float(ab.energy[i]) - float(a1.energy)) / abs(float(a1.energy))
    assert rel < 1e-5, (i, rel)
print("SHARDED_BLOCKED_OK")
"""


def test_k_axis_shards_with_blocked_solver():
    """The K axis still device-shards when the blocked SIC engine is the
    solver core (subprocess: device count is fixed at jax import)."""
    run_forced_devices(_SHARD_SCRIPT, marker="SHARDED_BLOCKED_OK")


# ---------------------------------------------------------------------------
# dinkelbach_power invariants (property-based, ISSUE 5 satellite)
# ---------------------------------------------------------------------------
@given(st.floats(1e5, 2e6), st.floats(0.5, 9.0), st.floats(-4.0, -1.0),
       st.floats(-15.0, -13.0))
@settings(max_examples=25, deadline=None)
def test_dinkelbach_box_invariant(d, g, log_h2, log_s2):
    """p* always lies in [min(p_floor, p_max), p_max] — the Eq. 43 box with
    the rate-floor lower bound, whatever the (d, g, h², σ²) draw."""
    f_eff = (10.0 ** log_h2) / (10.0 ** log_s2)
    p, q, _ = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX)
    lo = min(float(_p_floor(d, g, f_eff, BW, P_MIN)), P_MAX)
    assert lo - 1e-9 <= float(p) <= P_MAX + 1e-9
    assert float(q) > 0.0


@given(st.floats(1e5, 2e6), st.floats(0.5, 9.0), st.floats(-4.0, -1.0),
       st.floats(-15.0, -13.0))
@settings(max_examples=25, deadline=None)
def test_dinkelbach_rate_floor_when_admissible(d, g, log_h2, log_s2):
    """Whenever the box admits the rate floor (p_floor ≤ p_max), the
    optimum satisfies R(p*) ≥ d/G — the (35b)/(40) deadline constraint."""
    f_eff = (10.0 ** log_h2) / (10.0 ** log_s2)
    floor_p = float(_p_floor(d, g, f_eff, BW, P_MIN))
    p, _q, _ = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX)
    if floor_p <= P_MAX:
        rate = BW * jnp.log2(1.0 + p * f_eff)
        assert float(rate) >= (d / g) * (1.0 - 1e-5)


@given(st.floats(1e5, 2e6), st.floats(0.5, 9.0), st.floats(-4.0, -1.0),
       st.floats(-15.0, -13.0))
@settings(max_examples=20, deadline=None)
def test_dinkelbach_q_inner_invariant(d, g, log_h2, log_s2):
    """q* is a property of the PROBLEM, not the inner solver: projected
    closed form vs paper-faithful KKT subgradient agree ≤1e-4."""
    f_eff = (10.0 ** log_h2) / (10.0 ** log_s2)
    _p1, q1, _ = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX,
                                  inner="projected")
    _p2, q2, _ = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX,
                                  inner="kkt")
    assert abs(float(q1) - float(q2)) <= 1e-4 * max(abs(float(q1)), 1e-12)


# ---------------------------------------------------------------------------
# Fig. 4 trace path == jitted while_loop path (ISSUE 5 satellite)
# ---------------------------------------------------------------------------
@given(st.floats(1e5, 2e6), st.floats(0.5, 9.0), st.floats(9.0, 14.0))
@settings(max_examples=15, deadline=None)
def test_dinkelbach_trace_path_matches_while_loop(d, g, log_f):
    """``return_trace=True`` (the host loop Fig. 4 plots) and the jitted
    ``lax.while_loop`` path are the same algorithm — same (p*, q*), same
    iteration count, and the trace ends at q*."""
    f_eff = 10.0 ** log_f
    p_w, q_w, it_w = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX)
    p_t, q_t, it_t, trace = dinkelbach_power(d, g, f_eff, BW, P_MIN, P_MAX,
                                             return_trace=True)
    assert abs(float(p_w) - float(p_t)) <= 1e-6 * max(float(p_w), 1e-12)
    assert abs(float(q_w) - float(q_t)) <= 1e-6 * max(abs(float(q_w)), 1e-12)
    assert int(it_w) == int(it_t)
    assert trace[0] == 0.0 and len(trace) == it_t + 1
    assert abs(trace[-1] - float(q_t)) <= 1e-6 * max(abs(float(q_t)), 1e-12)


# ---------------------------------------------------------------------------
# padded (masked) tails — the ragged-N serving contract (ISSUE 6)
# ---------------------------------------------------------------------------
class TestPaddedTail:
    """The allocation service pads variable-N cells with ZERO channel gains
    at the SIC-order tail; both power engines and the suffix kernel must be
    invariant to such tails (see the contract in repro/core/sic.py)."""

    @pytest.mark.parametrize("pad", [1, 3, 11])
    def test_sequential_zero_tail_parity(self, pad):
        h2, g = _sic_inputs(5, seed=2)
        p, q = successive_power(h2, 200.0, g, BW, SIGMA2, P_MIN, P_MAX)
        h2p = jnp.concatenate([h2, jnp.zeros(pad)])
        gp = jnp.concatenate([g, jnp.zeros(pad)])
        pp, qp = successive_power(h2p, 200.0, gp, BW, SIGMA2, P_MIN, P_MAX)
        assert _rel(pp[:5], p) <= REL and _rel(qp[:5], q) <= REL
        # padded lanes themselves stay finite: F=0 -> rate-floor power hits
        # +inf and clips to the box top, q collapses to 0
        assert bool(jnp.all(pp[5:] == P_MAX)) and bool(jnp.all(qp[5:] == 0.0))

    @pytest.mark.parametrize("suffix_mode", ["ref", "interpret"])
    def test_blocked_zero_tail_parity(self, suffix_mode):
        h2, g = _sic_inputs(6, seed=4)
        p, q = successive_power_blocked(h2, 200.0, g, BW, SIGMA2, P_MIN,
                                        P_MAX, suffix_mode=suffix_mode)
        h2p = jnp.concatenate([h2, jnp.zeros(10)])
        gp = jnp.concatenate([g, jnp.zeros(10)])
        pp, qp = successive_power_blocked(h2p, 200.0, gp, BW, SIGMA2, P_MIN,
                                          P_MAX, suffix_mode=suffix_mode)
        assert _rel(pp[:6], p) <= REL and _rel(qp[:6], q) <= REL
        assert bool(jnp.all(jnp.isfinite(pp))) and \
            bool(jnp.all(jnp.isfinite(qp)))

    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_suffix_scan_zero_tail_parity(self, mode):
        """A zero tail must not perturb any real element's suffix sum.
        The Pallas kernel walks blocks sequentially with a scalar carry, so
        zero blocks add exactly 0.0 (bitwise); the jnp oracle's cumsum is
        an XLA associative tree whose shape changes with padding, so it
        gets the repo's 1e-5 relative budget instead."""
        w = jax.random.uniform(jax.random.PRNGKey(3), (4, 37))
        wp = jnp.pad(w, ((0, 0), (0, 91)))         # 37 -> 128 (block edge)
        s = sic_suffix_sum(w, mode=mode, block=32)
        sp = sic_suffix_sum(wp, mode=mode, block=32)
        if mode == "interpret":
            assert bool(jnp.all(sp[:, :37] == s))   # bitwise, not approx
        else:
            assert _rel(sp[:, :37], s) <= REL
        assert bool(jnp.all(sp[:, 37:] == 0.0))

    def test_n1_both_engines(self):
        """N=1 — the service's smallest-bucket edge: no later-decoded
        clients, interference 0, both engines finite and equal."""
        h2, g = _sic_inputs(1, seed=8)
        p_s, q_s = successive_power(h2, 200.0, g, BW, SIGMA2, P_MIN, P_MAX)
        p_b, q_b = successive_power_blocked(h2, 200.0, g, BW, SIGMA2,
                                            P_MIN, P_MAX)
        assert _rel(p_b, p_s) <= REL and _rel(q_b, q_s) <= REL
        assert bool(jnp.all(jnp.isfinite(p_s))) and \
            bool(jnp.all(jnp.isfinite(q_s)))

    def test_all_zero_gains_finite(self):
        """Degenerate all-masked lane set (a dummy batch-padding row):
        every power pins at the box top, q at 0, nothing NaN."""
        z = jnp.zeros(8)
        for fn in (successive_power,
                   lambda *a, **k: successive_power_blocked(*a, **k)):
            p, q = fn(z, 200.0, jnp.zeros(8), BW, SIGMA2, P_MIN, P_MAX)
            assert bool(jnp.all(p == P_MAX)) and bool(jnp.all(q == 0.0))
