"""Fault-injection scenario engine tests (ISSUE 7 tentpole).

Four contracts of ``repro.core.faults`` + its ``fl_round`` threading:

  * parity — the faulted scanned trajectory matches the faulted eager
    host loop, and the fault-free path is untouched by the new plumbing
    (``faults=None`` compiles the exact legacy round program — no extra
    metric keys, no PRNG stream change);
  * attack semantics — the adaptive reputation gate and the duty cycle
    behave exactly as specified (deterministic gate checks), sybil pools
    split one hoard across colluding IDs;
  * graceful mid-round degradation — a solve with dropped (h2=0, masked)
    lanes matches the exact n_eff-survivor solve ≤ 1e-5 on every surviving
    lane, for BOTH ``sic_mode`` families (the acceptance criterion);
  * compile behavior — a ≥3-attack × 2-defense × 2-seed grid runs as one
    sharded dispatch per (scheme, use_roni) with zero mid-grid retraces.

Plus seeded property tests (``tests/_prop`` fallback): reputation strictly
decreases for a detected poisoner and recovers boundedly after the attack
stops.

Shapes here are deliberately unusual (M=10 pool, hidden=22) so earlier
tests cannot have pre-warmed the jit cache and trace deltas are real.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _prop import given, settings, strategies as st

from repro.core import reputation as rep
from repro.core.channel import sample_positions
from repro.core.digital_twin import DTConfig, sample_v_max
from repro.core.faults import (ATTACK_PROFILES, FaultConfig, FaultOps,
                               adaptive_attacker, attack_active,
                               duty_cycle_attacker, fault_ops,
                               stack_fault_ops, straggler_storm)
from repro.core.fl_round import (FLConfig, FLState, run_round,
                                 run_training_eager, run_training_scan,
                                 stack_states, sweep_training)
from repro.core.reputation import (BENCHMARK_WEIGHTS, PROPOSED_WEIGHTS,
                                   ReputationState, init_reputation,
                                   update_interactions)
from repro.core.stackelberg import (TRACE_COUNTS, GameConfig,
                                    _physics_cached, _solve)
from repro.data.federated import make_federated_data, make_sybil_data
from repro.data.synthetic import SYNTHETIC_MNIST
from repro.models.classifier import make_classifier

M, CAP, HID, NSEL = 10, 40, 22, 3
REL = 1e-5
STORM = FaultConfig(p_outage=0.4, p_slow=0.4, compute_slowdown=3.0,
                    channel_fade=0.4)


def _setup(seed=0, poison=0.3, m=M, cap=CAP, hidden=HID):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    data = make_federated_data(ks[0], SYNTHETIC_MNIST, m=m, cap=cap,
                               poison_ratio=poison)
    params, logits_fn = make_classifier("mlp", ks[1], in_dim=784,
                                        hidden=hidden)
    state = FLState(params=params, rep=init_reputation(m),
                    v_max=sample_v_max(ks[2], m, DTConfig()),
                    distances=sample_positions(ks[3], m), key=ks[4])
    return state, data, logits_fn


def _fl(**kw):
    kw.setdefault("n_selected", NSEL)
    kw.setdefault("local_steps", 4)
    kw.setdefault("server_steps", 4)
    kw.setdefault("lr", 0.1)
    return FLConfig(**kw)


# ---------------------------------------------------------------------------
# parity: faulted scan == faulted eager; fault-free path untouched
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scheme,fc", [
    ("proposed", STORM),
    ("proposed", adaptive_attacker()),
    ("wo_dt", duty_cycle_attacker()),
])
def test_faulted_scan_matches_eager(scheme, fc):
    state, data, logits_fn = _setup(seed=1)
    fl = _fl(scheme=scheme)
    game = GameConfig()
    fs, stacked = run_training_scan(state, data, fl, game, logits_fn, 4,
                                    faults=fc)
    es, hist = run_training_eager(state, data, fl, game, logits_fn, 4,
                                  faults=fc)
    for k in ("val_acc", "latency", "energy", "n_dropped", "n_slowed",
              "n_attacking", "n_stragglers"):
        ref = jnp.asarray([h[k] for h in hist])
        rel = float(jnp.max(jnp.abs(stacked[k] - ref)
                            / jnp.maximum(jnp.abs(ref), 1e-12)))
        assert rel < REL, (k, rel)
    for new, old in zip(jax.tree_util.tree_leaves(fs.rep),
                        jax.tree_util.tree_leaves(es.rep)):
        assert bool(jnp.all(new == old))


def test_fault_free_path_has_no_fault_metrics():
    """``faults=None`` must compile the legacy round program: no fault
    metric keys, and identical results to the pre-fault engine (the
    figure-CSV byte-parity tests pin the numbers; here we pin the
    surface)."""
    state, data, logits_fn = _setup(seed=2)
    _, stacked = run_training_scan(state, data, _fl(), GameConfig(),
                                   logits_fn, 2)
    for k in ("n_dropped", "n_slowed", "n_attacking"):
        assert k not in stacked


def test_null_faultconfig_reproduces_static_attacker():
    """``FaultConfig()`` (gates wide open, no straggler process) is the
    legacy always-on label flipper: every selected poisoner attacks every
    round and nobody drops or slows."""
    state, data, logits_fn = _setup(seed=3)
    _, stacked = run_training_scan(state, data, _fl(), GameConfig(),
                                   logits_fn, 4, faults=FaultConfig())
    assert [int(x) for x in stacked["n_attacking"]] == \
           [int(x) for x in stacked["n_poisoned_selected"]]
    assert int(jnp.sum(stacked["n_dropped"])) == 0
    assert int(jnp.sum(stacked["n_slowed"])) == 0


# ---------------------------------------------------------------------------
# attack semantics
# ---------------------------------------------------------------------------
def test_adaptive_gate_blocks_low_reputation():
    """The reputation gate compares the attacker's own Eq.-16 score to the
    population median: a gate far above any plausible own/median ratio
    silences every attacker; a zero gate silences none (Z ≥ 0)."""
    state, data, logits_fn = _setup(seed=4)
    _, hi = run_training_scan(state, data, _fl(), GameConfig(), logits_fn,
                              3, faults=adaptive_attacker(rep_gate=50.0))
    assert int(jnp.sum(hi["n_attacking"])) == 0
    _, lo = run_training_scan(state, data, _fl(), GameConfig(), logits_fn,
                              3, faults=adaptive_attacker(rep_gate=0.0))
    assert [int(x) for x in lo["n_attacking"]] == \
           [int(x) for x in lo["n_poisoned_selected"]]


def test_duty_cycle_pattern():
    """period=2, on=1 ⇒ poison exactly on even rounds (round % 2 < 1)."""
    state, data, logits_fn = _setup(seed=5)
    _, m = run_training_scan(state, data, _fl(), GameConfig(), logits_fn,
                             6, faults=duty_cycle_attacker(period=2, on=1))
    att = [int(x) for x in m["n_attacking"]]
    pois = [int(x) for x in m["n_poisoned_selected"]]
    assert att[0::2] == pois[0::2]              # on-phase rounds
    assert att[1::2] == [0, 0, 0]               # off-phase rounds


def test_attack_active_gate_unit():
    """The gate function itself, off-trajectory: all three conjuncts."""
    fops = fault_ops(FaultConfig(rep_gate=0.5, duty_period=4, duty_on=2))
    poisoned = jnp.array([True, True, True, False])
    z = jnp.array([0.6, 0.4, 0.6, 0.9])
    z_ref = jnp.asarray(1.0)              # gate threshold = 0.5 · 1.0
    on = attack_active(fops, poisoned, z, z_ref,
                       jnp.asarray(1))                      # 1 % 4 < 2: on
    assert on.tolist() == [True, False, True, False]
    off = attack_active(fops, poisoned, z, z_ref,
                        jnp.asarray(3))                     # 3 % 4 ≥ 2: off
    assert off.tolist() == [False] * 4


def test_straggler_storm_metrics():
    """The storm scenario actually drops/slows clients, dropped clients
    count as stragglers (their update never arrives), and the trajectory
    stays finite through the masked re-solves."""
    state, data, logits_fn = _setup(seed=6, poison=0.0)
    _, m = run_training_scan(state, data, _fl(), GameConfig(), logits_fn,
                             8, faults=straggler_storm())
    assert int(jnp.sum(m["n_dropped"])) > 0
    assert int(jnp.sum(m["n_slowed"])) > 0
    assert bool(jnp.all(m["n_stragglers"] >= m["n_dropped"]))
    assert bool(jnp.all(jnp.isfinite(m["val_acc"])))
    assert bool(jnp.all(jnp.isfinite(m["latency"])))


def test_sybil_pool_split():
    """One hoard across P colluding IDs: equal small shares, flipped
    training labels, all flagged poisoned, clean slots untouched."""
    key = jax.random.PRNGKey(7)
    data = make_federated_data(key, SYNTHETIC_MNIST, m=M, cap=CAP,
                               poison_ratio=0.0)
    pool = 4
    syb = make_sybil_data(jax.random.PRNGKey(8), data, pool)
    share = CAP // pool
    assert syb.x.shape == data.x.shape
    assert bool(jnp.all(syb.poisoned[:pool]))
    assert bool(jnp.all(~syb.poisoned[pool:]))
    assert syb.sizes[:pool].tolist() == [float(share)] * pool
    assert int(jnp.sum(syb.mask[:pool])) == pool * share
    # flipped labels on the sybil slots, true labels preserved alongside
    assert bool(jnp.all(syb.y_train[:pool] == 9 - syb.y[:pool]))
    assert bool(jnp.all(syb.y_train[pool:] == data.y_train[pool:]))
    for f in ("x", "y", "mask", "sizes"):
        assert bool(jnp.all(getattr(syb, f)[pool:]
                            == getattr(data, f)[pool:])), f
    with pytest.raises(ValueError, match="pool size"):
        make_sybil_data(key, data, M + 1)


# ---------------------------------------------------------------------------
# graceful mid-round degradation: dropped lanes == exact-survivor solve
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sic_mode", ["sequential", "blocked"])
def test_dropped_lanes_match_survivor_solve(sic_mode):
    """The acceptance criterion: a solve where dropped clients ride as
    h2=0 masked tail lanes matches the exact n_eff-survivor solve ≤ 1e-5
    on every surviving lane, for both SIC engine families."""
    n, dropped = 8, (2, 5)
    rng = np.random.default_rng(17)
    h2 = np.sort(rng.uniform(0.2, 2.0, n).astype(np.float32))[::-1].copy()
    d = np.full(n, 200.0, np.float32)
    vm = np.full(n, 0.5, np.float32)
    phys = _physics_cached(GameConfig(), jnp.float32)
    tol = jnp.asarray(1e-6, jnp.float32)
    eps = jnp.asarray(0.05, jnp.float32)

    # dropped path: zero the outage lanes, re-sort (zeros sink to the
    # tail — exactly what the round body does), mask the tail
    alive = np.ones(n, bool)
    alive[list(dropped)] = False
    h2_f = np.where(alive, h2, 0.0)
    order = np.argsort(-h2_f, kind="stable")
    out_drop = _solve(phys, jnp.asarray(h2_f[order]), jnp.asarray(d[order]),
                      jnp.asarray(vm[order]), eps, 20, tol, "closed",
                      sic_mode, mask=jnp.asarray(alive[order]))

    # oracle: the survivors solved exactly at n_eff
    n_eff = int(alive.sum())
    out_ref = _solve(phys, jnp.asarray(h2[alive]), jnp.asarray(d[alive]),
                     jnp.asarray(vm[alive]), eps, 20, tol, "closed",
                     sic_mode, mask=None)

    for f in ("p", "q", "f", "alpha", "rates", "v"):
        got = np.asarray(getattr(out_drop, f))[:n_eff]
        ref = np.asarray(getattr(out_ref, f))
        rel = np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-12))
        assert rel <= REL, (f, rel)
    for f in ("t_total", "energy"):
        got, ref = float(getattr(out_drop, f)), float(getattr(out_ref, f))
        assert abs(got - ref) / max(abs(ref), 1e-12) <= REL, f
    assert bool(out_drop.feasible) == bool(out_ref.feasible)


def test_round_with_all_alive_matches_no_fault_solve():
    """p_outage=0 ⇒ the alive mask is all-True and the masked solve must
    equal the unmasked one (the mask plumbing itself is free)."""
    state, data, logits_fn = _setup(seed=9, poison=0.0)
    fl, game = _fl(), GameConfig()
    calm = FaultConfig()                         # no outage, no slowdown
    _, m_fault = run_round(state, data, fl, game, logits_fn, faults=calm)
    assert m_fault["n_dropped"] == 0
    # same state, no fault engine: latency/energy come from the same
    # equilibrium (the fault path only adds the extra PRNG split, which
    # feeds draws that gate NOTHING here)
    _, m_plain = run_round(state, data, fl, game, logits_fn)
    assert abs(m_fault["latency"] - m_plain["latency"]) <= REL
    assert abs(m_fault["energy"] - m_plain["energy"]) <= REL


# ---------------------------------------------------------------------------
# compile behavior: the attack-vs-defense grid
# ---------------------------------------------------------------------------
def test_attack_grid_zero_midgrid_retraces():
    """3 attacks × {reputation+RONI, reputation-only, no-defense} × 2
    seeds: ONE sweep dispatch per use_roni value (weights are traced, so
    rep-only and no-defense share the RONI-off executable) — the round
    body traces exactly twice for the whole grid."""
    per_seed = [_setup(seed=s) for s in range(2)]
    states = stack_states([s for s, _, _ in per_seed])
    data, logits_fn = per_seed[0][1], per_seed[0][2]
    attacks = [ATTACK_PROFILES["static"], ATTACK_PROFILES["adaptive"],
               ATTACK_PROFILES["duty"]]
    game = GameConfig()
    before = TRACE_COUNTS["run_round"]

    # defended: reputation + RONI (use_roni=True executable)
    fls_def = [_fl(weights=PROPOSED_WEIGHTS, use_roni=True)] * 3
    _, m_def = sweep_training(states, data, fls_def, game, logits_fn, 2,
                              faults=attacks)
    # rep-only and no-defense ride ONE RONI-off sweep: C = 3 attacks × 2
    # weight settings, weights traced along the config axis
    fls_off = ([_fl(weights=PROPOSED_WEIGHTS, use_roni=False)] * 3
               + [_fl(weights=BENCHMARK_WEIGHTS, use_roni=False)] * 3)
    _, m_off = sweep_training(states, data, fls_off, game, logits_fn, 2,
                              faults=attacks + attacks)
    assert TRACE_COUNTS["run_round"] - before == 2
    assert m_def["val_acc"].shape == (3, 2, 2)
    assert m_off["val_acc"].shape == (6, 2, 2)
    assert bool(jnp.all(jnp.isfinite(m_def["val_acc"])))
    assert bool(jnp.all(jnp.isfinite(m_off["val_acc"])))


def test_sweep_fault_validation():
    states = stack_states([_setup(seed=0)[0]])
    data, logits_fn = _setup(seed=0)[1], _setup(seed=0)[2]
    fls = [_fl()] * 2
    with pytest.raises(ValueError, match="fault axis mismatch"):
        sweep_training(states, data, fls, GameConfig(), logits_fn, 1,
                       faults=[FaultConfig()] * 3)
    with pytest.raises(ValueError, match=r"must be \[2\]-shaped"):
        sweep_training(states, data, fls, GameConfig(), logits_fn, 1,
                       faults=stack_fault_ops([FaultConfig()] * 3))
    with pytest.raises(ValueError, match="data_axis"):
        sweep_training(states, data, fls, GameConfig(), logits_fn, 1,
                       data_axis="nope")


# ---------------------------------------------------------------------------
# property tests: reputation under detection (tests/_prop fallback)
# ---------------------------------------------------------------------------
def _rep_state(pi: float, ni: float, m: int = 4) -> ReputationState:
    return ReputationState(ms=jnp.ones((m,)),
                           pi_count=jnp.full((m,), pi),
                           ni_count=jnp.full((m,), ni))


_D = jnp.full((4,), 100.0)
_IDX0 = jnp.asarray([0])
_POS = jnp.asarray([True])
_NEG = jnp.asarray([False])


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=40),
       st.integers(min_value=0, max_value=40),
       st.integers(min_value=1, max_value=6))
def test_reputation_strictly_decreases_on_detection(pi0, ni0, k):
    """Every recorded NI strictly sinks the detected poisoner's Eq.-16
    score (ξ3 > 0 and PI = pi/(pi+ni) is strictly decreasing in ni),
    while the untouched clients' scores never move."""
    state = _rep_state(float(pi0), float(ni0))
    z = rep.reputation(state, _D)
    for _ in range(k):
        state = update_interactions(state, _IDX0, _NEG)
        z_new = rep.reputation(state, _D)
        assert float(z_new[0]) < float(z[0])
        assert bool(jnp.all(z_new[1:] == z[1:]))
        z = z_new


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=10),
       st.integers(min_value=1, max_value=25))
def test_reputation_recovers_boundedly_after_attack_stops(n_attack, n_rec):
    """After the attack stops, PI recordings raise the score monotonically
    — but it stays STRICTLY below the counterfactual score of a client
    that was never detected (same positive history, no NIs): detections
    leave a permanent dent, recovery is bounded."""
    attacked = _rep_state(1.0, 0.0)
    clean = _rep_state(1.0, 0.0)
    for _ in range(n_attack):
        attacked = update_interactions(attacked, _IDX0, _NEG)
    z_prev = rep.reputation(attacked, _D)
    for _ in range(n_rec):
        attacked = update_interactions(attacked, _IDX0, _POS)
        clean = update_interactions(clean, _IDX0, _POS)
        z_att = rep.reputation(attacked, _D)
        assert float(z_att[0]) > float(z_prev[0])          # monotone up
        assert float(z_att[0]) < float(
            rep.reputation(clean, _D)[0])                  # bounded
        z_prev = z_att


def test_count_mask_skips_dropped_verdicts():
    """A dropped client's verdict is not recorded: count_mask=False rows
    leave both counters untouched (the server never saw an update)."""
    state = _rep_state(3.0, 2.0)
    idx = jnp.asarray([0, 1])
    verdicts = jnp.asarray([True, False])
    alive = jnp.asarray([False, True])
    out = update_interactions(state, idx, verdicts, count_mask=alive)
    assert float(out.pi_count[0]) == 3.0 and float(out.ni_count[0]) == 2.0
    assert float(out.ni_count[1]) == 3.0                   # recorded NI
    full = update_interactions(state, idx, verdicts)
    assert float(full.pi_count[0]) == 4.0                  # contrast
