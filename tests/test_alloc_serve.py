"""Ragged-N streaming allocation service: padded-bucket parity + scheduler.

The serving contract (ISSUE 6 tentpole): a request solved inside a padded
bucket must MATCH the exact-N solve — same p/q/f/latency/energy within the
repo's 1e-5 relative budget (empirically the masked path is bitwise equal:
zero-gain tails are invisible to every suffix sum and the mask erases the
padded lanes from every reduction) — and a mixed-N stream over warm buckets
must trigger ZERO retraces (TRACE_COUNTS["serve_allocation"]).
"""
import dataclasses
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fl_round import allocate_batched
from repro.core.stackelberg import GameConfig, stack_physics
from repro.core.tracking import TRACE_COUNTS
from repro.launch.alloc_serve import (DEFAULT_BUCKETS, READ_FIELDS,
                                      SERVE_SCHEMES, AllocationService,
                                      AllocRequest, solve_row, unpack_rows)

REL = 1e-5
D_BITS, V_MAX, EPS = 200.0, 0.5, 0.05


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def _draw(n, seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.2, 2.0, n).astype(np.float32)


def _exact(cfg, h2, scheme="proposed"):
    """Exact-N oracle via the batched engine (already parity-locked to the
    scalar solver in tests/test_equilibrium_batched.py)."""
    order = np.argsort(-h2, kind="stable")
    n = h2.shape[0]
    out = allocate_batched(
        scheme, cfg, jnp.asarray(h2[order])[None, :],
        jnp.full((1, n), D_BITS, jnp.float32),
        jnp.full((1, n), V_MAX, jnp.float32), epsilon=EPS)
    inv = np.empty_like(order)
    inv[order] = np.arange(n)
    per = {f: np.asarray(getattr(out, f))[0][inv]
           for f in ("p", "q", "f", "alpha", "rates")}
    return per, out


def _serve_one(h2, scheme, cfg, buckets=(8, 16), max_batch=2):
    svc = AllocationService(buckets=buckets, max_batch=max_batch)
    svc.submit(AllocRequest(h2=h2, d=D_BITS, v_max=V_MAX, cfg=cfg,
                            scheme=scheme, epsilon=EPS))
    (res,) = svc.drain()
    return res


class TestPaddedParity:
    """Padded-bucket solve == exact-N solve, across schemes and sic modes."""

    @pytest.mark.parametrize("scheme", ["proposed", "ideal", "wo_dt",
                                        "oma", "oma_tdma"])
    def test_scheme_parity(self, scheme):
        h2 = _draw(5, seed=3)                      # n=5 inside bucket 8
        cfg = GameConfig()
        res = _serve_one(h2, scheme, cfg)
        per, out = _exact(cfg, h2, scheme=scheme)
        for f in ("p", "q", "f", "alpha", "rates"):
            assert _rel(getattr(res, f), per[f]) <= REL, f
        assert _rel(res.t_total, out.t_total[0]) <= REL
        assert _rel(res.energy, out.energy[0]) <= REL
        assert res.feasible == bool(out.feasible[0])

    @pytest.mark.parametrize("sic_mode", ["sequential", "blocked",
                                          "blocked_interpret"])
    def test_sic_mode_parity(self, sic_mode):
        h2 = _draw(11, seed=7)                     # n=11 inside bucket 16
        cfg = GameConfig(sic_mode=sic_mode)
        res = _serve_one(h2, "proposed", cfg)
        per, out = _exact(cfg, h2)
        for f in ("p", "q", "f"):
            assert _rel(getattr(res, f), per[f]) <= REL, f
        assert _rel(res.t_total, out.t_total[0]) <= REL
        assert _rel(res.energy, out.energy[0]) <= REL

    def test_n1_smallest_bucket(self):
        """N=1 rides the smallest bucket with 7 padded lanes — the edge the
        service's smallest bucket surfaces (ISSUE satellite 3)."""
        h2 = _draw(1, seed=11)
        cfg = GameConfig()
        res = _serve_one(h2, "proposed", cfg)
        per, out = _exact(cfg, h2)
        assert res.bucket == 8 and res.n == 1
        assert _rel(res.p, per["p"]) <= REL
        assert _rel(res.energy, out.energy[0]) <= REL
        assert np.isfinite(res.t_total) and np.isfinite(res.energy)

    def test_original_order_restored(self):
        """h2 submitted in ascending (anti-SIC) order comes back aligned
        with the request's own client indexing."""
        h2 = np.sort(_draw(6, seed=5))             # ascending on purpose
        cfg = GameConfig()
        res = _serve_one(h2, "proposed", cfg)
        per, _ = _exact(cfg, h2)
        # per-client parity in the REQUEST's order is the proof: rates are
        # channel-dependent, so a wrong unsort permutation cannot match
        assert _rel(res.p, per["p"]) <= REL
        assert _rel(res.rates, per["rates"]) <= REL
        assert _rel(res.alpha, per["alpha"]) <= REL

    def test_heterogeneous_physics_one_batch(self):
        """Two requests with different t_max/bandwidth share one dispatch
        and each matches its own exact solve."""
        cfg_a = GameConfig(t_max=1.0)
        cfg_b = GameConfig(t_max=2.5, bandwidth=2e6)
        h2a, h2b = _draw(4, seed=21), _draw(6, seed=22)
        svc = AllocationService(buckets=(8,), max_batch=2)
        ra = svc.submit(AllocRequest(h2=h2a, cfg=cfg_a, epsilon=EPS))
        rb = svc.submit(AllocRequest(h2=h2b, cfg=cfg_b, epsilon=EPS))
        res = {r.rid: r for r in svc.drain()}
        assert svc.stats["dispatches"] == 1        # one shared batch
        for rid, cfg, h2 in ((ra, cfg_a, h2a), (rb, cfg_b, h2b)):
            per, out = _exact(cfg, h2)
            assert _rel(res[rid].p, per["p"]) <= REL
            assert _rel(res[rid].energy, out.energy[0]) <= REL

    def test_random_scheme_in_box(self):
        """The random baseline's draws stay inside the physics box even
        through the padded path (distributional scheme — no bitwise
        oracle, bucket-shaped draws differ from exact-N draws)."""
        h2 = _draw(5, seed=9)
        cfg = GameConfig()
        res = _serve_one(h2, "random", cfg)
        assert np.all(res.p >= cfg.p_min - 1e-9)
        assert np.all(res.p <= cfg.p_max + 1e-9)
        assert np.all(res.f <= cfg.f_max + 1e-6)
        assert np.isfinite(res.energy) and np.isfinite(res.t_total)


class TestScheduler:
    def test_zero_retrace_mixed_stream(self):
        """50-request mixed-N stream over warm buckets: ZERO retraces
        (the ISSUE acceptance criterion)."""
        svc = AllocationService(buckets=(8, 16), max_batch=4)
        svc.warmup(schemes=("proposed",))
        before = TRACE_COUNTS["serve_allocation"]
        rng = np.random.default_rng(0)
        for i in range(50):
            n = int(rng.integers(1, 17))
            svc.submit(AllocRequest(h2=_draw(n, seed=100 + i), epsilon=EPS))
        res = svc.drain()
        assert len(res) == 50
        assert TRACE_COUNTS["serve_allocation"] == before  # zero retraces
        assert all(np.isfinite(r.energy) and np.isfinite(r.t_total)
                   for r in res)

    def test_partial_batch_dummy_rows_finite(self):
        """A lone request padded with all-masked dummy rows must not be
        poisoned by them (the follower_alpha 0/0 guard regression)."""
        svc = AllocationService(buckets=(8,), max_batch=4)
        svc.submit(AllocRequest(h2=_draw(3, seed=1), epsilon=EPS))
        (res,) = svc.drain()
        assert svc.stats["padded_slots"] == 3
        assert np.all(np.isfinite(res.p)) and np.isfinite(res.energy)

    def test_bucket_routing_and_overflow(self):
        svc = AllocationService(buckets=DEFAULT_BUCKETS)
        assert svc.bucket_for(1) == 8
        assert svc.bucket_for(8) == 8
        assert svc.bucket_for(9) == 16
        assert svc.bucket_for(128) == 128
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            svc.bucket_for(129)
        with pytest.raises(ValueError, match="unknown scheme"):
            svc.submit(AllocRequest(h2=np.ones(3), scheme="nope"))
        with pytest.raises(ValueError, match="0 clients"):
            svc.submit(AllocRequest(h2=np.ones(0)))

    def test_full_batch_autoflush(self):
        svc = AllocationService(buckets=(8,), max_batch=2)
        svc.submit(AllocRequest(h2=_draw(3, seed=1)))
        assert svc.stats["dispatches"] == 0
        svc.submit(AllocRequest(h2=_draw(4, seed=2)))
        assert svc.stats["dispatches"] == 1        # auto-flushed when full
        assert len(svc.drain()) == 2

    def test_latency_recorded(self):
        svc = AllocationService(buckets=(8,))
        svc.submit(AllocRequest(h2=_draw(4, seed=2)))
        (res,) = svc.drain()
        assert res.latency_s > 0.0


class TestGracefulDegradation:
    """ISSUE-7 satellite: undispatchable or infeasible requests come back
    as structured per-request rows instead of exceptions that kill the
    in-flight stream."""

    def test_overflow_rejected_not_fatal(self):
        """An N > largest-bucket request mid-stream yields a
        status='rejected' NaN row; the surrounding requests still solve."""
        svc = AllocationService(buckets=(8,), max_batch=2)
        ra = svc.submit(AllocRequest(h2=_draw(4, seed=31), epsilon=EPS))
        rbad = svc.submit(AllocRequest(h2=_draw(9, seed=32), epsilon=EPS))
        rb = svc.submit(AllocRequest(h2=_draw(5, seed=33), epsilon=EPS))
        res = {r.rid: r for r in svc.drain()}
        assert len(res) == 3
        bad = res[rbad]
        assert bad.status == "rejected"
        assert "exceeds the largest bucket" in bad.error
        assert bad.n == 9 and not bad.feasible
        assert np.all(np.isnan(bad.p)) and np.isnan(bad.energy)
        assert svc.stats["rejected"] == 1
        for rid in (ra, rb):
            assert res[rid].status == "ok"
            assert np.all(np.isfinite(res[rid].p))

    def test_ok_status_on_normal_request(self):
        svc = AllocationService(buckets=(8,))
        svc.submit(AllocRequest(h2=_draw(4, seed=2), epsilon=EPS))
        (res,) = svc.drain()
        assert res.status == "ok" and res.error == "" and res.feasible

    def test_infeasible_tagged_not_fatal(self):
        """A cell whose deadline cannot be met solves to feasible=False and
        is tagged status='infeasible' — the allocation is still returned
        (the solver's best answer) and the stream keeps running."""
        svc = AllocationService(buckets=(8,), max_batch=2)
        tight = GameConfig(t_max=1e-4)             # unmeetable deadline
        r_bad = svc.submit(AllocRequest(h2=_draw(4, seed=41), cfg=tight,
                                        epsilon=EPS))
        r_ok = svc.submit(AllocRequest(h2=_draw(4, seed=42), epsilon=EPS))
        res = {r.rid: r for r in svc.drain()}
        assert res[r_bad].status == "infeasible"
        assert not res[r_bad].feasible
        assert "deadline" in res[r_bad].error
        assert svc.stats["infeasible"] == 1
        assert res[r_ok].status == "ok" and res[r_ok].feasible


# ---------------------------------------------------------------------------
# packed operands: two host buffers per dispatch, readback started at dispatch
# ---------------------------------------------------------------------------
def _mixed_batch(nb):
    """Three requests in one bucket (a partial batch of four: one dummy
    row), each with its own client count, t_max, epsilon and seed; one
    also with its own bandwidth."""
    ns = (3, 5, nb) if nb == 8 else (70, nb, 100)
    cfgs = (GameConfig(t_max=0.8), GameConfig(t_max=10.0, bandwidth=2e6),
            GameConfig(t_max=35.0))
    seeds = (7, 2**31 + 3, 0)
    rng = np.random.default_rng(nb)
    return [AllocRequest(h2=rng.uniform(0.2, 2.0, n).astype(np.float32),
                         d=rng.uniform(100.0, 300.0, n).astype(np.float32),
                         v_max=0.5, cfg=cfg, epsilon=0.01 * (i + 1),
                         seed=seed)
            for i, (n, cfg, seed) in enumerate(zip(ns, cfgs, seeds))]


def _parent_operands(reqs, nb, b):
    """The operand layout each dispatch sent before packing: per-field
    [b, nb] arrays in SIC order, ``stack_physics`` of the configs (dummy
    rows take the first's), an eager mask and eager ``vmap(PRNGKey)``
    keys."""
    h2, D, vm = (np.zeros((b, nb), np.float32) for _ in range(3))
    mask = np.zeros((b, nb), bool)
    eps = np.zeros((b,), np.float32)
    for i, q in enumerate(reqs):
        x = np.asarray(q.h2, np.float32)
        n = x.size
        order = np.argsort(-x, kind="stable")
        h2[i, :n] = x[order]
        D[i, :n] = np.broadcast_to(np.asarray(q.d, np.float32), (n,))[order]
        vm[i, :n] = np.broadcast_to(np.asarray(q.v_max, np.float32),
                                    (n,))[order]
        mask[i, :n] = True
        eps[i] = q.epsilon
    cfgs = [q.cfg for q in reqs] + [reqs[0].cfg] * (b - len(reqs))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(
        [q.seed for q in reqs] + [0] * (b - len(reqs)), jnp.uint32))
    return (stack_physics(cfgs), keys, jnp.asarray(h2), jnp.asarray(D),
            jnp.asarray(vm), jnp.asarray(eps), jnp.asarray(mask))


def _recorded_dispatch(reqs, nb, scheme):
    """Run ``reqs`` through one partial-batch dispatch of a bucket-``nb``
    service (no retry ladder, so it is the only one); returns (service,
    the seam's positional operands, its output)."""
    svc = AllocationService(buckets=(nb,), max_batch=4, degraded_retry=False)
    real, seen = svc._dispatch, {}

    def record(*a, **kw):
        seen["args"], seen["out"] = a, real(*a, **kw)
        return seen["out"]

    svc._dispatch = record
    for q in reqs:
        svc.submit(dataclasses.replace(q, scheme=scheme))
    svc.drain()
    assert svc.stats["dispatches"] == 1
    return svc, seen["args"], seen["out"]


class TestPackedDispatch:
    """The two packed buffers carry exactly the operands the per-field
    layout sent, the answers are the same bits, and the readback starts
    on whatever the dispatch seam returned."""

    @pytest.mark.parametrize("nb", [8, 128])
    def test_unpacked_operands_equal_parent_layout(self, nb):
        """Every operand the executable unpacks — the physics leaves, the
        PRNG keys made in the program, the mask from the client counts —
        equals the per-field layout's, bit for bit."""
        reqs = _mixed_batch(nb)
        svc, args, _ = _recorded_dispatch(reqs, nb, "random")
        got = jax.jit(unpack_rows)(*args[:2])
        want = _parent_operands(reqs, nb, svc.batch_width)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("nb", [8, 128])
    @pytest.mark.parametrize("scheme", SERVE_SCHEMES)
    def test_dispatch_equals_parent_layout(self, scheme, nb):
        """All nine fields a result reads, on every row (the dummy too),
        equal a vmapped call of the same scheme body on the per-field
        layout, bit for bit on the CPU."""
        reqs = _mixed_batch(nb)
        svc, _, out = _recorded_dispatch(reqs, nb, scheme)
        cfg = reqs[0].cfg
        body = partial(solve_row, scheme, svc.max_iter, cfg.dinkelbach_inner,
                       cfg.sic_mode)
        ref = jax.jit(jax.vmap(body, in_axes=(0,) * 7 + (None,)))(
            *_parent_operands(reqs, nb, svc.batch_width),
            jnp.asarray(svc.tol, jnp.float32))
        for f in READ_FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(out, f)),
                                          np.asarray(getattr(ref, f)),
                                          err_msg=f)

    def test_warm_dispatch_sends_two_buffers_explicitly(self, monkeypatch):
        """A warm service packs, dispatches and reaps a full batch with no
        implicit host→device transfer, in one ``device_put`` of two
        buffers, and starts every readback at dispatch."""
        svc = AllocationService(buckets=(8,), max_batch=4)
        svc.warmup()
        reqs = [AllocRequest(h2=_draw(n, seed=50 + n), epsilon=EPS)
                for n in (2, 3, 5, 8)]
        puts = []
        real_put = jax.device_put

        def counting_put(x, *a, **kw):
            puts.append(len(jax.tree_util.tree_leaves(x)))
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", counting_put)
        with jax.transfer_guard("disallow"):
            for q in reqs:
                svc.submit(q)
            res = svc.drain()
        assert [r.status for r in res] == ["ok"] * 4
        d = svc.stats["dispatches"]
        assert d >= 2                              # warmup's and this one
        assert puts == [2]
        assert svc.stats["operand_buffers"] == 2 * d
        assert svc.stats["readback_prefetched"] == d
        assert svc.health()["per_dispatch"] == {"operand_buffers": 2.0,
                                                "readback_prefetched": 1.0}

    def test_readback_reads_what_the_seam_returned(self):
        """A wrapper at the dispatch seam that alters ``p`` after the call
        changes the result's ``p``: the readback started at dispatch is of
        the wrapper's output.  A NaN-poisoned output is still rejected."""
        h2 = _draw(5, seed=3)
        base = _serve_one(h2, "proposed", GameConfig())
        svc = AllocationService(buckets=(8, 16), max_batch=2)
        real = svc._dispatch

        def altered(*a, **kw):
            out = real(*a, **kw)
            return out.__class__(**{**vars(out), "p": out.p * 2.0})

        svc._dispatch = altered
        svc.submit(AllocRequest(h2=h2, d=D_BITS, v_max=V_MAX, epsilon=EPS))
        (res,) = svc.drain()
        np.testing.assert_array_equal(res.p, base.p * np.float32(2.0))
        assert svc.stats["readback_prefetched"] == 1

        def poisoned(*a, **kw):
            return jax.tree_util.tree_map(
                lambda x: jnp.full_like(x, jnp.nan)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                real(*a, **kw))

        svc._dispatch = poisoned
        svc.submit(AllocRequest(h2=h2, d=D_BITS, v_max=V_MAX, epsilon=EPS))
        (res,) = svc.drain()
        assert res.status == "rejected"
        assert "non-finite allocation" in res.error
