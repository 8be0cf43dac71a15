"""The program's own instrumentation: the span vocabulary
(``repro.core.tracking``), the ``sic_power`` scope in the compiled
equilibrium engine, and the allocation service's request stages
(``AllocResult.stages``, ``health()["stages"]``)."""
import re
import time
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import stackelberg as st
from repro.core.tracking import SPANS, span
from repro.launch.alloc_serve import STAGES, AllocationService, AllocRequest

SRC = Path(__file__).resolve().parents[1] / "src"


def test_span_refuses_unknown_name():
    with pytest.raises(ValueError, match="unknown span"):
        span("serve.unpack")
    with span("serve.pack", batch=7):       # a declared name opens
        pass


def test_every_span_in_src_is_declared_and_used():
    used = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        used |= set(re.findall(r'\bspan\(\s*"([^"]+)"', text))
        if path.name != "tracking.py":
            assert "TraceAnnotation" not in text, path
    assert used == set(SPANS)


def test_sic_power_scope_in_compiled_engine():
    cfg = st.GameConfig()
    h2 = jnp.sort(jnp.linspace(0.1, 2.0, 20).reshape(4, 5), axis=1)[:, ::-1]
    phys, h2, d, vm, eps, tol, shards, _ = st._canon_batch(
        cfg, h2, 200.0, 0.5, 0.0, 1e-6)
    text = st._batched_equilibrium_jit.lower(
        phys, h2, d, vm, eps, tol, max_iter=20, inner=cfg.dinkelbach_inner,
        sic_mode=cfg.sic_mode, shards=shards).compile().as_text()
    scopes = re.findall(r'op_name="([^"]*)"', text)
    assert any("/sic_power/" in s for s in scopes)
    # the leader loop itself lies outside the scope
    assert any("/while" in s and "sic_power" not in s for s in scopes)


# ---------------------------------------------------------------------------
# request stages: one mixed stream, read by the tests below
# ---------------------------------------------------------------------------
SLOW_S = 0.1


@pytest.fixture(scope="module")
def stream():
    """ok, infeasible (after the retry ladder), late timeout, shed,
    rejected and expired-in-queue rows from one service whose dispatch
    call takes ``SLOW_S`` longer (the late row's, five times that)."""
    svc = AllocationService(buckets=(8, 16), max_batch=4, max_queue=3)
    real = svc._dispatch

    def slowed(secs):
        def slow(*a, **kw):
            out = real(*a, **kw)
            time.sleep(secs)
            return out
        return slow

    svc._dispatch = slowed(SLOW_S)
    rng = np.random.default_rng(5)
    h2 = lambda n: rng.uniform(0.2, 2.0, n)
    rid = {}
    rid["ok1"] = svc.submit(AllocRequest(h2=h2(4), priority=1))
    rid["ok2"] = svc.submit(AllocRequest(h2=h2(5), priority=1))
    rid["infeasible"] = svc.submit(AllocRequest(
        h2=h2(5), priority=1, cfg=st.GameConfig(t_max=1e-4)))
    rid["shed"] = svc.submit(AllocRequest(h2=h2(3), priority=0))
    rid["oversize"] = svc.submit(AllocRequest(h2=np.ones(40)))
    rid["nan"] = svc.submit(AllocRequest(h2=np.array([1.0, np.nan])))
    res = {r.rid: r for r in svc.drain()}
    # bucket 16 has no dispatch time on record, so admission passes both;
    # the late row is packed well inside its deadline and answered after
    svc._dispatch = slowed(5 * SLOW_S)
    rid["late"] = svc.submit(AllocRequest(h2=h2(12), deadline_s=0.25))
    rid["expired"] = svc.submit(AllocRequest(h2=h2(12), deadline_s=1e-4))
    time.sleep(0.01)
    res.update({r.rid: r for r in svc.drain()})
    return svc, {k: res[v] for k, v in rid.items()}


def test_stream_statuses(stream):
    _, r = stream
    assert {k: r[k].status for k in r} == {
        "ok1": "ok", "ok2": "ok", "infeasible": "infeasible",
        "shed": "shed", "oversize": "rejected", "nan": "rejected",
        "late": "timeout", "expired": "timeout"}
    assert r["late"].iterations > 0 and r["expired"].iterations == 0


def test_stages_sum_to_latency(stream):
    _, r = stream
    for k in ("ok1", "ok2", "infeasible", "late"):
        stages = r[k].stages
        assert set(stages) == set(STAGES) | {"batch"}, k
        assert all(stages[s] >= 0.0 for s in STAGES), (k, stages)
        assert sum(stages[s] for s in STAGES) == pytest.approx(
            r[k].latency_s, abs=1e-6), k
        # the slowed dispatch call is the launch stage of its batch
        assert stages["launch_s"] >= SLOW_S, (k, stages)
    for k in ("shed", "oversize", "nan", "expired"):
        assert r[k].stages is None, k


def test_stages_batch_ids(stream):
    _, r = stream
    assert r["ok1"].stages["batch"] == r["ok2"].stages["batch"]
    # the infeasible row rode the first batch too, and was answered by a
    # retry batch; its queue counts from its first submit, so it holds the
    # whole first batch, slowed dispatch included
    assert r["infeasible"].stages["batch"] > r["ok1"].stages["batch"]
    assert r["infeasible"].stages["queue_s"] >= SLOW_S
    assert r["infeasible"].degradation == ("relax_tmax:1.5", "fallback:oma")
    assert r["late"].stages["batch"] not in (r["ok1"].stages["batch"],
                                              r["infeasible"].stages["batch"])


def test_health_stages_percentiles(stream):
    svc, _ = stream
    stages = svc.health()["stages"]
    assert set(stages) == set(STAGES)
    for name, row in stages.items():
        assert row["n"] == 4, name          # ok1, ok2, infeasible, late
        assert 0.0 <= row["p50_ms"] <= row["p99_ms"], name
    assert stages["launch_s"]["p50_ms"] >= 1e3 * SLOW_S


def test_health_stages_empty_before_completions():
    svc = AllocationService(buckets=(8,), max_batch=4)
    assert svc.health()["stages"] == {}


def test_retried_dispatch_counts_backoff_in_pack():
    svc = AllocationService(buckets=(8,), max_batch=4, backoff_base_s=0.05)
    real, calls = svc._dispatch, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient")
        return real(*a, **kw)

    svc._dispatch = flaky
    rid = svc.submit(AllocRequest(h2=np.ones(4)))
    r = {r.rid: r for r in svc.drain()}[rid]
    assert r.status == "ok" and svc.stats["dispatch_retries"] == 1
    assert r.stages["pack_s"] >= 0.05
    assert sum(r.stages[s] for s in STAGES) == pytest.approx(r.latency_s,
                                                             abs=1e-6)
