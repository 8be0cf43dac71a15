"""Streaming allocation example: a mixed-N request stream through the
ragged-N bucket scheduler (``repro.launch.alloc_serve``), then the same
service under pressure with the ISSUE-9 SLA/resilience contract.

Part 1 — the baseline stream: ten cells with 2–30 clients each, their
own channel draws and deadlines, padded into warm 8/16/32-wide bucket
executables (zero retraces), same-bucket requests batched per dispatch,
each cell's Stackelberg allocation returned in its own client order.

Part 2 — the SLA contract.  Every submitted rid yields EXACTLY ONE
result whose ``status`` comes from the five-word vocabulary:

  ok          solved, feasible, inside any deadline
  infeasible  solved, but the equilibrium violates the deadline/resource
              box even after the degraded-retry ladder (the ladder first
              re-solves with t_max x relax_factor — same executable,
              zero retrace — then falls back to the cheaper oma scheme;
              the trail is recorded in ``result.degradation``)
  rejected    no valid allocation: oversized N, non-finite channel
              gains, admission control (the EWMA queue-wait prediction
              already busts ``deadline_s``), an OPEN circuit breaker, or
              a dispatch that failed after backoff retries
  shed        dropped by priority-ordered load shedding when the bounded
              queue (``max_queue``) overflowed — lowest priority sheds
              first, high priority keeps completing
  timeout     solved (or expired in queue) after ``deadline_s``

Per-(bucket, scheme) circuit breakers contain a sick executable:
``breaker_threshold`` consecutive bad batches (non-finite outputs,
watchdog trips, dispatch failures) trip it OPEN → submissions fast-fail
→ after ``breaker_cooldown_s`` a HALF_OPEN probe either closes it or
re-opens.  ``service.health()`` snapshots queues, breakers, counters,
per-priority latency percentiles and those of each request stage (queued,
packing, dispatch call, in flight, wait for the device, readback).

    PYTHONPATH=src python examples/serve_allocation.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.runtime import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np

from repro.core.stackelberg import GameConfig
from repro.core.tracking import TRACE_COUNTS
from repro.launch.alloc_serve import AllocationService, AllocRequest

rng = np.random.default_rng(0)
svc = AllocationService(buckets=(8, 16, 32), max_batch=4)

print("warming bucket executables (one-time compile)...")
# warm the oma fallback too: the degraded-retry ladder may land on it,
# and a warmed pair keeps even degraded streams retrace-free
print(f"  warmup: {svc.warmup(schemes=('proposed', 'oma')):.1f}s")
warm = TRACE_COUNTS["serve_allocation"]

cells = [int(n) for n in rng.integers(2, 31, size=10)]
t0 = time.time()
for i, n in enumerate(cells):
    svc.submit(AllocRequest(
        h2=rng.uniform(0.2, 2.0, n).astype(np.float32),
        d=200.0, v_max=0.5, epsilon=0.05,
        cfg=GameConfig(t_max=float(rng.uniform(0.9, 1.4)))))
results = svc.drain()                      # rid-sorted by contract
dt = time.time() - t0

print(f"\n{len(results)} cells allocated in {dt*1e3:.0f} ms "
      f"({svc.stats['dispatches']} dispatches, "
      f"{TRACE_COUNTS['serve_allocation'] - warm} retraces)")
print(f"{'cell':>4} {'N':>3} {'bucket':>6} {'status':>10} {'energy(J)':>10} "
      f"{'t_tot(s)':>9} {'degradation':>22}")
for r in results:
    print(f"{r.rid:>4} {r.n:>3} {r.bucket:>6} {r.status:>10} "
          f"{r.energy:>10.4f} {r.t_total:>9.4f} "
          f"{','.join(r.degradation) or '-':>22}")

# --- part 2: the same service under pressure -------------------------------
print("\nSLA mode: bounded queue, priorities, deadlines --")
sla = AllocationService(buckets=(8,), max_batch=4, max_queue=6)
sla.warmup(schemes=("proposed",))
for i in range(12):                        # a burst over the queue bound:
    hi = i % 3 == 0                        # every 3rd request is priority 2
    sla.submit(AllocRequest(
        h2=rng.uniform(0.2, 2.0, int(rng.integers(2, 9))),
        priority=2 if hi else 0,
        deadline_s=2.0 if hi else None))
sla.submit(AllocRequest(h2=np.ones(99)))             # oversized  → rejected
sla.submit(AllocRequest(h2=np.array([1.0, np.nan])))  # poisoned  → rejected
burst = sla.drain()

by_status = {}
for r in burst:
    by_status.setdefault(r.status, []).append(r.rid)
print(f"  {len(burst)} results for {len(burst)} submits (exactly once):")
for status, rids in sorted(by_status.items()):
    print(f"    {status:>10}: rids {rids}")
health = sla.health()
print(f"  health: counters={health['counters']}")
print(f"          breakers={health['breakers']}")
print(f"          latency by priority (ms) = "
      f"{health['latency_by_priority_ms']}")
print("          stages of a request, p50 / p99 (ms):")
for name, row in health["stages"].items():
    print(f"            {name:>12}: {row['p50_ms']:8.2f} / "
          f"{row['p99_ms']:8.2f}")
