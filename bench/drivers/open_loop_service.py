"""Open-loop Poisson load on the allocation service, as an operator's base
stations send it: each request is one cell's round (N clients in no
particular order, their own deadline t_max), due at a time fixed in
advance whether or not the service keeps up.

Every request is timed from when it was due: the generator's lateness plus
the service's own ``latency_s``.  A request that is refused, shed, timed
out or never answered counts as missing every limit.

The service (``AllocationService`` with its defaults, ``buckets`` from the
traffic file) dispatches a full batch as soon as it holds one.  It has no
timed flush and no public poll that leaves partial batches queued, so the
generator calls ``flush`` and then ``drain`` every ``tick_ms``: partial batches
go out and every finished batch is read back.

Traffic parameters: ``rate_per_s``, ``buckets``, ``t_max_range`` (s),
``epsilon``, ``deadline_s``, ``tick_ms``, ``allow_degraded``.  The gaps
between arrivals are one fixed set of exponential draws, scaled to fill the
window, which the seed puts in its own order: every seed offers the same
number of requests in the same time.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax
from jax.profiler import TraceAnnotation

from bench import compare, inputs, reference

WARM_REQUESTS = 32
OK = ("ok", "infeasible")


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float):
        from repro.core.stackelberg import GameConfig
        from repro.launch.alloc_serve import AllocationService, AllocRequest
        self.config, self.traffic, self.seed = config, traffic, seed
        self.phys = inputs.physics(config)
        self.solver = config["solver"]
        self._request = AllocRequest
        self._game = GameConfig(
            **self.phys, dinkelbach_inner=self.solver["dinkelbach_inner"],
            sic_mode=self.solver["sic_mode"])
        self.svc = AllocationService(buckets=tuple(traffic["buckets"]),
                                     max_iter=int(self.solver["max_iter"]),
                                     tol=float(self.solver["tol"]))
        self.svc.warmup(cfg=self._game)
        warm = self._requests(WARM_REQUESTS, stream=2)
        for r in warm:
            self.svc.submit(r)
        self.svc.drain()
        self.svc.stats.clear()
        self.due = self._schedule(seconds)
        self.reqs = self._requests(len(self.due), stream=1)

    def _requests(self, count: int, stream: int):
        """``count`` requests: channel draws on the device, the rest on the
        host, all from the seed."""
        n = int(self.config["clients_per_round"])
        with TraceAnnotation("generate"):
            h2, d, v = jax.device_get(inputs.client_draws(
                inputs.prng_key(self.seed, stream), (count, n), self.config))
        rng = np.random.default_rng([self.seed, stream])
        lo, hi = self.traffic["t_max_range"]
        t_max = rng.uniform(lo, hi, count)
        reqs = []
        for i in range(count):
            perm = rng.permutation(n)           # arrives in no SIC order
            reqs.append(self._request(
                h2=h2[i][perm], d=d[i][perm], v_max=v[i][perm],
                cfg=dataclasses.replace(self._game, t_max=float(t_max[i])),
                epsilon=float(self.traffic["epsilon"]),
                deadline_s=float(self.traffic["deadline_s"]),
                allow_degraded=bool(self.traffic["allow_degraded"])))
        return reqs

    def _schedule(self, seconds: float) -> np.ndarray:
        count = max(1, int(round(float(self.traffic["rate_per_s"]) * seconds)))
        gaps = np.random.default_rng(0).exponential(1.0, count)
        gaps *= seconds / gaps.sum()
        return np.cumsum(np.random.default_rng([self.seed, 3]).permutation(gaps))

    def run(self, seconds: float) -> dict:
        due, reqs = self.due, self.reqs
        tick = float(self.traffic["tick_ms"]) / 1e3
        rids, late, results = [], [], []
        t0 = time.perf_counter()
        next_tick = t0 + tick
        i = 0
        while i < len(due):
            now = time.perf_counter()
            if t0 + due[i] <= now:
                with TraceAnnotation("enqueue"):
                    rids.append(self.svc.submit(reqs[i]))
                late.append(now - (t0 + due[i]))
                i += 1
            elif next_tick <= now:
                self._tick(results)
                next_tick = max(next_tick + tick, now)
            else:
                time.sleep(min(t0 + due[i], next_tick) - now)
        self._tick(results)
        window = time.perf_counter() - t0
        by_rid = {r.rid: r for r in results}
        self.answered = [(reqs[j], by_rid.get(rid)) for j, rid in enumerate(rids)]
        deadline = float(self.traffic["deadline_s"])
        lat = np.full(len(due), np.inf)
        served = 0
        for j, (_, res) in enumerate(self.answered):
            if res is not None and res.status in OK:
                lat[j] = late[j] + res.latency_s
                served += lat[j] <= deadline
        stats = self.svc.stats
        # nearest rank, so that a missed request (inf) never interpolates
        p95 = float(np.percentile(lat, 95, method="higher"))
        self.lat = lat
        return {"metrics": {"req_p95_ms": 1e3 * p95,
                            "served_req_per_s": served / seconds},
                "attempted": len(due), "failed": len(due) - int(served),
                "window_s": window,
                "counters": {"dispatches": int(stats["dispatches"]),
                             "padded_slots": int(stats["padded_slots"]),
                             "batch_width": int(self.svc.batch_width),
                             "gen_late_p95_ms": 1e3 * float(np.percentile(late, 95)),
                             "statuses": {s: sum(1 for _, r in self.answered
                                                 if r is not None and r.status == s)
                                          for s in ("ok", "infeasible", "timeout",
                                                    "rejected", "shed")}}}

    def _tick(self, results: list) -> None:
        with TraceAnnotation("flush"):
            self.svc.flush()
        with TraceAnnotation("poll"):
            results.extend(self.svc.drain())

    def collect(self) -> None:
        self.svc = None

    def check(self, dtype=np.float64) -> dict:
        """Every request answered with an allocation, against the reference
        on that request, solved in SIC order and put back in the request's
        own order.  ``unanswered`` counts the requests that got no row at
        all.  A refused or shed row, and a ``timeout`` row that expired in
        the queue before it was solved (``iterations == 0``, NaN arrays by
        the service's contract), carries no allocation: it is an answer,
        counted as failed, and not compared."""
        rows = [(q, r) for q, r in self.answered if r is not None and (
            r.status in OK or (r.status == "timeout" and r.iterations > 0))]
        missing = sum(1 for _, r in self.answered if r is None)
        if not rows:
            return {name: float("inf") for name in compare.NUMBERS}
        h2 = np.stack([np.asarray(q.h2, np.float64) for q, _ in rows])
        order = np.argsort(-h2, axis=1, kind="stable")
        take = lambda a: np.take_along_axis(a, order, axis=1)
        d = take(np.stack([np.asarray(q.d, np.float64) for q, _ in rows]))
        v = take(np.stack([np.asarray(q.v_max, np.float64) for q, _ in rows]))
        t_max = np.asarray([q.cfg.t_max for q, _ in rows])
        phys = dict(self.phys, t_max=t_max)
        s = self.solver
        kw = dict(epsilon=float(self.traffic["epsilon"]), max_iter=s["max_iter"],
                  tol=s["tol"], dinkelbach_delta=s["dinkelbach_delta"],
                  dinkelbach_iter=s["dinkelbach_iter"])
        ref = reference.equilibrium(take(h2), d, v, phys, **kw)
        inv = np.argsort(order, axis=1)
        back = lambda a: np.take_along_axis(np.asarray(a, np.float64), inv, axis=1)
        ref = dict(ref, p=back(ref["p"]), f=back(ref["f"]),
                   alpha=back(ref["alpha"]), t_com=back(ref["t_com"]))
        if dtype is np.float64:
            got = {f: np.stack([getattr(r, f) for _, r in rows])
                   for f in ("p", "f", "alpha")}
            got.update({f: np.asarray([getattr(r, f) for _, r in rows])
                        for f in ("t_total", "energy", "feasible")})
        else:
            got = reference.equilibrium(take(h2), d, v, phys, **kw, dtype=dtype)
            got = dict(got, p=back(got["p"]), f=back(got["f"]),
                       alpha=back(got["alpha"]))
        numbers = compare.allocation_numbers(got, ref, t_max)
        numbers["unanswered"] = float(missing)
        return numbers
