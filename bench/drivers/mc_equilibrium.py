"""Closed-loop Monte-Carlo of the equilibrium, as a planner's figure script
runs it: one ``batched_equilibrium`` call of K draws after another, each
ending in a host readback of its per-call summary (energies, feasible
flags, leader iterations).  The share of draws that meet their deadline is
counted, so that a cell whose draws are all infeasible shows.

Traffic parameters: ``draws_per_call`` (K), ``pool`` (input batches made at
set-up and cycled, so consecutive calls solve different draws),
``epsilon`` (the DT deviation), ``check_slots`` (how many pool batches the
check compares, drawn from the seed).
"""
from __future__ import annotations

import time

import numpy as np

import jax
from jax.profiler import TraceAnnotation

from bench import compare, inputs, reference

FIELDS = ("p", "f", "alpha", "t_total", "energy", "feasible")


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float):
        from repro.core.stackelberg import GameConfig, batched_equilibrium
        self._solve = batched_equilibrium
        self.config, self.traffic, self.seed = config, traffic, seed
        self.solver = config["solver"]
        self.phys = inputs.physics(config)
        self.cfg = GameConfig(**self.phys,
                              dinkelbach_inner=self.solver["dinkelbach_inner"],
                              sic_mode=self.solver["sic_mode"])
        self.k = int(traffic["draws_per_call"])
        n = int(config["clients_per_round"])
        pool = int(traffic["pool"])
        with TraceAnnotation("generate"):
            h2, d, v = inputs.client_draws(inputs.prng_key(seed),
                                           (pool, self.k, n), config)
            self.pool = jax.block_until_ready(
                [(h2[i], d[i], v[i]) for i in range(pool)])
        for i in range(2):          # compile, then one steady call
            self._call(i % pool)
        self.last = [None] * pool

    def _call(self, i: int):
        h2, d, v = self.pool[i]
        with TraceAnnotation("enqueue"):
            out = self._solve(self.cfg, h2, d, v,
                              epsilon=float(self.traffic["epsilon"]),
                              max_iter=int(self.solver["max_iter"]),
                              tol=float(self.solver["tol"]))
        with TraceAnnotation("readback"):
            energy, feasible, iters = jax.device_get(
                (out.energy, out.feasible, out.iterations))
        return out, energy, feasible, iters

    def run(self, seconds: float) -> dict:
        calls, bad, feasible = 0, 0, 0
        iters_mean, waste = [], []
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            i = calls % len(self.pool)
            self.last[i], energy, ok, iters = self._call(i)
            calls += 1
            bad += int(np.sum(~np.isfinite(energy)))
            feasible += int(np.sum(ok))
            iters_mean.append(float(np.mean(iters)))
            waste.append(1.0 - float(np.mean(iters)) / max(1, int(np.max(iters))))
            now = time.perf_counter()
            if now >= end:
                break
        window = now - t0
        return {"metrics": {"solves_per_s": calls * self.k / window},
                "attempted": calls * self.k, "failed": bad,
                "window_s": window,
                "counters": {"calls": calls,
                             "leader_iters_mean": float(np.mean(iters_mean)),
                             "leader_lane_waste": 100.0 * float(np.mean(waste)),
                             "feasible_share": 100.0 * feasible / (calls * self.k)}}

    def collect(self) -> None:
        """Bring the checked outputs and their inputs to the host, then let
        go of every device array of the program."""
        rng = np.random.default_rng([self.seed, 1])
        used = [i for i, out in enumerate(self.last) if out is not None]
        slots = rng.choice(used, min(len(used), int(self.traffic["check_slots"])),
                           replace=False)
        self.checked = [jax.device_get(
            ({f: getattr(self.last[i], f) for f in FIELDS}, self.pool[i]))
            for i in sorted(slots)]
        self.pool = self.last = None

    def check(self, dtype=np.float64) -> dict:
        """The numbers compared, worst over the checked batches.  ``dtype``
        other than float64 puts the reference in the program's place in
        that precision: the control."""
        s = self.solver
        numbers = []
        for got, (h2, d, v) in self.checked:
            args = (np.asarray(h2, np.float64), np.asarray(d, np.float64),
                    np.asarray(v, np.float64), self.phys)
            kw = dict(epsilon=float(self.traffic["epsilon"]),
                      max_iter=s["max_iter"], tol=s["tol"],
                      dinkelbach_delta=s["dinkelbach_delta"],
                      dinkelbach_iter=s["dinkelbach_iter"])
            ref = reference.equilibrium(*args, **kw)
            if dtype is not np.float64:
                got = reference.equilibrium(*args, **kw, dtype=dtype)
            numbers.append(compare.allocation_numbers(got, ref,
                                                      self.phys["t_max"]))
        return compare.merge(numbers)


def compiled_text(config: dict, traffic: dict) -> str:
    """Compiled text of the program the cell's calls run:
    ``batched_equilibrium`` at the cell's draws per call, clients and
    solver settings."""
    from repro.core import stackelberg as st
    solver = config["solver"]
    cfg = st.GameConfig(**inputs.physics(config),
                        dinkelbach_inner=solver["dinkelbach_inner"],
                        sic_mode=solver["sic_mode"])
    zeros = np.zeros((int(traffic["draws_per_call"]),
                      int(config["clients_per_round"])), np.float32)
    phys, h2, d, vm, eps, tol, shards, _ = st._canon_batch(
        cfg, zeros, zeros, zeros, float(traffic["epsilon"]),
        float(solver["tol"]))
    return st._batched_equilibrium_jit.lower(
        phys, h2, d, vm, eps, tol, max_iter=int(solver["max_iter"]),
        inner=cfg.dinkelbach_inner, sic_mode=cfg.sic_mode,
        shards=shards).compile().as_text()
