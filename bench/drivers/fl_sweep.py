"""Closed loop of FL training grids, as a planner's figure script runs the
paper's Figs. 5-8: one ``sweep_training`` call after another, C
configuration points x S seeds x R rounds each, every call ending in a host
readback of its metrics history (``val_acc``, ``energy``, ``latency``,
``n_excluded_roni``, ``n_stragglers`` [C, S, R] and ``selected``
[C, S, R, N]).  On several chips the program lays the C x S grid over its
(cfg, draw) mesh.

Traffic parameters: ``points`` (C rows of ``point_keys``: lr, DT deviation
eps, deadline t_max), ``seeds`` (S, each with its own dataset), ``rounds``
(R), the round protocol (``scheme``, ``use_roni``, ``roni_threshold``,
``selection_weights``, ``local_steps``, ``server_steps``,
``samples_per_unit``), ``data`` and ``model`` (``bench/fl_inputs.py``),
``pool`` (input sets made at set-up and cycled, so that consecutive calls
train on different inputs), ``check_slots`` (grid points of the pool's last
calls that the check compares, drawn from the seed).

The calls run under the matmul precision that gives the configuration's
``precision``: for float32, ``highest``, since the TPU's default float32
dot is one bfloat16 pass.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench import fl_compare, fl_inputs, fl_reference, inputs, work

FIELDS = ("val_acc", "energy", "latency", "n_excluded_roni", "n_stragglers",
          "selected")
# the default matmul precision that computes in a configuration's precision
MATMUL_PRECISION = {"float32": "highest"}


def protocol(config: dict, traffic: dict) -> dict:
    """The round protocol's discrete and shared settings."""
    if traffic["scheme"] != "proposed":
        raise ValueError(f"the FL reference runs the 'proposed' scheme, not "
                         f"{traffic['scheme']!r}")
    return {"n_selected": int(config["clients_per_round"]),
            "local_steps": int(traffic["local_steps"]),
            "server_steps": int(traffic["server_steps"]),
            "roni_threshold": float(traffic["roni_threshold"]),
            "weights": tuple(float(w) for w in traffic["selection_weights"]),
            "samples_per_unit": float(traffic["samples_per_unit"]),
            "use_roni": bool(traffic["use_roni"])}


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float):
        self._build(config, traffic, seed, int(traffic["pool"]))
        # per slot, [S, M] data sizes and v_max: the work count's inputs
        self.sizes = [jax.device_get((g["sizes"], g["v_max"]))
                      for g in self.inputs]
        dims = [(a, b) for _, a, b in fl_inputs.mlp_shapes(
            int(traffic["data"]["dim"]), int(traffic["model"]["hidden"]))]
        self.weights = work.mlp_weights(dims)
        self.last = [None] * len(self.pool)
        for i in range(2):              # compile, then one steady call
            self._call(i % len(self.pool))

    def _build(self, config: dict, traffic: dict, seed: int, pool: int):
        """The grid's configuration points and ``pool`` input sets."""
        from repro.core.fl_round import FLConfig, FLState, sweep_training
        from repro.core.reputation import ReputationState
        from repro.core.stackelberg import GameConfig
        from repro.data.federated import FedData
        from repro.models.classifier import mlp_classifier_logits
        self._sweep, self._logits = sweep_training, mlp_classifier_logits
        self.config, self.traffic, self.seed = config, traffic, seed
        self.solver = config["solver"]
        self.phys = inputs.physics(config)
        self.proto = pr = protocol(config, traffic)
        self.points = [dict(zip(traffic["point_keys"], map(float, p)))
                       for p in traffic["points"]]
        self.seeds, self.rounds = int(traffic["seeds"]), int(traffic["rounds"])
        self.precision = MATMUL_PRECISION[config["precision"]]
        self.fls = [FLConfig(
            n_selected=pr["n_selected"], local_steps=pr["local_steps"],
            server_steps=pr["server_steps"], lr=p["lr"], epsilon=p["epsilon"],
            roni_threshold=pr["roni_threshold"], weights=pr["weights"],
            scheme=traffic["scheme"], use_roni=pr["use_roni"],
            samples_per_unit=pr["samples_per_unit"]) for p in self.points]
        self.games = [GameConfig(**dict(self.phys, t_max=p["t_max"]),
                                 dinkelbach_inner=self.solver["dinkelbach_inner"],
                                 sic_mode=self.solver["sic_mode"])
                      for p in self.points]
        self.inputs, self.pool = [], []
        with TraceAnnotation("generate"):
            for i in range(pool):
                g = fl_inputs.grid_inputs(inputs.prng_key(seed, 10 + i),
                                          self.seeds, config, traffic)
                self.inputs.append(g)
                self.pool.append((FLState(
                    params=g["params"],
                    rep=ReputationState(ms=g["ms"], pi_count=g["pi_count"],
                                        ni_count=g["ni_count"]),
                    v_max=g["v_max"], distances=g["distances"], key=g["key"],
                    round=jnp.zeros((self.seeds,), jnp.int32)),
                    FedData(**{f: g[f] for f in (
                        "x", "y", "y_train", "mask", "sizes", "poisoned",
                        "x_val", "y_val")})))
            jax.block_until_ready(self.pool)

    def _call(self, i: int):
        states, data = self.pool[i]
        with TraceAnnotation("enqueue"), \
                jax.default_matmul_precision(self.precision):
            final, metrics = self._sweep(states, data, self.fls, self.games,
                                         self._logits, self.rounds,
                                         data_axis="seed")
        with TraceAnnotation("readback"):
            host = jax.device_get({f: metrics[f] for f in FIELDS})
        return final, host

    def _flops(self, i: int, selected) -> float:
        """Model FLOPs of one call's C x S x R rounds (``work.fl_training``):
        the selected clients' samples, the unmapped ones counted as
        (1 - v)·D and the mapped ones as v·D, the DT split's expectation."""
        sizes, v_max = self.sizes[i]
        pick = lambda a: np.take_along_axis(
            np.broadcast_to(a[None, :, None, :], selected.shape[:3]
                            + a.shape[-1:]), selected, axis=-1)
        d, v = pick(sizes).astype(np.float64), pick(v_max).astype(np.float64)
        n = selected.shape[-1]
        passes = (n + 3) if self.proto["use_roni"] else 1
        return work.fl_training(
            self.weights, local_samples=float(np.sum((1.0 - v) * d)),
            mapped_samples=float(np.sum(v * d)),
            local_steps=self.proto["local_steps"],
            server_steps=self.proto["server_steps"],
            val_samples=int(self.traffic["data"]["val_size"])
            * int(np.prod(selected.shape[:3])) * passes)["flops"]

    def run(self, seconds: float) -> dict:
        calls, bad, flops, wasted = 0, 0, 0.0, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            i = calls % len(self.pool)
            self.last[i] = self._call(i)
            calls += 1
            host = self.last[i][1]
            bad += int(np.sum(~(np.isfinite(host["val_acc"])
                                & np.isfinite(host["energy"])
                                & np.isfinite(host["latency"]))))
            flops += self._flops(i, host["selected"])
            n = host["selected"].shape[-1]
            wasted += int(np.sum(np.minimum(
                n, host["n_excluded_roni"] + host["n_stragglers"])))
            now = time.perf_counter()
            if now >= end:
                break
        window = now - t0
        grid = len(self.points) * self.seeds * self.rounds
        n = self.proto["n_selected"]
        return {"metrics": {"rounds_per_s": calls * grid / window},
                "attempted": calls * grid, "failed": bad, "window_s": window,
                "counters": {"calls": calls, "window_s": window,
                             "fl_flops": flops,
                             "chips": jax.device_count(),
                             "mesh_devices": _devices(self.last[0][0]),
                             "fl_update_waste": 100.0 * wasted
                             / (calls * grid * n)}}

    def collect(self) -> None:
        """Bring the checked grid points' inputs and outputs to the host,
        then let go of every device array of the program."""
        rng = np.random.default_rng([self.seed, 1])
        used = [i for i, out in enumerate(self.last) if out is not None]
        cells = [(i, c, s) for i in used for c in range(len(self.points))
                 for s in range(self.seeds)]
        take = rng.choice(len(cells), min(len(cells),
                                          int(self.traffic["check_slots"])),
                          replace=False)
        self.checked = []
        for i, c, s in (cells[k] for k in sorted(take)):
            final, host = self.last[i]
            got = {f: host[f][c, s] for f in FIELDS}
            got.update(jax.device_get({
                "params": {k: v[c, s] for k, v in final.params.items()},
                "pi_count": final.rep.pi_count[c, s],
                "ni_count": final.rep.ni_count[c, s]}))
            inp = jax.device_get(jax.tree_util.tree_map(
                lambda a: a[s], self.inputs[i]))
            self.checked.append((c, got, inp))
        self.pool = self.last = self.inputs = None

    def check(self, dtype=np.float64) -> dict:
        """The numbers compared, worst over the checked grid points
        (``bench/fl_compare.py``).  ``dtype`` other than float64 puts the
        reference in that precision in the program's place: the control."""
        numbers = []
        for c, got, inp in self.checked:
            args = (inp, self.points[c], self.proto, self.phys,
                    self.config["channel"], self.solver, self.rounds)
            if dtype is not np.float64:
                got = fl_reference.trajectory(*args, dtype=dtype)
            ref = fl_reference.trajectory(*args, follow=got)
            numbers.append(fl_compare.point_numbers(got, ref, inp))
        return fl_compare.merge(numbers)


def _devices(final) -> int:
    """Devices that hold the grid's output."""
    leaf = jax.tree_util.tree_leaves(final)[0]
    return len(leaf.sharding.device_set)


def compiled_text(config: dict, traffic: dict) -> str:
    """Compiled text of the program that the cell's calls run: one call of
    the cell's traffic through ``sweep_training`` itself, with the operands
    that it hands its jitted grid program recorded on the way and that
    program lowered and compiled again for them."""
    from repro.core import fl_round
    real, seen = fl_round._sweep_training_jit, []

    def record(*args, **kwargs):
        seen.append((args, kwargs))
        return real(*args, **kwargs)
    cell = Cell.__new__(Cell)
    cell._build(config, traffic, seed=0, pool=1)
    fl_round._sweep_training_jit = record
    try:
        cell._call(0)
    finally:
        fl_round._sweep_training_jit = real
    (args, kwargs), = seen
    with jax.default_matmul_precision(cell.precision):
        return real.lower(*args, **kwargs).compile().as_text()
