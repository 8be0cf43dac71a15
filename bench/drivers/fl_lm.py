"""Closed loop of FL rounds whose client model is a language model: one
``sweep_training`` call after another at C = 1 point x S = 1 seed x R = 1
round, as an operator's edge server runs DT-assisted FL rounds of a hybrid
Mamba / MoE language model over its NOMA uplink.  Each call ends in a host
readback of the round's metrics history (``val_acc``, ``energy``,
``latency``, ``n_excluded_roni``, ``n_stragglers``, ``selected`` and the
expert counters ``expert_tokens`` [N + 1, steps, n_moe, held] and
``tokens_dropped``).

The configuration (``bench/configs/<config>.json``) holds the model's
published settings under their own names, cut to one chip's share (its
``reduced`` and ``held``), and the paper's physics, channel and client
counts.  Traffic parameters: ``point`` (lr, DT deviation eps, deadline
t_max), ``seeds`` (S), ``rounds`` (R), the round protocol (``scheme``,
``use_roni``, ``roni_threshold``, ``selection_weights``, ``local_steps``,
``server_steps``, ``batch`` sequences a step, ``samples_per_unit``),
``data`` (``bench/fl_lm_inputs.py``), ``pool`` (input sets made at set-up
and cycled).  The window's last round is compared against the plain
reference (``bench/fl_lm_reference.py``), computed on the chip.

The model's matmuls take their inputs in the configuration's
``compute_dtype`` with float32 accumulation; the calls run under the
default matmul precision.
"""
from __future__ import annotations

import functools
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench import compare, fl_lm_inputs, fl_lm_reference, inputs, work_lm
from bench.drivers.fl_sweep import protocol as _protocol

FIELDS = ("val_acc", "energy", "latency", "n_excluded_roni", "n_stragglers",
          "selected", "expert_tokens", "tokens_dropped")


@jax.jit
def finite_weights(params):
    """[C, S]: whether every weight of each grid cell's model is finite."""
    return functools.reduce(jnp.logical_and, [
        jnp.all(jnp.isfinite(a), axis=tuple(range(2, a.ndim)))
        for a in jax.tree_util.tree_leaves(params)])


def protocol(config: dict, traffic: dict) -> dict:
    """The round protocol's discrete and shared settings."""
    return dict(_protocol(config, traffic), batch=int(traffic["batch"]))


def model_config(config: dict):
    """The program's ``HybridConfig`` of the configuration's model."""
    from repro.models.nemotron_h import HybridConfig
    c, held = config, config["held"]
    return HybridConfig(
        name=c["name"], pattern=c["hybrid_override_pattern"],
        d_model=c["hidden_size"], vocab_size=c["vocab_size"],
        norm_eps=c["norm_eps"], mamba_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk=c["chunk_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        n_experts=held["router_experts"], top_k=c["num_experts_per_tok"],
        expert_ff=c["moe_intermediate_size"],
        shared_ff=c["moe_shared_expert_intermediate_size"],
        routed_scale=c["routed_scaling_factor"],
        norm_topk=c["norm_topk_prob"], expert_first=held["expert_first"],
        experts_held=c["n_routed_experts"], dtype=c["compute_dtype"])


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float):
        self._build(config, traffic, seed, int(traffic["pool"]))
        self.last = None
        self._call(0)                   # compile and warm the one shape
        self.last = None

    def _build(self, config: dict, traffic: dict, seed: int, pool: int):
        """The round's settings, the model's initial weights (shared by the
        input sets) and ``pool`` input sets."""
        from repro.core.fl_round import FLConfig, FLState, sweep_training
        from repro.core.reputation import ReputationState
        from repro.core.stackelberg import GameConfig
        from repro.data.federated import FedData
        from repro.models.nemotron_h import LMClient
        self._sweep = sweep_training
        # the persistent compilation cache writes into a directory that
        # has to exist: without it every run of this cell compiles its
        # round and its reference anew
        cache = jax.config.jax_compilation_cache_dir
        if cache:
            Path(cache).mkdir(parents=True, exist_ok=True)
        self.config, self.traffic, self.seed = config, traffic, seed
        self.solver = config["solver"]
        self.phys = inputs.physics(config)
        self.proto = pr = protocol(config, traffic)
        self.point = {k: float(v) for k, v in traffic["point"].items()}
        self.seeds, self.rounds = int(traffic["seeds"]), int(traffic["rounds"])
        self.model = LMClient(model_config(config), batch=pr["batch"])
        self.fl = FLConfig(
            n_selected=pr["n_selected"], local_steps=pr["local_steps"],
            server_steps=pr["server_steps"], lr=self.point["lr"],
            epsilon=self.point["epsilon"],
            roni_threshold=pr["roni_threshold"], weights=pr["weights"],
            scheme=traffic["scheme"], use_roni=pr["use_roni"],
            samples_per_unit=pr["samples_per_unit"])
        self.game = GameConfig(**dict(self.phys, t_max=self.point["t_max"]),
                               dinkelbach_inner=self.solver["dinkelbach_inner"],
                               sic_mode=self.solver["sic_mode"])
        self.inputs, self.pool = [], []
        with TraceAnnotation("generate"):
            self.params = fl_lm_inputs.initial_params(
                inputs.prng_key(seed, 9), self.seeds, config)
            for i in range(pool):
                g = fl_lm_inputs.grid_inputs(inputs.prng_key(seed, 10 + i),
                                             self.seeds, config, traffic)
                self.inputs.append(g)
                self.pool.append((FLState(
                    params=self.params,
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
        with TraceAnnotation("enqueue"):
            final, metrics = self._sweep(states, data, self.fl, self.game,
                                         self.model, self.rounds,
                                         data_axis="seed")
            finite = finite_weights(final.params)
        with TraceAnnotation("readback"):
            host = jax.device_get(dict({f: metrics[f] for f in FIELDS},
                                       finite_weights=finite))
        return final, host

    def _flops(self) -> float:
        """Model FLOPs of one call's C x S x R rounds (``work_lm``): the
        N clients' and the twin's SGD steps, and the validation passes of
        RONI (the global model, the twin, each client) and of the round's
        accuracy."""
        d = self.traffic["data"]
        s, n = int(d["seq_len"]), self.proto["n_selected"]
        trained = ((n * self.proto["local_steps"] + self.proto["server_steps"])
                   * self.proto["batch"] * s)
        passes = (n + 3) if self.proto["use_roni"] else 1
        evaluated = passes * int(d["val_seqs"]) * s
        return self.seeds * self.rounds * work_lm.round_flops(
            self.config, s, trained, evaluated)

    def run(self, seconds: float) -> dict:
        """Calls until ``seconds`` have passed; the last call's output is
        kept for the check, every other call's final state let go before
        the next call starts.  A round fails where its validation accuracy,
        energy or latency is not finite, or a weight of the model it
        leaves is not."""
        calls, bad, wasted, dropped, ratios = 0, 0, 0, 0, []
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            i = calls % len(self.pool)
            final, host = self._call(i)
            calls += 1
            bad += int(np.sum(~(np.isfinite(host["val_acc"])
                                & np.isfinite(host["energy"])
                                & np.isfinite(host["latency"])
                                & host["finite_weights"][..., None])))
            n = host["selected"].shape[-1]
            wasted += int(np.sum(np.minimum(
                n, host["n_excluded_roni"] + host["n_stragglers"])))
            dropped += int(np.sum(host["tokens_dropped"]))
            tok = host["expert_tokens"].astype(np.float64)
            ratios.append(np.mean(np.max(tok, -1)
                                  / np.maximum(np.mean(tok, -1), 1e-30)))
            now = time.perf_counter()
            if now >= end:
                self.last = (i, final, host)
                break
            del final
        window = now - t0
        grid = self.seeds * self.rounds
        return {"metrics": {"rounds_per_s": calls * grid / window},
                "attempted": calls * grid, "failed": bad, "window_s": window,
                "counters": {"calls": calls, "window_s": window,
                             "fl_flops": calls * self._flops(),
                             "chips": jax.device_count(),
                             "fl_update_waste": 100.0 * wasted
                             / (calls * grid * self.proto["n_selected"]),
                             "tokens_dropped": dropped,
                             "moe_load_max_ratio": float(np.mean(ratios))}}

    def collect(self) -> None:
        """Bring the checked round (the window's last, its first seed), its
        inputs and the initial weights to the host, and let go of every
        device array of the program."""
        i, final, host = self.last
        got = {f: np.asarray(host[f])[0, 0, 0] for f in FIELDS}
        got.update(jax.device_get({
            "params": jax.tree_util.tree_map(lambda a: a[0, 0], final.params),
            "pi_count": final.rep.pi_count[0, 0],
            "ni_count": final.rep.ni_count[0, 0]}))
        inp = jax.device_get(jax.tree_util.tree_map(lambda a: a[0],
                                                    self.inputs[i]))
        self.checked = (got, inp, jax.device_get(jax.tree_util.tree_map(
            lambda a: a[0], self.params)))
        self.pool = self.last = self.inputs = self.params = final = None

    def check(self, dtype=np.float64) -> dict:
        """The numbers compared (``numbers``), the reference computed on
        the default device.  ``dtype`` other than float64 puts the
        reference in that precision in the program's place: the
        control."""
        got, inp, init = self.checked
        init = jax.device_put(init)
        args = (inp, init, self.point, self.proto, self.phys,
                self.config["channel"], self.solver, self.config)
        if dtype is not np.float64:
            got = fl_lm_reference.round_reference(*args, dtype=dtype)
            got["tokens_dropped"] = 0
        ref = fl_lm_reference.round_reference(*args, follow=got)
        return numbers(got, ref, init)


def _leaves(tree) -> list:
    return jax.tree_util.tree_leaves(tree)


def param_change_gap(got, ref, init) -> float:
    """The worst leaf's gap between the program's change of the weights
    over the round and the reference's, ||dw_got - dw_ref||, over the
    reference's ||dw_ref|| of that leaf or a hundredth of the median
    leaf's, whichever is larger: a leaf that barely moves, such as the
    correction bias (no gradient reaches it; Eq. (3) only rescales it), is
    held to a small share of the others' scale, not to its own.  Infinite
    where a leaf's gap or change is not finite, in whichever leaf."""
    gaps, norms = [], []
    for g, r, w in zip(_leaves(got), _leaves(ref), _leaves(init)):
        w = jnp.asarray(w, jnp.float32)
        dr = jnp.asarray(r, jnp.float32) - w
        dg = jnp.asarray(g, jnp.float32) - w
        gaps.append(float(jnp.linalg.norm((dg - dr).ravel())))
        norms.append(float(jnp.linalg.norm(dr.ravel())))
    if not np.all(np.isfinite(gaps + norms)):
        return float("inf")
    floor = 1e-2 * float(np.median(norms))
    return max(gap / max(nrm, floor, 1e-30) for gap, nrm in zip(gaps, norms))


def numbers(got: dict, ref: dict, init) -> dict:
    """The numbers that decide ``correct`` for the checked round:

    * ``decision_flips``: selected clients in SIC order, RONI-rejected and
      late counts, and every client's tally of positive and negative
      verdicts, exactly;
    * ``tokens_dropped``: assignments to held experts left uncomputed, 0
      by construction of the expert blocks (``nemotron_h.moe_mixer``): the
      check holds the program to that invariant;
    * ``val_acc_gap``: the round's validation accuracy, absolute gap;
    * ``energy_cond``, ``latency_cond``: the allocation's relative errors
      over the draw's condition number (``compare.condition``);
    * ``param_change_gap`` (``param_change_gap``);
    * ``uncompared_share``: 1 where an edge decision was left unsettled
      (the round is then not compared), else 0; ``edge_overrides``: edge
      decisions the reference took the program's side of against its own.
    """
    unsettled = ref["edge"] is not None
    flips = (int(np.any(np.asarray(got["selected"]) != ref["selected"]))
             + abs(int(got["n_excluded_roni"]) - ref["n_excluded_roni"])
             + abs(int(got["n_stragglers"]) - ref["n_stragglers"])
             + int(np.sum(np.abs(np.asarray(got["pi_count"])
                                 - ref["pi_count"])))
             + int(np.sum(np.abs(np.asarray(got["ni_count"])
                                 - ref["ni_count"]))))
    rel = lambda f: float(compare._rel(np.asarray([got[f]]),
                                       np.asarray([ref[f]]))[0] / ref["cond"])
    out = {"decision_flips": float(flips),
           "tokens_dropped": float(np.sum(got["tokens_dropped"])),
           "val_acc_gap": abs(float(got["val_acc"]) - ref["val_acc"]),
           "energy_cond": rel("energy"), "latency_cond": rel("latency"),
           "param_change_gap": (float("inf") if unsettled else
                                param_change_gap(got["params"],
                                                 ref["params"], init)),
           "uncompared_share": float(unsettled),
           "edge_overrides": float(len(ref["overridden"]))}
    if unsettled:
        out["decision_flips"] = 0.0
    return out


def compiled_text(config: dict, traffic: dict) -> str:
    """Compiled text of the program that the cell's calls run: the
    operands that one call of the cell's traffic through
    ``sweep_training`` hands its jitted grid program, recorded on the way
    (the call itself is not made), and that program lowered and compiled
    for them."""
    from repro.core import fl_round
    real, seen = fl_round._sweep_training_jit, []

    class Recorded(Exception):
        pass

    def record(*args, **kwargs):
        seen.append((args, kwargs))
        raise Recorded
    cell = Cell.__new__(Cell)
    cell._build(config, traffic, seed=0, pool=1)
    fl_round._sweep_training_jit = record
    try:
        cell._call(0)
    except Recorded:
        pass
    finally:
        fl_round._sweep_training_jit = real
    (args, kwargs), = seen
    return real.lower(*args, **kwargs).compile().as_text()
