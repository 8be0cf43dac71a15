"""Chip smoke test: the system's main path, end to end, on a TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # the sharded sweeps on 4 chips vs 1

One process drives the chip.  Each phase prints one line — its shapes,
its seconds (compilation included) and the result of its check — and the
last line of standard output is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

That line is printed only when every phase passed.  A failed check or an
exception in any phase exits non-zero; so does a host without a TPU
(there is no CPU fallback) and a directory without the repository's
``src/``.

Phases (one chip):

  * device      — ``jax.devices()[0]`` is a TPU.
  * mc_equilibrium — ``batched_equilibrium`` (scheme ``proposed``) at the
    paper's N=5 over K=1024 draws of ``sample_sic_channel_batch``, checked
    against ``equilibrium_eager`` on the CPU device of this process on 32
    of the draws (feasible flags away from the deadline, energy, t_total).
  * large_n_sic — ``sic_mode="blocked_pallas"`` at N=1024, K=64 (the
    compiled Pallas suffix kernel: ``tpu_custom_call`` in the HLO),
    checked against ``sic_mode="sequential"`` on the same draws.
  * fl_trajectory — one ``run_training_scan`` at the figure-bench settings
    (MNIST-proxy MLP 784→64, M=20, N=5, cap=128, 30% poisoners, RONI on,
    40 local + 40 server steps, R=20): one trace, finite metrics, final
    accuracy within ``FL_ACC_BOUND`` of the same scan on the CPU device.
  * alloc_service — ``AllocationService`` with its default buckets: warm
    up, 64 requests with N mixed over 5–128, drain; exactly one result
    per rid, no ``rejected``/``timeout`` row, no dispatch failure, every
    row equal to ``equilibrium`` on that request.
  * mechanism   — two ``mechanism_step``s: finite objective and
    gradients, one trace.

Phases (``--four-chips``): ``sweep_equilibrium`` at C=10 × K=256 and
``sweep_training`` at C=6 × S=4 × R=20 on the 2-D ("cfg", "draw") mesh
over the 4 chips, each compared with the same call pinned to one chip
(``REPRO_MESH_DEVICES=1``), with a check that the sharded outputs span
all 4 devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

REL = 1e-5            # the tests' relative parity bound (test_sic.py etc.)
FL_ACC_BOUND = 0.05   # |final val_acc(TPU) − val_acc(CPU)|, absolute
EDGE_ULPS = 8         # deadline band where the feasible flag is a coin flip
SEED = 0


class CheckFailed(AssertionError):
    """A phase's result disagrees with its reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def max_rel(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def deadline_kappa(alloc, cfg):
    """Per draw, the condition number of the leader's CPU frequencies (and
    so of the energy) with respect to the airtimes: f_n = c(1-v)D_n /
    (t_max - t_com_n) turns a relative error d in t_com_n into
    t_com_n / (t_max - t_com_n) * d in f_n.  The clip to [f_min, f_max]
    only lowers that, except for a client whose unclipped f_n lies within
    the error of the box edge, which one backend clips and the other not:
    so every client ahead of the deadline counts.  Past it the leader
    clamps the slack to 1e-3 s and f_n no longer depends on t_com_n.  A
    draw that schedules a client just inside the deadline has a large
    kappa, and its energy differs between any two f32 evaluation orders
    (CPU jit vs CPU eager included) by about kappa ulps."""
    import numpy as np
    t_com = np.asarray(alloc.t_com, np.float64)
    kappa = t_com / np.maximum(cfg.t_max - t_com, 1e-3)
    return np.max(np.where(t_com < cfg.t_max, kappa, 0.0), axis=-1)


def all_finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.all(np.isfinite(np.asarray(x))))
               for x in jax.tree_util.tree_leaves(tree)
               if np.issubdtype(np.asarray(x).dtype, np.floating))


# ---------------------------------------------------------------------------
# one-chip phases — each returns (shapes, result) strings or raises
# ---------------------------------------------------------------------------
def phase_mc_equilibrium(k: int = 1024, n: int = 5, n_ref: int = 32):
    """Feasibility is ``max(t_cmp + t_com) <= t_max + 1e-6``, and the leader
    schedules deadline-exact finishes, so many draws land within a few f32
    ulps of that threshold (1 ulp of 10 s is 9.5e-7 s).  There either flag
    is a correct f32 answer; the flags are compared on the draws whose
    reference lies more than ``EDGE_ULPS`` ulps of t_max from it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.channel import sample_sic_channel_batch
    from repro.core.stackelberg import (GameConfig, batched_equilibrium,
                                        equilibrium_eager)
    cfg = GameConfig()
    key = jax.random.PRNGKey(SEED)
    h2 = sample_sic_channel_batch(key, k, n)
    d = 100.0 + 200.0 * jax.random.uniform(jax.random.fold_in(key, 1), (k, n))
    vm = 0.3 + 0.5 * jax.random.uniform(jax.random.fold_in(key, 2), (k, n))
    out = jax.block_until_ready(batched_equilibrium(cfg, h2, d, vm))
    check(all_finite((out.energy, out.t_total, out.p)),
          "non-finite batched equilibrium")
    host = {f: np.asarray(getattr(out, f))
            for f in ("energy", "t_total", "feasible")}
    h2_h, d_h, vm_h = (np.asarray(x) for x in (h2, d, vm))
    idx = np.linspace(0, k - 1, n_ref).astype(int)
    edge = EDGE_ULPS * float(np.spacing(np.float32(cfg.t_max)))
    flips, near = [], 0
    err = {"energy": 0.0, "t_total": 0.0}    # error / its bound, worst draw
    raw = dict(err)
    with jax.default_device(jax.devices("cpu")[0]):
        for i in idx:
            ref = equilibrium_eager(cfg, jnp.asarray(h2_h[i]),
                                    jnp.asarray(d_h[i]), jnp.asarray(vm_h[i]))
            slack = float(jnp.max(ref.t_cmp + ref.t_com)) - cfg.t_max - 1e-6
            if abs(slack) <= edge:
                near += 1
            elif bool(ref.feasible) != bool(host["feasible"][i]):
                flips.append(int(i))
            bound = {"energy": REL * max(1.0, float(deadline_kappa(ref, cfg))),
                     "t_total": REL}
            for f in err:
                e = max_rel(host[f][i], getattr(ref, f))
                raw[f] = max(raw[f], e)
                err[f] = max(err[f], e / bound[f])
    check(len(idx) - near >= 8, f"only {len(idx) - near} draws away from "
          "the deadline to compare feasible flags on")
    check(not flips and max(err.values()) <= 1.0,
          f"vs CPU eager: max rel {raw}, worst error/bound {err}; feasible "
          f"flag differs away from the deadline on draws {flips}")
    return (f"K={k} N={n}",
            f"{len(idx)} draws vs CPU equilibrium_eager: max rel energy "
            f"{raw['energy']:.3e} (<= {REL}*max(1, kappa): worst "
            f"{err['energy']:.2f} of bound), t_total {raw['t_total']:.3e} "
            f"<= {REL}; feasible flags equal on {len(idx) - near} draws "
            f"({near} within {EDGE_ULPS} ulps of the deadline), feasible "
            f"{int(host['feasible'].sum())}/{k}")


def phase_large_n_sic(k: int = 64, n: int = 1024):
    import jax
    import numpy as np
    from repro.core import stackelberg as sb
    from repro.core.channel import sample_sic_channel_batch
    key = jax.random.PRNGKey(SEED + 1)
    h2 = sample_sic_channel_batch(key, k, n)
    d = 100.0 + 200.0 * jax.random.uniform(jax.random.fold_in(key, 1), (k, n))
    vm = 0.3 + 0.5 * jax.random.uniform(jax.random.fold_in(key, 2), (k, n))
    cfg_k = sb.GameConfig(sic_mode="blocked_pallas")
    # the compiled program of exactly this call: the suffix kernel must be
    # in it as a Mosaic custom call, not interpreted or replaced by jnp
    phys, h2c, dc, vmc, eps, tol, shards, _ = sb._canon_batch(
        cfg_k, h2, d, vm, 0.0, 1e-6)
    hlo = sb._batched_equilibrium_jit.lower(
        phys, h2c, dc, vmc, eps, tol, max_iter=20,
        inner=cfg_k.dinkelbach_inner, sic_mode=cfg_k.sic_mode,
        shards=shards).compile().as_text()
    check("tpu_custom_call" in hlo, "no tpu_custom_call in the compiled "
          "blocked_pallas engine")
    got = jax.block_until_ready(sb.batched_equilibrium(cfg_k, h2, d, vm))
    ref_cfg = sb.GameConfig(sic_mode="sequential")
    ref = jax.block_until_ready(sb.batched_equilibrium(ref_cfg, h2, d, vm))
    check(all_finite((got.energy, got.t_total, got.p)),
          "non-finite blocked_pallas equilibrium")
    # the fields tests/test_sic.py holds the blocked engine to at the
    # equilibrium level; f and energy within REL * kappa (deadline_kappa)
    kappa = np.maximum(1.0, deadline_kappa(ref, ref_cfg))
    errs = {f: max_rel(getattr(got, f), getattr(ref, f))
            for f in ("p", "f", "energy", "t_total", "alpha")}
    scaled = {f: max(max_rel(getattr(got, f)[i], getattr(ref, f)[i])
                     / kappa[i] for i in range(k)) for f in ("f", "energy")}
    check(np.array_equal(np.asarray(got.feasible), np.asarray(ref.feasible)),
          "feasible flags differ between blocked_pallas and sequential")
    flat = max(errs[f] for f in ("p", "t_total", "alpha"))
    check(flat <= REL and max(scaled.values()) <= REL,
          f"blocked_pallas vs sequential: {errs}, scaled by kappa {scaled}")
    return (f"K={k} N={n}",
            "tpu_custom_call in HLO; vs sequential max rel "
            + ", ".join(f"{f} {e:.3e}" for f, e in errs.items())
            + f"; p, t_total, alpha <= {REL}, f and energy <= {REL}*kappa "
            f"(max kappa {float(kappa.max()):.1f})")


def _fl_cell():
    from benchmarks.common import fl_bench_config, fl_setup
    state, data, logits_fn = fl_setup(SEED, "mnist", poison_ratio=0.3)
    return state, data, logits_fn, fl_bench_config()


def phase_fl_trajectory(rounds: int = 20):
    import jax
    import numpy as np
    from repro.core.fl_round import run_training_scan
    from repro.core.stackelberg import GameConfig, TRACE_COUNTS
    state, data, logits_fn, fl = _fl_cell()
    before = TRACE_COUNTS["run_training_scan"]
    _, hist = run_training_scan(state, data, fl, GameConfig(), logits_fn,
                                rounds)
    hist = jax.block_until_ready(hist)
    traces = TRACE_COUNTS["run_training_scan"] - before
    check(traces == 1, f"run_training_scan traced {traces}x")
    check(all_finite(hist), "non-finite training metrics")
    acc = float(np.asarray(hist["val_acc"])[-1])
    with jax.default_device(jax.devices("cpu")[0]):
        state_c, data_c, logits_c, fl_c = _fl_cell()
        _, hist_c = run_training_scan(state_c, data_c, fl_c, GameConfig(),
                                      logits_c, rounds)
        acc_c = float(np.asarray(hist_c["val_acc"])[-1])
    diff = abs(acc - acc_c)
    check(diff <= FL_ACC_BOUND, f"final val_acc {acc:.4f} vs CPU "
          f"{acc_c:.4f}: |diff| {diff:.4f} > {FL_ACC_BOUND}")
    m, cap, dim = data.x.shape
    return (f"M={m} N={fl.n_selected} cap={cap} in={dim} hidden=64 "
            f"R={rounds} local={fl.local_steps} server={fl.server_steps}",
            f"1 trace, metrics finite, final val_acc {acc:.4f} vs CPU "
            f"{acc_c:.4f} (|diff| {diff:.4f} <= {FL_ACC_BOUND})")


def _service_requests(n_requests: int, sizes):
    """Cells of the paper's channel model (500 m disc, d^-3.76 path loss,
    Rayleigh fading), each with its own round deadline t_max (a traced
    operand: one executable per bucket serves them all).  Requests opt
    out of the retry ladder, so every row is the Stackelberg engine's own
    answer — ``ok`` or ``infeasible`` — and can be held to it."""
    import jax
    import numpy as np
    from repro.core.channel import sample_sic_channel_batch
    from repro.core.stackelberg import GameConfig
    from repro.launch.alloc_serve import AllocRequest
    rng = np.random.default_rng(SEED)
    reqs = []
    for i in range(n_requests):
        n = int(sizes[i % len(sizes)])
        h2 = np.asarray(sample_sic_channel_batch(
            jax.random.PRNGKey(1000 + i), 1, n)[0])
        perm = rng.permutation(n)          # arrives in no particular order
        reqs.append(AllocRequest(
            h2=h2[perm], d=rng.uniform(100.0, 300.0, n).astype(np.float32),
            v_max=rng.uniform(0.3, 0.8, n).astype(np.float32),
            cfg=GameConfig(t_max=float(rng.uniform(20.0, 60.0))),
            epsilon=0.05, deadline_s=600.0, allow_degraded=False))
    return reqs


def _exact_answer(req):
    """``equilibrium`` on one request, in the request's client order."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.stackelberg import equilibrium
    h2 = np.asarray(req.h2, np.float32)
    order = np.argsort(-h2, kind="stable")
    out = equilibrium(req.cfg, jnp.asarray(h2[order]),
                      jnp.asarray(np.asarray(req.d, np.float32)[order]),
                      jnp.asarray(np.asarray(req.v_max, np.float32)[order]),
                      epsilon=req.epsilon)
    inv = np.empty_like(order)
    inv[order] = np.arange(h2.shape[0])
    return {"p": np.asarray(out.p)[inv], "f": np.asarray(out.f)[inv],
            "energy": float(out.energy), "t_total": float(out.t_total),
            "feasible": bool(out.feasible)}


def phase_alloc_service(n_requests: int = 64,
                        sizes=(5, 8, 13, 16, 27, 32, 45, 64, 77, 100, 121,
                               128)):
    import collections
    from repro.launch.alloc_serve import AllocationService
    svc = AllocationService()
    warm_s = svc.warmup()
    reqs = _service_requests(n_requests, sizes)
    rids = [svc.submit(r) for r in reqs]
    res = svc.drain()
    by_rid = collections.Counter(r.rid for r in res)
    check(sorted(by_rid) == sorted(rids) and set(by_rid.values()) == {1},
          "not exactly one result per rid")
    status = collections.Counter(r.status for r in res)
    check(status["rejected"] == 0 and status["timeout"] == 0,
          f"rejected/timeout rows: {dict(status)} "
          f"({[r.error for r in res if r.status == 'rejected'][:2]})")
    check(svc.stats["dispatch_failures"] == 0, "dispatch failures")
    check(status["ok"] + status["infeasible"] == len(res),
          f"unsolved rows: {dict(status)}")
    check(status["ok"] > 0, "no ok row")
    worst = 0.0
    for r in res:
        ref = _exact_answer(reqs[rids.index(r.rid)])
        for f in ("p", "f", "energy", "t_total"):
            worst = max(worst, max_rel(getattr(r, f), ref[f]))
        check(r.feasible == ref["feasible"], f"rid {r.rid}: feasible flag "
              "differs from equilibrium")
    check(worst <= REL, f"service vs equilibrium max rel {worst:.3e}")
    return (f"buckets={svc.buckets} B={svc.batch_width} requests="
            f"{n_requests} N in {min(sizes)}..{max(sizes)}",
            f"warmup {warm_s:.3f} s, statuses {dict(status)}, "
            f"{svc.stats['dispatches']} dispatches, 0 dispatch failures, "
            f"every row vs equilibrium max rel {worst:.3e} <= {REL}")


def phase_mechanism(m: int = 20, k_draws: int = 4, steps: int = 2):
    import jax
    import numpy as np
    from repro.core.mechanism import (MechanismStatics, init_params,
                                      mechanism_step, synthetic_context)
    from repro.core.stackelberg import TRACE_COUNTS
    from repro.optim.adamw import init_opt_state
    statics = MechanismStatics()
    ctx = synthetic_context(jax.random.PRNGKey(SEED), m=m, k_draws=k_draws)
    params = init_params(m)
    opt = init_opt_state(params, statics.adamw)
    before = TRACE_COUNTS["mechanism_step"]
    objs = []
    for _ in range(steps):
        params, opt, obj, grads = mechanism_step(params, opt, ctx, statics)
        check(bool(np.isfinite(np.asarray(obj))), "non-finite objective")
        check(all_finite(grads), "non-finite gradient")
        objs.append(float(obj))
    traces = TRACE_COUNTS["mechanism_step"] - before
    check(traces == 1, f"mechanism_step traced {traces}x")
    return (f"M={m} K={k_draws} steps={steps}",
            f"1 trace, objective {objs}, gradients finite")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------
def _pinned_to_one_chip(fn):
    """Run ``fn`` with the game mesh clamped to one device."""
    from repro.sharding import game_mesh
    old = os.environ.get("REPRO_MESH_DEVICES")
    os.environ["REPRO_MESH_DEVICES"] = "1"
    try:
        return fn()
    finally:
        if old is None:
            del os.environ["REPRO_MESH_DEVICES"]
        else:
            os.environ["REPRO_MESH_DEVICES"] = old
        game_mesh.clear_cache()


def _devices_spanned(x) -> int:
    return len(x.sharding.device_set)


def phase_sweep_equilibrium_4(c: int = 10, k: int = 256, n: int = 5,
                              devices: int = 4):
    import jax
    import jax.numpy as jnp
    from repro.core.channel import sample_sic_channel_batch
    from repro.core.stackelberg import GameConfig, sweep_equilibrium
    from repro.sharding import game_mesh
    cfgs = [dataclasses.replace(GameConfig(), t_max=tm, model_bits=mb)
            for mb in (0.5e6, 2.0e6) for tm in (4.0, 6.0, 8.0, 10.0, 12.0)]
    cfgs = (cfgs * -(-c // len(cfgs)))[:c]
    h2 = sample_sic_channel_batch(jax.random.PRNGKey(SEED + 77), k, n)
    d, vm = jnp.full((n,), 200.0), jnp.full((n,), 0.5)
    grid = game_mesh.grid_layout(c, k)
    check(grid[0] * grid[1] == devices, f"grid layout {grid} does not "
          f"cover {devices} devices")
    got = jax.block_until_ready(sweep_equilibrium(cfgs, h2, d, vm))
    spanned = _devices_spanned(got.energy)
    check(spanned == devices, f"sharded output spans {spanned} devices")
    ref = _pinned_to_one_chip(
        lambda: jax.block_until_ready(sweep_equilibrium(cfgs, h2, d, vm)))
    check(_devices_spanned(ref.energy) == 1, "pinned run spans >1 device")
    errs = {f: max_rel(getattr(got, f), getattr(ref, f))
            for f in ("energy", "t_total", "p")}
    check(max(errs.values()) <= REL, f"4-chip vs 1-chip: {errs} > {REL}")
    return (f"C={c} K={k} N={n} mesh (cfg, draw)={grid}",
            f"output on {spanned} devices; vs 1 chip max rel "
            + ", ".join(f"{f} {e:.3e}" for f, e in errs.items())
            + f" <= {REL}")


def phase_sweep_training_4(c: int = 6, s: int = 4, rounds: int = 20,
                           devices: int = 4):
    import jax
    from benchmarks.common import fl_bench_config, fl_setup
    from repro.core.fl_round import stack_states, sweep_training
    from repro.core.stackelberg import GameConfig
    from repro.sharding import game_mesh
    cells = [fl_setup(SEED + i, "mnist", poison_ratio=0.3) for i in range(s)]
    states = stack_states([st for st, _, _ in cells])
    _, data, logits_fn = cells[0]
    fls = [dataclasses.replace(fl_bench_config(), lr=lr, epsilon=eps)
           for lr, eps in ((0.1, 0.0), (0.08, 0.1), (0.12, 0.2),
                           (0.1, 0.3), (0.06, 0.0), (0.1, 0.45))][:c]
    games = [dataclasses.replace(GameConfig(), t_max=t)
             for t in (8.0, 9.0, 10.0, 11.0, 12.0, 10.5)][:c]
    grid = game_mesh.grid_layout(c, s)
    check(grid[0] * grid[1] == devices, f"grid layout {grid} does not "
          f"cover {devices} devices")
    run = lambda: jax.block_until_ready(
        sweep_training(states, data, fls, games, logits_fn, rounds))
    _, got = run()
    spanned = _devices_spanned(got["val_acc"])
    check(spanned == devices, f"sharded output spans {spanned} devices")
    _, ref = _pinned_to_one_chip(run)
    check(_devices_spanned(ref["val_acc"]) == 1, "pinned run spans >1 device")
    check(all_finite(got), "non-finite sharded training metrics")
    errs = {f: max_rel(got[f], ref[f])
            for f in ("val_acc", "energy", "latency")}
    check(max(errs.values()) <= REL, f"4-chip vs 1-chip: {errs} > {REL}")
    return (f"C={c} S={s} R={rounds} mesh (cfg, draw)={grid}",
            f"output on {spanned} devices; vs 1 chip max rel "
            + ", ".join(f"{f} {e:.3e}" for f, e in errs.items())
            + f" <= {REL}")


# ---------------------------------------------------------------------------
# phases, in order, and the last line
# ---------------------------------------------------------------------------
def run_phase(name: str, fn) -> bool:
    t0 = time.perf_counter()
    try:
        shapes, result = fn()
    except Exception as e:  # noqa: BLE001 — every phase reports, then exit 1
        traceback.print_exc()
        print(f"[{name}] FAIL after {time.perf_counter() - t0:.3f} s: "
              f"{type(e).__name__}: {e}", flush=True)
        return False
    print(f"[{name}] {shapes} | {time.perf_counter() - t0:.3f} s | "
          f"{result} | PASS", flush=True)
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded sweeps and their "
                         "one-chip references")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    from repro.launch.runtime import enable_compile_cache
    cache = enable_compile_cache()

    t0 = time.perf_counter()
    devs = jax.devices()
    dev = devs[0]
    want = 4 if args.four_chips else 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu" or len(devs) < want:
        print(f"[device] FAIL: need {want} TPU device(s), found "
              f"{len(devs)} x {dev.platform} ({dev.device_kind})", flush=True)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x {len(devs)} | "
          f"{time.perf_counter() - t0:.3f} s | compile cache {cache} | PASS",
          flush=True)

    if args.four_chips:
        phases = [("sweep_equilibrium_4", phase_sweep_equilibrium_4),
                  ("sweep_training_4", phase_sweep_training_4)]
    else:
        phases = [("mc_equilibrium", phase_mc_equilibrium),
                  ("large_n_sic", phase_large_n_sic),
                  ("fl_trajectory", phase_fl_trajectory),
                  ("alloc_service", phase_alloc_service),
                  ("mechanism", phase_mechanism)]
    ok = [run_phase(name, fn) for name, fn in phases]
    if not all(ok):
        print(f"chip_smoke: {ok.count(False)} phase(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
