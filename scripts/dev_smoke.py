"""Dev smoke: tiny variants of each family, forward + loss + decode."""
import jax, jax.numpy as jnp
from repro.launch.runtime import cpu_child_env, enable_compile_cache
from repro.models import (ATTN, CROSS, MAMBA, MOE, SHARED_ATTN, BlockSpec,
                          ModelConfig, decode_step, init_caches, init_params,
                          loss_fn, prefill)

enable_compile_cache()

def run(name, cfg, batch):
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    loss, m = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
    assert jnp.isfinite(loss), (name, loss)
    # decode one token
    caches = init_caches(cfg, batch["tokens"].shape[0], 64)
    tok = batch["tokens"][:, :1]
    logits, caches = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))(params, tok, caches)
    assert logits.shape == (batch["tokens"].shape[0], cfg.padded_vocab_size)
    assert jnp.all(jnp.isfinite(logits)), name
    print(f"{name}: loss={float(loss):.4f} decode ok")

B, S, V = 2, 32, 128
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
base = dict(tokens=toks, targets=toks)

# dense w/ alternating local/global + softcap (gemma-like)
cfg = ModelConfig(name="tiny-dense", family="dense", num_layers=4, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=V,
                  pattern=(BlockSpec(ATTN, 8), BlockSpec(ATTN, 0)),
                  attn_softcap=50.0, logit_softcap=30.0)
run("dense", cfg, base)

# moe
cfg = ModelConfig(name="tiny-moe", family="moe", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=4, head_dim=16, d_ff=64, vocab_size=V,
                  pattern=(BlockSpec(MOE, 0),), num_experts=4, num_experts_per_tok=2)
run("moe", cfg, base)

# ssm
cfg = ModelConfig(name="tiny-ssm", family="ssm", num_layers=2, d_model=64,
                  num_heads=1, num_kv_heads=1, head_dim=16, d_ff=0, vocab_size=V,
                  pattern=(BlockSpec(MAMBA),), ssm_state=16, ssm_head_dim=16,
                  ssm_chunk=8)
run("ssm", cfg, base)

# hybrid (zamba2-like: 3 mamba + shared attn)
cfg = ModelConfig(name="tiny-hybrid", family="hybrid", num_layers=4, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=V,
                  pattern=(BlockSpec(MAMBA), BlockSpec(SHARED_ATTN, 0)),
                  ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
run("hybrid", cfg, base)

# audio enc-dec
cfg = ModelConfig(name="tiny-audio", family="audio", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=V,
                  pattern=(BlockSpec(CROSS, 0),), encoder_layers=2, encoder_ratio=4)
frames = jax.random.normal(jax.random.PRNGKey(2), (B, S // 4, 64))
run("audio", cfg, dict(base, frames=frames))

# vlm
P = 8
cfg = ModelConfig(name="tiny-vlm", family="vlm", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=V,
                  pattern=(BlockSpec(ATTN, 0),), num_patch_tokens=P)
patches = jax.random.normal(jax.random.PRNGKey(3), (B, P, 64))
run("vlm", cfg, dict(tokens=toks[:, :S - P], targets=toks[:, :S - P], patches=patches))

print("ALL FAMILIES OK")

# batched Stackelberg equilibrium engine (core FL hot path): K realizations
# in one vmapped XLA call — exercises the jit/vmap throughput path in smoke
import dataclasses
from repro.core.channel import sample_sic_channel_batch
from repro.core.fl_round import allocate_batched
from repro.core.stackelberg import (GameConfig, TRACE_COUNTS,
                                    batched_equilibrium, sweep_equilibrium)

K, N = 8, 5
h2b = sample_sic_channel_batch(jax.random.PRNGKey(7), K, N)
alloc = batched_equilibrium(GameConfig(), h2b, jnp.full((N,), 200.0),
                            jnp.full((N,), 0.5))
assert alloc.energy.shape == (K,) and bool(jnp.all(jnp.isfinite(alloc.energy)))
assert bool(jnp.all(jnp.isfinite(alloc.t_total)))
print(f"batched equilibrium OK: K={K} mean_energy={float(alloc.energy.mean()):.4f}")

# sweep engine: a 4-point config grid × K draws in one dispatch, one trace
cfgs = [dataclasses.replace(GameConfig(), t_max=t) for t in (6., 8., 10., 12.)]
before = TRACE_COUNTS["sweep_equilibrium"]
sw = sweep_equilibrium(cfgs, h2b, jnp.full((N,), 200.0), jnp.full((N,), 0.5))
assert sw.energy.shape == (len(cfgs), K)
assert TRACE_COUNTS["sweep_equilibrium"] - before == 1, "sweep retraced"
print(f"sweep equilibrium OK: {len(cfgs)} configs x K={K}, 1 trace")

# large-N blocked SIC engine: N=128 Jacobi fixed-point sweeps must land on
# the same equilibrium as the sequential reverse-scan chain (ISSUE 5)
h2_128 = sample_sic_channel_batch(jax.random.PRNGKey(21), 4, 128)
d_128, vm_128 = jnp.full((128,), 200.0), jnp.full((128,), 0.5)
a_seq = batched_equilibrium(GameConfig(), h2_128, d_128, vm_128)
a_blk = batched_equilibrium(GameConfig(sic_mode="blocked"), h2_128, d_128,
                            vm_128)
_rel = lambda a, b: float(jnp.max(jnp.abs(a - b) /
                                  jnp.maximum(jnp.abs(b), 1e-12)))
# equilibrium-LEVEL bound is 1e-3, not the solver-level 1e-5: the Alg-2
# energy-change stopping rule can pick a different valid best-iterate from
# ~1e-7 solver residue on infeasible draws (see equilibrium_throughput.py)
assert _rel(a_blk.energy, a_seq.energy) < 1e-3, "blocked energy drift"
assert _rel(a_blk.p, a_seq.p) < 1e-3, "blocked power drift"
print(f"blocked SIC OK: N=128 K=4, energy rel={_rel(a_blk.energy, a_seq.energy):.2e}")

# every scheme has a batched Monte-Carlo path now
for scheme in ("proposed", "wo_dt", "oma", "oma_tdma", "random"):
    a = allocate_batched(scheme, GameConfig(), h2b, jnp.full((N,), 200.0),
                         jnp.full((N,), 0.5), key=jax.random.PRNGKey(1))
    assert a.energy.shape == (K,) and bool(jnp.all(jnp.isfinite(a.energy))), scheme
print("allocate_batched OK for all schemes")

# scan-compiled FL trajectory: R rounds in one lax.scan dispatch, round
# body traced exactly once, stacked-metrics history
from repro.core.channel import sample_positions
from repro.core.digital_twin import DTConfig, sample_v_max
from repro.core.fl_round import FLConfig, FLState, run_training_scan
from repro.core.reputation import init_reputation
from repro.data.federated import make_federated_data
from repro.data.synthetic import SYNTHETIC_MNIST
from repro.models.classifier import make_classifier

_ks = jax.random.split(jax.random.PRNGKey(11), 6)
_data = make_federated_data(_ks[0], SYNTHETIC_MNIST, m=10, cap=32)
_params, _logits_fn = make_classifier("mlp", _ks[1], in_dim=784, hidden=16)
_state = FLState(params=_params, rep=init_reputation(10),
                 v_max=sample_v_max(_ks[2], 10, DTConfig()),
                 distances=sample_positions(_ks[3], 10), key=_ks[4])
_before = TRACE_COUNTS["run_round"]
_fin, _hist = run_training_scan(_state, _data,
                                FLConfig(n_selected=3, local_steps=4,
                                         server_steps=4, lr=0.1),
                                GameConfig(), _logits_fn, rounds=3)
assert _hist["val_acc"].shape == (3,)
assert bool(jnp.all(jnp.isfinite(_hist["val_acc"])))
assert TRACE_COUNTS["run_round"] - _before == 1, "scan retraced run_round"
print(f"run_training_scan OK: R=3, 1 trace, "
      f"val_acc={float(_hist['val_acc'][-1]):.3f}")

# config-axis training sweep: C=2 configs (ε/lr/t_max vary) × S=2 seeds ×
# R=2 rounds in ONE dispatch — the Fig. 5/6/7/8 grid workload; the round
# body must trace exactly once for the whole grid
from repro.core.fl_round import stack_states, sweep_training

_state_b = dataclasses.replace(_state, key=jax.random.PRNGKey(99))
_states = stack_states([_state, _state_b])
_fls = [FLConfig(n_selected=3, local_steps=4, server_steps=4, lr=lr,
                 epsilon=eps) for lr, eps in ((0.1, 0.0), (0.08, 0.3))]
_games = [dataclasses.replace(GameConfig(), t_max=t) for t in (9.0, 11.0)]
_before = TRACE_COUNTS["run_round"]
_fin_g, _grid = sweep_training(_states, _data, _fls, _games, _logits_fn,
                               rounds=2)
assert _grid["val_acc"].shape == (2, 2, 2)
assert bool(jnp.all(jnp.isfinite(_grid["val_acc"])))
assert TRACE_COUNTS["run_round"] - _before == 1, "sweep retraced run_round"
print(f"sweep_training OK: C=2 x S=2 x R=2, 1 trace, "
      f"val_acc={float(_grid['val_acc'][0, 0, -1]):.3f}")

# ragged-N streaming allocation service: 4 mixed-N requests spanning two
# buckets — padded solves finite, results restored to request order, and
# EXACTLY one trace per touched bucket executable (ISSUE 6 smoke)
import numpy as np
from repro.launch.alloc_serve import AllocationService, AllocRequest

_svc = AllocationService(buckets=(8, 16), max_batch=2)
_before = TRACE_COUNTS["serve_allocation"]
_rng = np.random.default_rng(5)
_ns = (3, 7, 12, 5)                        # buckets: 8, 8, 16, 8
for _n in _ns:
    _svc.submit(AllocRequest(h2=_rng.uniform(0.2, 2.0, _n), d=200.0,
                             v_max=0.5, epsilon=0.05))
_res = sorted(_svc.drain(), key=lambda r: r.rid)
assert [r.n for r in _res] == list(_ns)
assert [r.bucket for r in _res] == [8, 8, 16, 8]
assert all(np.isfinite(r.energy) and np.all(np.isfinite(r.p)) for r in _res)
_touched = len({(r.bucket) for r in _res})
assert TRACE_COUNTS["serve_allocation"] - _before == _touched, \
    "alloc-serve traced more than once per bucket"
print(f"alloc serve OK: {len(_res)} mixed-N requests, "
      f"{_touched} buckets, 1 trace each")

# SLA-resilience smoke (ISSUE 9): a 30-request burst with ONE injected
# dispatch stall into a bounded-queue SLA service — the exactly-once
# invariant must hold (every submitted rid drains exactly once, with a
# status from the contract vocabulary, zero lost)
from repro.launch.serve_chaos import (ChaosScenario, assert_exactly_once,
                                      run_chaos)

_burst = ChaosScenario(name="smoke_burst_stall", n_requests=30,
                       stall_dispatches=(1,), stall_s=0.2,
                       hi_priority_frac=0.25,
                       service_kwargs={"max_queue": 16, "max_batch": 4,
                                       "buckets": (8,)})
_rep = run_chaos(_burst)
assert_exactly_once(_rep)
assert _rep.submitted == 30 and len(_rep.results) == 30
assert _rep.injection["injected_stalls"] == 1
print(f"serve resilience OK: 30-request burst + 1 stall, 0 lost, "
      f"statuses={_rep.status_counts}")

# fault-injection engine: a tiny attack-vs-defense grid — 2 scenarios
# (clean-gates vs adaptive attacker + straggler storm) × S=2 seeds in ONE
# sweep dispatch, zero mid-grid retraces (ISSUE 7 smoke).  Every fault
# knob is a traced operand: the two scenarios share the executable.
from repro.core.faults import FaultConfig

_scenarios = [FaultConfig(),                   # legacy static attacker
              FaultConfig(rep_gate=0.85, p_outage=0.2, p_slow=0.3,
                          compute_slowdown=2.0, channel_fade=0.5)]
_fls_f = [FLConfig(n_selected=3, local_steps=4, server_steps=4, lr=0.1)] * 2
_before = TRACE_COUNTS["run_round"]
_fin_f, _fgrid = sweep_training(_states, _data, _fls_f, GameConfig(),
                                _logits_fn, rounds=2, faults=_scenarios)
assert _fgrid["val_acc"].shape == (2, 2, 2)
assert bool(jnp.all(jnp.isfinite(_fgrid["val_acc"])))
assert _fgrid["n_dropped"].shape == (2, 2, 2)
assert TRACE_COUNTS["run_round"] - _before == 1, "fault grid retraced"
print(f"fault grid OK: 2 scenarios x S=2 x R=2, 1 trace, "
      f"dropped={int(jnp.sum(_fgrid['n_dropped']))}")

# multi-device smoke (ISSUE 8): 4 forced host devices, a C=3 × K=5 sweep
# on the 2D (cfg, draw) mesh — non-divisible axes pad + slice back, the
# grid still traces exactly ONCE, and cells match per-instance solves.
# Subprocess: the XLA device count is fixed at jax import, and the child is
# a CPU run (cpu_child_env refuses while this process holds a chip).
import os, pathlib, subprocess, sys
_root = pathlib.Path(__file__).resolve().parents[1]
_MD_SMOKE = r"""
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.core.channel import sample_sic_channel_batch
from repro.core.stackelberg import (GameConfig, TRACE_COUNTS, equilibrium,
                                    sweep_equilibrium)
assert len(jax.devices()) == 4, jax.devices()
h2 = sample_sic_channel_batch(jax.random.PRNGKey(7), 5, 5)
d = jnp.full((5,), 200.0); vm = jnp.full((5,), 0.5)
cfgs = [dataclasses.replace(GameConfig(), t_max=t) for t in (6., 9., 12.)]
before = TRACE_COUNTS["sweep_equilibrium"]
sw = sweep_equilibrium(cfgs, h2, d, vm)
assert TRACE_COUNTS["sweep_equilibrium"] - before == 1, "sweep retraced"
en = np.asarray(jax.device_get(sw.energy))
assert en.shape == (3, 5), en.shape
ref = float(equilibrium(cfgs[1], h2[2], d, vm).energy)
rel = abs(float(en[1, 2]) - ref) / max(abs(ref), 1e-12)
assert rel <= 1e-5, rel
print("MULTIDEVICE_SMOKE_OK")
"""
_env = cpu_child_env(4)
_env["PYTHONPATH"] = (str(_root / "src") + os.pathsep +
                      _env.get("PYTHONPATH", ""))
_proc = subprocess.run([sys.executable, "-c", _MD_SMOKE], env=_env,
                       capture_output=True, text=True, timeout=420)
assert _proc.returncode == 0, _proc.stderr[-2000:]
assert "MULTIDEVICE_SMOKE_OK" in _proc.stdout
print("multi-device sweep OK: 4 forced devices, C=3 x K=5, 1 trace")

# mechanism tuning smoke (ISSUE 10): 2 AdamW steps END-TO-END through the
# solved Stackelberg equilibria (IFT custom_vjp) — every gradient leaf
# finite, objective finite, and both steps share ONE executable
from repro.core.mechanism import (MechanismStatics, init_params,
                                  mechanism_step, synthetic_context)
from repro.optim.adamw import init_opt_state

_mctx = synthetic_context(jax.random.PRNGKey(0), m=12, k_draws=2)
_mp = init_params(12)
_mopt = init_opt_state(_mp, MechanismStatics().adamw)
_before = TRACE_COUNTS["mechanism_step"]
for _ in range(2):
    _mp, _mopt, _mj, _mg = mechanism_step(_mp, _mopt, _mctx,
                                          MechanismStatics())
    assert bool(jnp.isfinite(_mj)), "mechanism objective not finite"
    assert all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree_util.tree_leaves(_mg)), \
        "NaN gradient through the IFT custom_vjp"
assert TRACE_COUNTS["mechanism_step"] - _before == 1, "mechanism retraced"
print(f"mechanism tuning OK: 2 grad-through-the-game steps, 1 trace, "
      f"J={float(_mj):.4f}")

# benchmark regression gate (no-op when BENCH json / git baseline is absent)
subprocess.run([sys.executable, str(_root / "scripts" / "check_bench.py")],
               check=True)
