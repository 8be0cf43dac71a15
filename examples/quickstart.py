"""Quickstart: one DT-assisted FL round, end to end, narrated.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.runtime import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax
import jax.numpy as jnp

from repro.core import (FLConfig, FLState, GameConfig, equilibrium,
                        init_reputation, run_round, select_clients)
from repro.core.channel import sample_positions, sample_round_channels
from repro.core.digital_twin import DTConfig, sample_v_max
from repro.data.federated import make_federated_data
from repro.data.synthetic import SYNTHETIC_MNIST
from repro.models.classifier import make_classifier

key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 6)
M, N = 20, 5

print("=== DT-assisted FL over NOMA: one round ===")
data = make_federated_data(ks[0], SYNTHETIC_MNIST, m=M, cap=128,
                           poison_ratio=0.3)
print(f"{M} clients, data sizes {data.sizes.astype(int).tolist()}")
print(f"poisoned clients: {jnp.where(data.poisoned)[0].tolist()}")

# 1. reputation-based selection (paper §III)
rep = init_reputation(M)
sel, z = select_clients(rep, data.sizes, N)
print(f"\n[1] selected by reputation: {sel.tolist()}")
print(f"    reputation scores: {[round(float(z[i]), 3) for i in sel]}")

# 2. channel realization + SIC order (paper §II-C)
dist = sample_positions(ks[1], M)
h2 = sample_round_channels(ks[2], dist)[sel]
order = jnp.argsort(-h2)
print(f"\n[2] SIC decode order (desc |h|²): {sel[order].tolist()}")

# 3. Stackelberg equilibrium (paper §IV–V)
game = GameConfig()
vmax = sample_v_max(ks[3], M, DTConfig())
alloc = equilibrium(game, h2[order], data.sizes[sel[order]], vmax[sel[order]])
print(f"\n[3] Stackelberg allocation (leader=clients, follower=server):")
print(f"    v* (DT mapping ratios) = {[round(float(x),2) for x in alloc.v]}")
print(f"    f* (GHz)               = {[round(float(x)/1e9,2) for x in alloc.f]}")
print(f"    p* (W)                 = {[round(float(x),3) for x in alloc.p]}")
print(f"    alpha* (server shares) = {[round(float(x),4) for x in alloc.alpha]}")
print(f"    round latency T = {float(alloc.t_total):.2f}s  "
      f"energy E = {float(alloc.energy):.3f}J")

# 4. full round through the orchestrator (train, RONI, aggregate)
params, logits_fn = make_classifier("mlp", ks[4], in_dim=784, hidden=64)
state = FLState(params=params, rep=rep, v_max=vmax, distances=dist, key=ks[5])
state, metrics = run_round(state, data, FLConfig(), game, logits_fn)
print(f"\n[4] round metrics: " + ", ".join(
    f"{k}={v}" for k, v in metrics.items() if not hasattr(v, 'shape')))
print("\nOK — see examples/federated_poisoning.py for multi-round training.")
