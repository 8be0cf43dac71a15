"""Batched serving example: run prefix-primed batched decoding with a KV
cache on a small gemma2-family model (sliding-window + global layers,
softcaps — the real serving code path).

    PYTHONPATH=src python examples/serve_batched.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.runtime import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.launch.serve import generate
from repro.models import forward_logits, init_params

cfg = smoke_variant(get_config("gemma2-9b"))
key = jax.random.PRNGKey(0)
params = init_params(cfg, key)

B, P, G = 4, 12, 24
prompt = jax.random.randint(jax.random.fold_in(key, 1), (B, P), 0,
                            cfg.vocab_size)
t0 = time.time()
toks = generate(cfg, params, prompt, max_seq=P + G + 1, gen=G)
dt = time.time() - t0
print(f"batch={B} prompt={P} generated={G} in {dt:.1f}s "
      f"({B*G/dt:.1f} tok/s on CPU)")

# consistency check: decode path must agree with the full forward pass
logits_full, _ = forward_logits(params, {"tokens": toks[:, :-1]}, cfg)
greedy_full = jnp.argmax(logits_full[:, P - 1:, :], axis=-1)
match = bool(jnp.all(greedy_full[:, 0] == toks[:, P]))
print(f"first generated token matches full-forward greedy: {match}")
assert match, "decode/forward divergence"
print("sample tokens:", toks[0].tolist())
