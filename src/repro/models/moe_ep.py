"""Expert-parallel MoE dispatch via shard_map + all-to-all (beyond-paper
§Perf path).

The baseline (`moe.py`) dispatches with gather/scatter under plain SPMD,
leaving XLA to reshard the [E, C, ·] buffers — which it does with all-gathers
sized by the whole dispatch buffer.  This path makes the communication
pattern explicit and minimal, the GShard/DeepSpeed-MoE way:

  * tokens are sharded over EVERY mesh axis (data × model jointly) for the
    MoE block — each device routes only its local tokens;
  * each model column owns E/TP experts; one ``all_to_all`` over the model
    axis sends each device's per-expert slots to the owning column, one
    reverse ``all_to_all`` brings the outputs back;
  * combine is local (scatter-add into the local token block).

Requires E % TP == 0 (olmoe: 64/16 ✓).  Archs with fewer experts than the
TP width (grok: 8) keep the baseline expert-TP path.

Validated against the baseline dispatch in tests/test_moe_ep.py on a host
mesh (outputs match exactly when capacity admits every routed token).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import ModelConfig


def _local_dispatch(xt, p_router, cfg: ModelConfig, cap: int):
    """Route T_loc local tokens; returns (idx [E,C], gates [E,C], aux)."""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = (xt @ p_router.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, _ = jax.lax.top_k(probs, k)
    is_topk = probs >= gate_k[:, -1:]
    gates = jnp.where(is_topk, probs, 0.0)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-9)
    frac = jnp.mean(is_topk.astype(jnp.float32), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    score_et = jnp.where(is_topk, probs, -1.0).T          # [E, T_loc]
    top_scores, idx = jax.lax.top_k(score_et, cap)        # [E, C]
    valid = (top_scores > 0.0).astype(jnp.float32)
    gsel = jnp.take_along_axis(gates.T, idx, axis=1) * valid
    return idx, gsel, aux


def moe_forward_ep(p, x, cfg: ModelConfig, mesh: Mesh):
    """Expert-parallel forward. x: [B,S,D] -> (y, aux). Requires a mesh with
    a "model" axis dividing num_experts."""
    b, s, d = x.shape
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    t = b * s
    assert t % n_shards == 0, (t, n_shards)
    tp = mesh.shape["model"]
    e = cfg.num_experts
    assert e % tp == 0, (e, tp)
    t_loc = t // n_shards
    cap = max(1, min(t_loc, int(cfg.num_experts_per_tok * t_loc
                                * cfg.capacity_factor) // e))
    dt = cfg.compute_dtype

    def body(xt, router, w_in, w_out, w_gate):
        # xt: [T_loc, d]; w_*: [E_loc, ...] (expert shards of this column)
        idx, gsel, aux = _local_dispatch(xt, router, cfg, cap)
        xe = jnp.take(xt, idx.reshape(-1), axis=0).reshape(e, cap, d)
        # send each expert's slots to the owning model column
        xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)                # [E/TP, TP*C, d]
        if w_gate is not None:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt))) \
                * jnp.einsum("ecd,edf->ecf", xe, w_in.astype(dt))
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w_in.astype(dt)))
        ye = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))
        # bring outputs back to the token-owning devices
        ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                tiled=True)                # [E, C, d]
        ye = ye * gsel[..., None].astype(dt)
        out = jnp.zeros((t_loc, d), dt).at[idx.reshape(-1)].add(
            ye.reshape(e * cap, d), mode="drop")
        # aux is a per-shard mean over local tokens → average across shards
        aux = jax.lax.pmean(aux, "data") if "data" in mesh.shape else aux
        aux = jax.lax.pmean(aux, "model")
        if "pod" in mesh.shape:
            aux = jax.lax.pmean(aux, "pod")
        return out, aux

    tok_spec = P(axes, None)
    has_gate = "w_gate" in p
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P("model", None, None),
                  P("model", None, None),
                  P("model", None, None) if has_gate else None),
        out_specs=(tok_spec, P()),
        check_vma=False)
    xt = x.reshape(t, d)
    out, aux = fn(xt, p["router"], p["w_in"], p["w_out"],
                  p.get("w_gate"))
    return out.reshape(b, s, d), aux
