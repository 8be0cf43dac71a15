"""Model FLOPs of the cut Nemotron-H client model, per token, from its
configuration's shapes (``bench/configs/nemotron3_nano_fl_ep16.json``).

Per token of the forward pass: 2 per weight of every matmul the token goes
through; the held experts' weights times the expected number of
assignments a token makes to them, top-k x held / router width (the
router's choice is over every expert); the SSD recurrence's own work, a
state update and a readout of h x p x n multiply-adds each (4·h·p·n); in
attention, the scores and the weighted sum over the causal prefix, (S + 1)/2
keys on average (4·heads·head_dim·(S + 1)/2).  The embedding is a lookup,
norms and elementwise work are not counted.  A trained token costs three
forward passes (the backward pass two); recomputed activations are not
work.
"""
from __future__ import annotations


def forward_per_token(mc: dict, seq_len: int) -> float:
    d = mc["hidden_size"]
    h, p = mc["mamba_num_heads"], mc["mamba_head_dim"]
    g, n = mc["n_groups"], mc["ssm_state_size"]
    din = h * p
    mamba = 2 * (d * (2 * din + 2 * g * n + h) + din * d) + 4 * h * p * n
    router = mc["held"]["router_experts"]
    assignments = mc["num_experts_per_tok"] * mc["n_routed_experts"] / router
    moe = 2 * (d * router
               + assignments * 2 * d * mc["moe_intermediate_size"]
               + 2 * d * mc["moe_shared_expert_intermediate_size"])
    hq, hk, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                  mc["head_dim"])
    attn = (2 * d * hd * (2 * hq + 2 * hk)
            + 4 * hq * hd * (seq_len + 1) / 2)
    per = {"M": mamba, "E": moe, "*": attn}
    head = 2 * d * mc["vocab_size"]
    return float(sum(per[k] for k in mc["hybrid_override_pattern"]) + head)


def round_flops(mc: dict, seq_len: int, trained_tokens: float,
                evaluated_tokens: float) -> float:
    """FLOPs of the tokens one FL round trains (3 forward passes each) and
    evaluates (one)."""
    f = forward_per_token(mc, seq_len)
    return 3.0 * f * trained_tokens + f * evaluated_tokens
