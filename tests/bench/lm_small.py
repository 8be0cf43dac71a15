"""The language-model FL cell at a size a CPU test run holds: one layer of
each kind (``ME*``) at d_model 64, Mamba2 4 heads x 16 in 2 groups with
state 16 and chunk 8, GQA 4 query / 2 KV heads of 16, 16 routed experts of
width 32 (top 2, experts 0-7 held), a shared expert of width 64, a
vocabulary slice of 64, sequences of 32; M = 6 clients, N = 3 selected, 8
to 16 sequences each, 2 poisoners, the cell's 4 local and 4 twin steps;
float32 matmuls."""
from bench import run

CELL = "nemotron3_nano_fl_s4096"
MODEL = {"hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16,
         "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "n_routed_experts": 8, "num_experts_per_tok": 2,
         "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 64, "vocab_size": 64,
         "compute_dtype": "float32", "hybrid_override_pattern": "ME*"}
CONFIG = {"clients_total": 6, "clients_per_round": 3,
          "data_samples": [8.0, 16.0]}


def small(spec: dict | None = None):
    """(config, traffic) of the small cell, from ``spec``, the cell's own
    resolved spec by default."""
    spec = spec or run.resolve(CELL)
    config = dict(spec["config"], **MODEL, **CONFIG)
    config["held"] = dict(config["held"], router_experts=16)
    traffic = dict(spec["traffic"])
    traffic["data"] = dict(traffic["data"], seq_len=32, poison_ratio=0.34)
    return config, traffic


def small_resolve(real_resolve):
    """``run.resolve`` with the language-model cell at the small size."""
    def resolve(workload, root=run.ROOT):
        spec = real_resolve(workload, root)
        if workload == CELL:
            spec["config"], spec["traffic"] = small(spec=spec)
        return spec
    return resolve
