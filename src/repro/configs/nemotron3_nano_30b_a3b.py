"""NVIDIA-Nemotron-3-Nano-30B-A3B [hybrid: Mamba2 + routed MoE + GQA],
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (config.json).

52 layers by ``hybrid_override_pattern`` (M = Mamba2, E = MoE, * = attention),
hidden 2688; Mamba2 64 heads x 64 with 8 groups, state 128, conv 4, chunk
128 (d_inner = heads x head_dim = 4096, the model code's convention; expand
x hidden would give 5376); GQA 32 query / 2 KV heads of 128; 128 routed
relu² experts of width 1856, top-6 by sigmoid score plus correction bias,
normalised, x 2.5, one shared expert of width 3712; vocabulary 131,072,
untied; RMSNorm eps 1e-5.
"""
from repro.models.nemotron_h import HybridConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def config() -> HybridConfig:
    return HybridConfig(
        name="nemotron-3-nano-30b-a3b",
        pattern=PATTERN,
        d_model=2688,
        vocab_size=131072,
        norm_eps=1e-5,
        mamba_heads=64, mamba_head_dim=64, n_groups=8, ssm_state=128,
        conv_kernel=4, chunk=128,
        num_heads=32, num_kv_heads=2, head_dim=128,
        n_experts=128, top_k=6, expert_ff=1856, shared_ff=3712,
        routed_scale=2.5, norm_topk=True,
    )
