"""End-to-end LM training driver: ~100M-parameter model, few hundred steps.

Uses the full production loop (launch/train.py): deterministic token
pipeline, prefetching, watchdog, atomic checkpoints with resume.

    PYTHONPATH=src python examples/lm_train.py [--steps 300]
"""
import argparse
import dataclasses

import jax.numpy as jnp

from repro.configs import get as get_config
from repro.launch.train import TrainConfig, make_model_and_step, run
import repro.launch.train as train_mod
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--ckpt", type=str, default="/tmp/repro_lm_ckpt")
args = ap.parse_args()

# ~100M llama-style config: 8 layers, d=512, derived from llama3.2-3b
base = get_config("llama3.2-3b")
cfg100m = dataclasses.replace(
    base, num_layers=8, d_model=512, num_heads=8, num_kv_heads=4,
    d_ff=1536, vocab_size=32_000, vocab_pad_multiple=128,
    dtype=jnp.float32, remat=False, head_dim=64)
print(f"model: {cfg100m.num_params() / 1e6:.1f}M params")

# monkey-wire the reduced config through the launcher
_orig = train_mod.make_model_and_step


def patched(tc):
    from repro.models import build
    from repro.optim import Adam, schedules
    import jax
    lm = build(cfg100m)
    opt = Adam(learning_rate=schedules.warmup_cosine(
        tc.lr, tc.warmup, tc.steps), clip_global_norm=1.0)
    step, _ = lm.make_train_step(opt)
    return cfg100m, lm, opt, jax.jit(step)


train_mod.make_model_and_step = patched
tc = TrainConfig(arch="llama3.2-3b", smoke=False, steps=args.steps,
                 global_batch=8, seq_len=256, lr=3e-4, warmup=30,
                 ckpt_dir=args.ckpt, ckpt_every=100, log_every=10)
out = run(tc)
losses = [l for _, l in out["losses"]]
print(f"\nloss: {losses[0]:.3f} -> {losses[-1]:.3f} over "
      f"{args.steps} steps; {len(out['breaches'])} watchdog breaches")
