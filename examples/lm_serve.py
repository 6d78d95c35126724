"""Batched serving with continuous batching + KV cache.

    PYTHONPATH=src python examples/lm_serve.py [--arch recurrentgemma-2b]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, smoke
from repro.launch.serve import Request, ServeEngine
from repro.models import build
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
ap.add_argument("--requests", type=int, default=6)
ap.add_argument("--batch", type=int, default=2)
ap.add_argument("--max-new", type=int, default=12)
args = ap.parse_args()

cfg = smoke(args.arch)
lm = build(cfg)
params = lm.init_params(jax.random.PRNGKey(0))
engine = ServeEngine(cfg, params, batch=args.batch, max_seq=128,
                     temperature=0.8)

rng = np.random.default_rng(0)
t0 = time.time()
for rid in range(args.requests):
    plen = int(rng.integers(3, 10))
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    engine.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))

done = engine.run()
dt = time.time() - t0
total_tokens = sum(len(c.tokens) for c in done)
print(f"arch={args.arch} ({cfg.family}); {len(done)} completions, "
      f"{total_tokens} tokens in {dt:.1f}s "
      f"({total_tokens / dt:.1f} tok/s with batch={args.batch})")
for c in sorted(done, key=lambda c: c.rid)[:3]:
    print(f"  request {c.rid}: {c.tokens}")
