"""Large-scale Simplex-GP: houseelectric-style MVMs + one training epoch.

Demonstrates the paper's core claim at the largest size this host can
hold: lattice MVMs on 100k+ points in seconds, where the exact kernel
matrix (n^2 floats) would not even fit in memory.

    PYTHONPATH=src python examples/gp_large_scale.py [--n 100000]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import filtering
from repro.core.stencil import make_stencil
from repro.data.synthetic_uci import load
from repro.gp import GPParams, SimplexGP, SimplexGPConfig
from repro.gp.mll import mll_value_and_grad
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--n", type=int, default=100_000)
args = ap.parse_args()

ds = load("houseelectric", scale=args.n / 2_049_280)
x = jnp.asarray(ds.x_train)
y = jnp.asarray(ds.y_train)
n, d = x.shape
print(f"houseelectric stand-in: n={n:,} d={d}  "
      f"(dense K would be {n * n * 4 / 2**30:.0f} GiB)")

# --- one MVM ----------------------------------------------------------------
st = make_stencil("matern32", 1)
t0 = time.time()
mv, lat = filtering.mvm_operator(x, st)
v = y[:, None]
u = jax.block_until_ready(mv(v))
print(f"lattice build + first MVM: {time.time() - t0:.2f}s "
      f"(m={int(lat.m):,} lattice points, "
      f"m/L={int(lat.m) / (n * (d + 1)):.3f})")
t0 = time.time()
jax.block_until_ready(mv(v))
print(f"amortized MVM: {time.time() - t0:.3f}s")

# --- one full BBMM training step (CG solves + SLQ + gradients) --------------
model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=20,
                                  num_probes=4, max_lanczos_iters=10))
params = GPParams.init(d)
t0 = time.time()
res = mll_value_and_grad(model, params, x, y, jax.random.PRNGKey(0),
                         tol=1e-2)
print(f"one MLL step (20 CG iters, 4 probes): {time.time() - t0:.1f}s  "
      f"mll/n={float(res.mll) / n:+.4f}")
print("grad wrt log-lengthscales:",
      jax.numpy.round(res.grads.raw_lengthscale, 4))
