"""Quickstart: Simplex-GP regression in ~40 lines (paper §5.3 workflow).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, fit, nll,
                      posterior, rmse)
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# --- data: a smooth function of 4 inputs + noise ---------------------------
rng = np.random.default_rng(0)
n, d = 2000, 4
x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
f = jnp.sin(2 * x[:, 0]) + 0.5 * jnp.cos(x[:, 1] * x[:, 2]) + 0.3 * x[:, 3]
y = f + 0.1 * jnp.asarray(rng.normal(size=n), jnp.float32)
x_tr, y_tr = x[:1400], y[:1400]
x_val, y_val = x[1400:1700], f[1400:1700]
x_te, y_te = x[1700:], f[1700:]

# --- model: Matern-3/2 on the permutohedral lattice, order-1 blur ----------
model = SimplexGP(SimplexGPConfig(
    kernel="matern32",     # any stationary profile (paper §4.1)
    order=1,               # blur stencil radius r (Appendix A)
    grad_mode="autodiff",  # beyond-paper gradient mode (DESIGN.md §7)
    max_cg_iters=50,
))

# --- train: Adam(0.1) on the BBMM MLL, early stop on val RMSE (§5.4) -------
result = fit(model, x_tr, y_tr, x_val=x_val, y_val=y_val, epochs=25,
             lr=0.1, log_fn=print)

# --- predict ---------------------------------------------------------------
post = posterior(model, result.best_params, x_tr, y_tr, x_te,
                 key=jax.random.PRNGKey(0))
noise = model.constrained(result.best_params)[2]
print(f"\ntest RMSE {float(rmse(post, y_te)):.4f}   "
      f"test NLL {float(nll(post, noise, y_te)):.4f}")
ls, os_, nz = model.constrained(result.best_params)
print(f"learned ARD lengthscales: {np.asarray(ls).round(3)}")
print(f"outputscale {float(os_):.3f}   noise {float(nz):.4f}")
