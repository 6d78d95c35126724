"""PILCO-style Monte-Carlo rollout on a frozen multi-output GP dynamics
model — the control workload differentiable frozen serving exists for
(ROADMAP item 4; DESIGN.md §15).

The loop, end to end:

  1. COLLECT  a few random-action episodes of the true dynamics (here a
     damped pendulum) give (state, action) -> next-state-delta pairs.
  2. FIT + FREEZE_MULTI  one Simplex-GP per state dimension, but frozen
     into ONE MultiPredictor: the k=2 output channels share the lattice
     index and a stacked (m+1, k*(1+r)) table, so serving both channels
     costs ONE embed + d+1 hash probes per query (gp/serve.py).
  3. ROLLOUT  P particles for H steps: each step queries the frozen
     model at [state, policy(state)], samples the next state from the
     predictive mean/variance (the Monte-Carlo counterpart of PILCO's
     moment matching), and accrues cost. The whole (P, H) trajectory
     cloud is one jitted ``lax.scan``. The LOVE low-rank variance is a
     CONSERVATIVE upper bound on the posterior variance (it only
     subtracts the explained mass the Lanczos subspace captured), so
     the sampled noise is tempered by ``LAM`` — the reparameterization,
     and therefore the gradient flow, is unchanged.
  4. IMPROVE  the expected cost is differentiated END TO END with
     ``jax.grad`` — through the sampling, through the frozen slice
     (the custom JVP of ``filtering.slice_only``: barycentric weights
     are piecewise-linear in the query, so the tangent is one extra
     contraction, no probes), into the policy parameters. A few plain
     gradient steps visibly drop the cost.

Validity gating: gradients of the frozen surface are exact FOR THE
SURROGATE everywhere, but only approximate the GP posterior's where
``miss_mass == 0`` (inside the frozen lattice). The rollout tracks the
worst per-step miss and reports it — a policy that drags particles off
the training manifold announces itself here rather than silently
following a kinked extrapolation.

    PYTHONPATH=src python examples/rollout_pilco.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.gp import GPParams, SimplexGP, SimplexGPConfig, freeze_multi
from repro.gp.serve import predict_multi
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# --- the true system: a damped pendulum, angle th / velocity om ------------
DT = 0.1


def true_step(state, action):
    th, om = state[..., 0], state[..., 1]
    om2 = om + DT * (-9.8 * jnp.sin(th) - 0.2 * om + action)
    th2 = th + DT * om2
    return jnp.stack([th2, om2], axis=-1)


# --- 1. collect off-policy transitions -------------------------------------
rng = np.random.default_rng(0)
n = 1500
states = jnp.asarray(
    np.stack([rng.uniform(-np.pi, np.pi, n), rng.uniform(-7, 7, n)], 1),
    jnp.float32)
actions = jnp.asarray(rng.uniform(-2, 2, n), jnp.float32)
deltas = true_step(states, actions) - states  # (n, 2): the GP targets

x_train = jnp.concatenate([states, actions[:, None]], axis=1)  # (n, 3)
y_train = deltas  # (n, k=2)

# --- 2. freeze a stacked 2-output dynamics model ---------------------------
model = SimplexGP(SimplexGPConfig(kernel="matern32"))
# anisotropic lengthscales sized to the state box: the lattice cell is
# ~1.3 lengthscales wide, so these keep the 1500 training points dense
# per cell (few coverage holes -> near-zero rollout miss_mass) while the
# smooth pendulum deltas stay well fit
params = GPParams.init(3, lengthscale=jnp.asarray([1.0, 2.0, 1.2]),
                       noise=1e-2)

t0 = time.perf_counter()
mp = freeze_multi(model, params, x_train, y_train,
                  key=jax.random.PRNGKey(0), variance_rank=24)
print(f"freeze_multi: {time.perf_counter() - t0:.2f}s — "
      f"{mp.n_outputs} channels in one {mp.tables.shape} table, "
      f"CG converged={np.asarray(mp.cg_converged).tolist()}")

# --- 3 + 4. differentiable MC rollout + policy gradient --------------------
P, H = 256, 100  # particles, horizon
LAM = 0.1  # variance tempering: LOVE var is conservative (see docstring)
TARGET = jnp.asarray([0.0, 0.0])  # damp a big swing down to rest


def wrap(th):
    """Wrap the angle into the trained [-pi, pi) chart. ``round`` is
    piecewise-constant, so d wrap/d th == 1 — gradients pass through."""
    return th - 2 * jnp.pi * jnp.round(th / (2 * jnp.pi))


def policy(w, s):
    """Tiny affine-tanh controller; w is what we optimize."""
    feats = jnp.stack([jnp.sin(s[..., 0]), jnp.cos(s[..., 0]),
                       s[..., 1]], axis=-1)
    return 2.0 * jnp.tanh(feats @ w[:3] + w[3])


def rollout_cost(w, key):
    """Expected cost of the particle cloud under the FROZEN model.

    Every step serves all P particles x k channels from one probe
    batch; the sampling reparameterization keeps the whole thing
    differentiable, so jax.grad(rollout_cost) is the policy gradient
    PILCO computes by moment-matching — here by Monte Carlo.
    """
    s0 = jnp.zeros((P, 2)).at[:, 0].set(2.5)  # released from a big swing
    eps = jax.random.normal(key, (H, P, 2))

    def step(s, e):
        a = policy(w, s)
        q = jnp.stack([wrap(s[:, 0]), s[:, 1], a], axis=1)  # (P, 3)
        res = predict_multi(mp, q)
        s2 = s + res.mean + LAM * jnp.sqrt(res.var) * e  # reparam sample
        err = jnp.stack([jnp.cos(s2[:, 0]) - jnp.cos(TARGET[0]),
                         jnp.sin(s2[:, 0]) - jnp.sin(TARGET[0]),
                         0.3 * (s2[:, 1] - TARGET[1])], axis=1)
        cost = jnp.mean(jnp.sum(err ** 2, axis=1))
        return s2, (cost, jnp.max(res.miss_mass))

    _, (costs, miss) = jax.lax.scan(step, s0, eps)
    return jnp.mean(costs), jnp.max(miss)


grad_fn = jax.jit(jax.value_and_grad(rollout_cost, has_aux=True))

w = jnp.zeros(4)
key = jax.random.PRNGKey(1)
t0 = time.perf_counter()
for it in range(15):
    key, sub = jax.random.split(key)
    (cost, worst_miss), g = grad_fn(w, sub)
    w = w - 0.5 * g
    if it % 3 == 0 or it == 14:
        print(f"iter {it:2d}  E[cost]={float(cost):.4f}  "
              f"worst step miss={float(worst_miss):.3f}  "
              f"|grad|={float(jnp.linalg.norm(g)):.3f}")
evals = 15 * P * H * mp.n_outputs
dt = time.perf_counter() - t0
print(f"policy search: {dt:.2f}s — {evals / dt:,.0f} "
      "state-evals/s THROUGH the gradient (fwd+bwd each step)")
