"""End-to-end Simplex-GP inference tests (paper §5 behaviours)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import kernels_math as km
from repro.core.exact import ExactGP
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, cross_mvm, fit,
                      mll_value_and_grad, nll, posterior, rmse)
from repro.gp.models import softplus


def _problem(rng, n=600, d=3, noise=0.1):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    f = jnp.sin(2 * x[:, 0]) + 0.5 * jnp.cos(x[:, 1] * (x[:, 2]
                                                        if d > 2 else 1.0))
    y = f + noise * jnp.asarray(rng.normal(size=n), jnp.float32)
    return x, y, f


def test_mll_value_close_to_exact(rng):
    x, y, _ = _problem(rng, n=500)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=80,
                                      num_probes=10, max_lanczos_iters=40))
    params = GPParams.init(3, noise=0.2)
    res = mll_value_and_grad(model, params, x, y, jax.random.PRNGKey(0),
                             tol=1e-3)
    eg = ExactGP(km.MATERN32)
    ls, os_, nz = model.constrained(params)
    want = float(eg.mll(x, y, lengthscale=ls, outputscale=os_, noise=nz))
    # lattice operator approximates K; SLQ adds noise — same decade check
    assert abs(float(res.mll) - want) < 0.45 * abs(want) + 50.0


@pytest.mark.parametrize("grad_mode", ["paper", "autodiff"])
def test_training_improves_validation_rmse(rng, grad_mode):
    x, y, _ = _problem(rng, n=700)
    xv, yv, fv = _problem(np.random.default_rng(7), n=150)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=40,
                                      num_probes=6, grad_mode=grad_mode,
                                      max_lanczos_iters=20))
    res = fit(model, x, y, x_val=xv, y_val=fv, epochs=10, lr=0.1,
              patience=10)
    first = res.history[0]["val_rmse"]
    assert res.best_val_rmse < first  # learning happened


def test_posterior_beats_prior(rng):
    x, y, _ = _problem(rng, n=600)
    xs, ys, fs = _problem(np.random.default_rng(3), n=120)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=60))
    params = GPParams.init(3, noise=0.1, lengthscale=1.0)
    post = posterior(model, params, x, y, xs, key=jax.random.PRNGKey(1))
    pr = float(rmse(post, fs))
    assert pr < float(jnp.std(fs))  # better than predicting the mean
    assert bool(jnp.all(post.var > 0))
    assert np.isfinite(float(nll(post, model.constrained(params)[2], fs)))


@pytest.mark.parametrize("shared", [True, False])
def test_posterior_reports_mean_solve_iterations(rng, shared):
    """``Posterior.cg_iters`` is the mean solve's iteration count: at the
    eval tolerance it stops well before ``max_cg_iters``."""
    x, y, _ = _problem(rng, n=400)
    xs, _, _ = _problem(np.random.default_rng(3), n=50)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=60,
                                      shared_lattice=shared))
    params = GPParams.init(3, noise=0.1, lengthscale=1.0)
    post = jax.jit(lambda p: posterior(model, p, x, y, xs,
                                       key=jax.random.PRNGKey(1),
                                       variance_rank=5))(params)
    assert post.cg_iters.dtype == jnp.int32
    assert 0 < int(post.cg_iters) < 60


def test_cross_mvm_matches_dense(rng):
    x = jnp.asarray(rng.normal(size=(300, 3)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(80, 3)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(300, 2)), jnp.float32)
    model = SimplexGP(SimplexGPConfig(kernel="rbf"))
    params = GPParams.init(3)
    got = cross_mvm(model, params, x, xs, v)
    ls, os_, _ = model.constrained(params)
    want = km.gram(km.RBF, xs, x, ls, os_) @ v
    cos = float(jnp.vdot(got, want)
                / (jnp.linalg.norm(got) * jnp.linalg.norm(want)))
    assert cos > 0.93


def test_one_lattice_build_per_step_and_posterior(rng):
    """DESIGN.md §9 contract: a jitted training step traces exactly ONE
    lattice build (seed: 3 — operator + two surrogate quad forms), and a
    posterior performs exactly ONE (seed: 3 — operator + two cross_mvm).

    The rebuild-per-call pipeline now traces TWO builds per step (operator
    + the single batched surrogate quad form — the multi-RHS batching of
    DESIGN.md §10 merged the two surrogate terms into one filtering even
    without lattice sharing); its posterior still builds 3 (operator + two
    cross_mvm joint builds)."""

    x, y, _ = _problem(rng, n=300)
    xs, _, _ = _problem(np.random.default_rng(5), n=60)
    params = GPParams.init(3)

    shared = SimplexGP(SimplexGPConfig(max_cg_iters=20, num_probes=4,
                                       max_lanczos_iters=10))
    legacy = SimplexGP(SimplexGPConfig(max_cg_iters=20, num_probes=4,
                                       max_lanczos_iters=10,
                                       shared_lattice=False,
                                       logdet_estimator="slq"))
    for model, want_step, want_post in [(shared, 1, 1), (legacy, 2, 3)]:
        step = jax.jit(lambda p, k, m=model: mll_value_and_grad(
            m, p, x, y, k))
        c0 = obs.counter("lattice.builds")
        jax.block_until_ready(step(params, jax.random.PRNGKey(0)))
        assert obs.counter("lattice.builds") - c0 == want_step

        c0 = obs.counter("lattice.builds")
        post = posterior(model, params, x, y, xs,
                         key=jax.random.PRNGKey(1), variance_rank=8)
        jax.block_until_ready(post.mean)
        assert obs.counter("lattice.builds") - c0 == want_post


def test_shared_lattice_matches_legacy_pipeline(rng):
    """Shared-lattice step == rebuild-per-call step: identical surrogate
    gradients (same lattice values by determinism) and MLL within
    stochastic-estimator noise (different log-det estimators)."""
    x, y, _ = _problem(rng, n=400)
    params = GPParams.init(3, noise=0.2)
    kw = dict(kernel="matern32", max_cg_iters=80, num_probes=8,
              max_lanczos_iters=40)
    shared = SimplexGP(SimplexGPConfig(**kw))
    legacy = SimplexGP(SimplexGPConfig(shared_lattice=False,
                                       logdet_estimator="slq", **kw))
    key = jax.random.PRNGKey(2)
    res_s = mll_value_and_grad(shared, params, x, y, key, tol=1e-4)
    res_l = mll_value_and_grad(legacy, params, x, y, key, tol=1e-4)
    for gs, gl in zip(jax.tree.leaves(res_s.grads),
                      jax.tree.leaves(res_l.grads)):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gl),
                                   rtol=1e-5, atol=1e-6)
    # same CG solves -> same data-fit term; log-det estimators differ only
    # by probe sets/depth, so values agree to estimator noise
    assert abs(float(res_s.mll) - float(res_l.mll)) < \
        0.05 * abs(float(res_l.mll)) + 20.0


def test_posterior_shared_joint_lattice_close_to_legacy(rng):
    """Single-joint-lattice posterior tracks the rebuild-per-call one (the
    K_XX approximations differ slightly — the joint lattice is denser)."""
    x, y, _ = _problem(rng, n=400)
    xs, _, fs = _problem(np.random.default_rng(9), n=80)
    params = GPParams.init(3, noise=0.1)
    kw = dict(kernel="matern32", max_cg_iters=60)
    shared = SimplexGP(SimplexGPConfig(**kw))
    legacy = SimplexGP(SimplexGPConfig(shared_lattice=False, **kw))
    ps = posterior(shared, params, x, y, xs, key=jax.random.PRNGKey(4))
    pl = posterior(legacy, params, x, y, xs, key=jax.random.PRNGKey(4))
    scale = float(jnp.std(pl.mean)) + 1e-6
    assert float(jnp.max(jnp.abs(ps.mean - pl.mean))) < 0.35 * scale
    assert bool(jnp.all(ps.var > 0))
    # both beat predicting the mean on held-out structure
    assert float(rmse(ps, fs)) < float(jnp.std(fs))


def test_rrcg_training_step_runs(rng):
    x, y, _ = _problem(rng, n=300)
    model = SimplexGP(SimplexGPConfig(kernel="rbf", max_cg_iters=40,
                                      num_probes=4, max_lanczos_iters=15))
    params = GPParams.init(3)
    res = mll_value_and_grad(model, params, x, y, jax.random.PRNGKey(5),
                             use_rrcg=True)
    assert np.isfinite(float(res.mll))
    for leaf in jax.tree.leaves(res.grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_precond_rank_config(rng):
    x, y, _ = _problem(rng, n=250)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=30,
                                      precond_rank=20, num_probes=4,
                                      max_lanczos_iters=10))
    params = GPParams.init(3)
    res = mll_value_and_grad(model, params, x, y, jax.random.PRNGKey(0))
    assert np.isfinite(float(res.mll))
