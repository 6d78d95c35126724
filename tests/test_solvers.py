"""Krylov solver tests: CG, preconditioning, SLQ, RR-CG."""
from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import kernels_math as km
from repro.solvers import (cg, cg_while, expected_iters, lanczos,
                           pivoted_cholesky, precond_logdet, rrcg,
                           slq_logdet, slq_logdet_from_cg, woodbury_precond)


def _spd(rng, n, cond=100.0):
    a = rng.normal(size=(n, n))
    m = a @ a.T / n + np.eye(n) / cond
    return jnp.asarray(m, jnp.float32)


def test_cg_solves_to_tolerance(rng):
    a = _spd(rng, 200)
    b = jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)
    x, info = cg(lambda v: a @ v, b, tol=1e-6, max_iters=300)
    rel = float(jnp.linalg.norm(a @ x - b) / jnp.linalg.norm(b))
    # f32 CG: the recurrence residual hits 1e-6 but the TRUE residual
    # stagnates around eps * sqrt(kappa) ~ 1e-5; allow that headroom.
    assert rel < 3e-5
    assert bool(info.converged.all())


def test_cg_min_iters_at_paper_tolerance(rng):
    """Appendix A train tolerance 1.0 must still do work (>= min_iters)."""
    a = _spd(rng, 150)
    b = jnp.asarray(rng.normal(size=(150, 1)), jnp.float32)
    x, info = cg(lambda v: a @ v, b, tol=1.0, max_iters=100, min_iters=10)
    assert int(info.iterations) >= 10
    assert float(jnp.linalg.norm(x)) > 0


def test_cg_while_matches_scan_cg_cold(rng):
    """The early-exit solver runs the identical update recurrence, so a
    cold start must reproduce the scan-based ``cg`` solution bit-for-bit
    (same converged mask, same solution, fewer wasted iterations)."""
    a = _spd(rng, 200)
    b = jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)
    xs, info_s = cg(lambda v: a @ v, b, tol=1e-5, max_iters=300)
    xw, info_w = cg_while(lambda v: a @ v, b, tol=1e-5, max_iters=300)
    np.testing.assert_array_equal(np.asarray(xw), np.asarray(xs))
    assert bool(info_w.converged.all())
    assert int(info_w.iterations) <= int(info_s.iterations)


def test_cg_while_warm_start_cuts_iterations(rng):
    """Warm-starting from the true solution exits without iterating;
    warm-starting from a nearby solve takes fewer iterations than cold
    and reaches the same answer. This is the refresh path's economics
    (gp/serve.refreeze)."""
    a = _spd(rng, 200)
    b = jnp.asarray(rng.normal(size=(200, 1)), jnp.float32)
    x_cold, info_cold = cg_while(lambda v: a @ v, b, tol=1e-5, max_iters=300)
    # a seed already within tolerance starts inactive: zero iterations.
    # (tol is looser than the cold solve's because the TRUE residual of
    # x_cold sits slightly above the recurrence residual it stopped on.)
    x_same, info_same = cg_while(lambda v: a @ v, b, tol=1e-4,
                                 max_iters=300, x0=x_cold)
    assert int(info_same.iterations) == 0
    np.testing.assert_array_equal(np.asarray(x_same), np.asarray(x_cold))
    # perturbed rhs: warm start from the old solution converges in fewer
    # iterations than the cold solve of the new system
    b2 = b + 0.01 * jnp.asarray(rng.normal(size=b.shape), jnp.float32)
    _, info_cold2 = cg_while(lambda v: a @ v, b2, tol=1e-5, max_iters=300)
    x_warm, info_warm = cg_while(lambda v: a @ v, b2, tol=1e-5,
                                 max_iters=300, x0=x_cold)
    assert bool(info_warm.converged.all())
    assert int(info_warm.iterations) < int(info_cold2.iterations)
    rel = float(jnp.linalg.norm(a @ x_warm - b2) / jnp.linalg.norm(b2))
    assert rel < 3e-5


def test_preconditioner_reduces_iterations(rng):
    x0 = jnp.asarray(rng.normal(size=(400, 4)), jnp.float32)
    k = km.gram(km.RBF, x0, x0)
    s2 = jnp.float32(0.05)
    mv = lambda v: k @ v + s2 * v
    b = jnp.asarray(rng.normal(size=(400, 1)), jnp.float32)
    pc = pivoted_cholesky(lambda i: km.gram(km.RBF, x0[i][None], x0)[0],
                          jnp.ones(400, jnp.float32), 40)
    pre = woodbury_precond(pc.l, s2)
    _, plain = cg(mv, b, tol=1e-4, max_iters=300)
    _, prec = cg(mv, b, precond=pre, tol=1e-4, max_iters=300)
    assert int(prec.iterations) < int(plain.iterations)


def test_pivoted_cholesky_approximates_kernel(rng):
    x0 = jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)
    k = km.gram(km.RBF, x0, x0)
    pc = pivoted_cholesky(lambda i: k[i], jnp.ones(200, jnp.float32), 60)
    approx = pc.l @ pc.l.T
    rel = float(jnp.linalg.norm(approx - k) / jnp.linalg.norm(k))
    assert rel < 0.1
    assert float(pc.error) >= 0


def test_woodbury_matches_direct(rng):
    l = jnp.asarray(rng.normal(size=(100, 10)), jnp.float32)
    s2 = jnp.float32(0.3)
    p = l @ l.T + s2 * jnp.eye(100)
    b = jnp.asarray(rng.normal(size=(100, 2)), jnp.float32)
    got = woodbury_precond(l, s2)(b)
    want = jnp.linalg.solve(p, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)
    ld = float(precond_logdet(l, s2, 100))
    want_ld = float(jnp.linalg.slogdet(p)[1])
    assert abs(ld - want_ld) < 1e-2 * abs(want_ld)


def test_slq_logdet(rng):
    a = _spd(rng, 250)
    ld = slq_logdet(lambda v: a @ v, 250, key=jax.random.PRNGKey(0),
                    num_probes=30, num_iters=60)
    want = float(jnp.linalg.slogdet(a)[1])
    assert abs(float(ld) - want) < 0.1 * abs(want)


def test_slq_logdet_from_cg_matches_dense(rng):
    """BBMM's free log-det: SLQ on the tridiagonals mBCG collects during
    Rademacher-probe solves matches dense slogdet on a small SPD matrix."""
    n, p = 250, 30
    a = _spd(rng, n)
    probes = jnp.asarray(np.sign(rng.normal(size=(n, p))), jnp.float32)
    _, info = cg(lambda v: a @ v, probes, tol=1e-7, max_iters=120)
    ld = slq_logdet_from_cg(info.alphas, info.betas, info.valid,
                            jnp.full((p,), float(n), jnp.float32))
    want = float(jnp.linalg.slogdet(a)[1])
    assert abs(float(ld) - want) < 0.1 * abs(want)


def test_slq_logdet_from_cg_agrees_with_separate_slq(rng):
    """The two estimators target the same quantity; with matched probes and
    depth they land within stochastic-estimator noise of each other."""
    n, p = 200, 25
    a = _spd(rng, n)
    key = jax.random.PRNGKey(3)
    probes = jax.random.rademacher(key, (n, p), dtype=jnp.float32)
    _, info = cg(lambda v: a @ v, probes, tol=1e-7, max_iters=100)
    ld_cg = float(slq_logdet_from_cg(info.alphas, info.betas, info.valid,
                                     jnp.full((p,), float(n), jnp.float32)))
    ld_slq = float(slq_logdet(lambda v: a @ v, n, key=key, num_probes=p,
                              num_iters=60))
    denom = max(abs(ld_slq), 1.0)
    assert abs(ld_cg - ld_slq) < 0.15 * denom + 5.0


def test_lanczos_extreme_eigenvalues(rng):
    a = _spd(rng, 150)
    evals = np.linalg.eigvalsh(np.asarray(a))
    q0 = jnp.asarray(rng.normal(size=(150, 1)), jnp.float32)
    res = lanczos(lambda v: a @ v, q0, 50)
    t = (np.diag(np.asarray(res.alphas[:, 0]))
         + np.diag(np.asarray(res.betas[:-1, 0]), 1)
         + np.diag(np.asarray(res.betas[:-1, 0]), -1))
    ritz = np.linalg.eigvalsh(t)
    assert abs(ritz.max() - evals.max()) < 1e-2 * evals.max()


def test_rrcg_unbiased(rng):
    a = _spd(rng, 120)
    b = jnp.asarray(rng.normal(size=(120, 1)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(2), 48)
    sols = jnp.stack([rrcg(lambda v: a @ v, b, key=k, min_iters=20,
                           max_iters=120).x for k in keys])
    mean = jnp.mean(sols, axis=0)
    want = jnp.linalg.solve(a, b)
    rel = float(jnp.linalg.norm(mean - want) / jnp.linalg.norm(want))
    assert rel < 0.05


def test_rrcg_expected_iters_between_bounds():
    e = expected_iters(20, 200, q=0.95)
    assert 20 < e < 60  # ~ min + 1/(1-q)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(20, 120), seed=st.integers(0, 999))
def test_property_cg_residual_decreases(n, seed):
    rng = np.random.default_rng(seed)
    a = _spd(rng, n)
    b = jnp.asarray(rng.normal(size=(n, 1)), jnp.float32)
    x, info = cg(lambda v: a @ v, b, tol=1e-5, max_iters=2 * n)
    assert float(info.residual_norms[0]) < 1e-3


def test_mbcg_issues_one_batched_mvm_per_iteration(rng):
    """Multi-RHS operator contract: mBCG with k probe columns advances the
    whole [y | Z] block through ONE (n, 1+k)-channel lattice MVM per
    iteration — never one MVM per column. Pinned at trace level with the
    ``mvm.calls``/``mvm.cols`` counters of ``repro.obs``: the CG scan body
    traces exactly one lattice_mvm call whose channel width is the full
    block, and the operator build itself traces exactly one more (the
    initial residual is b, so there is no extra setup MVM)."""
    from repro.core import filtering
    from repro.core.stencil import make_stencil

    n, d, k = 96, 2, 7
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n, 1 + k)), jnp.float32)
    stn = make_stencil("matern32", 1)
    matvec, _ = filtering.mvm_operator(x, stn)
    op = lambda v: matvec(v) + 0.5 * v

    c0, w0 = obs.counter("mvm.calls"), obs.counter("mvm.cols")
    _, info = cg(op, b, tol=1e-2, max_iters=25)
    calls = obs.counter("mvm.calls") - c0
    cols = obs.counter("mvm.cols") - w0
    assert calls == 1, calls  # one traced MVM in the scan body
    assert cols == 1 + k, cols  # ... carrying the WHOLE block
    assert int(info.iterations) > 1  # and it actually iterated


def test_lanczos_block_rides_one_mvm_per_iteration(rng):
    """Same contract for the Lanczos/LOVE side: a (n, k) start block is
    tridiagonalized with one batched MVM per iteration."""
    from repro.core import filtering
    from repro.core.stencil import make_stencil

    n, d, k = 80, 2, 5
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q0 = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    stn = make_stencil("rbf", 1)
    matvec, _ = filtering.mvm_operator(x, stn)

    c0, w0 = obs.counter("mvm.calls"), obs.counter("mvm.cols")
    res = lanczos(lambda v: matvec(v) + 0.1 * v, q0, 10)
    assert obs.counter("mvm.calls") - c0 == 1
    assert obs.counter("mvm.cols") - w0 == k
    assert bool(jnp.all(jnp.isfinite(res.alphas)))
