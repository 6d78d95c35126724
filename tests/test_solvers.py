"""Krylov solver tests: CG, preconditioning, SLQ, RR-CG."""
import re

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import kernels_math as km
from repro.solvers import (cg, expected_iters, lanczos,
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


def _scan_cg(matvec, b, *, tol, max_iters, min_iters=10):
    """The scan form ``cg`` had before it exited early: every one of the
    static ``max_iters`` trips pays its matvec, finished columns frozen
    by ``alpha = 0``. Kept here as the reference the loop must match."""
    k = b.shape[1]
    x, r = jnp.zeros_like(b), b
    p = z = r
    rz = jnp.sum(r * z, axis=0)
    bnorm = jnp.maximum(jnp.linalg.norm(b, axis=0), 1e-30)
    tol_arr = jnp.asarray(tol, b.dtype)
    min_iters = min(min_iters, max_iters)

    def body(carry, j):
        x, r, z, p, rz, active = carry
        ap = matvec(p)
        pap = jnp.sum(p * ap, axis=0)
        safe_pap = jnp.where(pap > 0, pap, 1.0)
        alpha = jnp.where(active & (pap > 0), rz / safe_pap, 0.0)
        x = x + alpha * p
        r = r - alpha * ap
        z = r
        rz_new = jnp.sum(r * z, axis=0)
        safe_rz = jnp.where(rz != 0, rz, 1.0)
        beta = jnp.where(active, rz_new / safe_rz, 0.0)
        p = z + beta * p
        res = jnp.linalg.norm(r, axis=0) / bnorm
        still = active & ((res > tol_arr) | (j + 1 < min_iters))
        return (x, r, z, p, rz_new, still), (alpha, beta, active)

    init = (x, r, z, p, rz, jnp.ones((k,), bool))
    (x, r, *_), (alphas, betas, valid) = jax.lax.scan(
        body, init, jnp.arange(max_iters))
    res = jnp.linalg.norm(r, axis=0) / bnorm
    iters = jnp.sum(jnp.any(valid, axis=1).astype(jnp.int32))
    return x, (iters, res, res <= tol_arr, alphas, betas, valid)


def _random_block(rng):
    """A random SPD matrix and RHS block: all columns alike."""
    a = _spd(rng, 200)
    return a, jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)


def _staggered_block(rng, n=120):
    """An SPD matrix and RHS columns built from 3, 12 and all of its
    eigenvectors: CG finishes each column at a different trip."""
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    lam = np.linspace(1.0, 60.0, n)
    a = (q * lam) @ q.T
    b = np.stack([q[:, :3].sum(1), q[:, ::10].sum(1), rng.normal(size=n)], 1)
    return jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)


# name -> (problem, cg kwargs, iterations: exact, or "lt" = < max_iters)
CG_CASES = {
    "cold": (_random_block, dict(tol=1e-5, max_iters=300), "lt"),
    # columns that converge at different trips
    "staggered": (_staggered_block, dict(tol=1e-5, max_iters=300), "lt"),
    # the paper's train tolerance: the min_iters floor does all the work
    "min_iters_floor": (_staggered_block,
                        dict(tol=1.0, max_iters=100, min_iters=10), 10),
    # a tolerance float32 cannot reach: the loop stops at max_iters
    "never_converges": (_staggered_block, dict(tol=1e-12, max_iters=40), 40),
}


def _cg_case(rng, name):
    problem, kw, want = CG_CASES[name]
    return (*problem(rng), kw, want)


@pytest.mark.parametrize("name", CG_CASES)
def test_cg_matches_scan_reference(rng, name):
    """The early-exit loop runs the scan's recurrence while a column is
    active and leaves, for the trips it skips, exactly what the scan
    wrote for frozen trips: ``x`` and the whole ``CGInfo`` agree bit for
    bit."""
    a, b, kw, want = _cg_case(rng, name)
    mv = lambda v: a @ v
    x, info = jax.jit(lambda bb: cg(mv, bb, **kw))(b)
    xs, ref = jax.jit(lambda bb: _scan_cg(mv, bb, **kw))(b)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xs))
    for got, exp in zip(info, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    iters = int(info.iterations)
    if want == "lt":
        assert iters < kw["max_iters"]
    else:
        assert iters == want
    if name == "staggered":  # the columns really do finish apart
        last = [int(np.max(np.nonzero(np.asarray(info.valid[:, c]))[0]))
                for c in range(b.shape[1])]
        assert len(set(last)) == b.shape[1], last


@pytest.mark.parametrize("name", CG_CASES)
def test_cg_runs_only_the_iterations_it_reports(rng, name):
    """Under jit the loop pays one matvec per iteration it reports and no
    more: it exits when every column is done, not at ``max_iters``."""
    a, b, kw, want = _cg_case(rng, name)
    calls = []

    def mv(v):
        jax.debug.callback(lambda: calls.append(1))
        return a @ v

    _, info = jax.jit(lambda bb: cg(mv, bb, **kw))(b)
    jax.effects_barrier()
    assert len(calls) == int(info.iterations)
    if want == "lt":
        assert len(calls) < kw["max_iters"]


@pytest.mark.parametrize("name", CG_CASES)
def test_slq_logdet_from_cg_matches_scan_reference(rng, name):
    """SLQ reads the same coefficients off both, so the same log-det."""
    a, b, kw, _ = _cg_case(rng, name)
    mv = lambda v: a @ v
    n, k = b.shape
    probes = jnp.asarray(np.sign(rng.normal(size=(n, k))), jnp.float32)
    norms = jnp.full((k,), float(n), jnp.float32)
    slq = jax.jit(lambda al, be, va: slq_logdet_from_cg(al, be, va, norms))
    _, info = jax.jit(lambda bb: cg(mv, bb, **kw))(probes)
    _, ref = jax.jit(lambda bb: _scan_cg(mv, bb, **kw))(probes)
    got = slq(info.alphas, info.betas, info.valid)
    exp = slq(*ref[3:])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_cg_compiles_to_a_loop_that_exits_on_the_residuals(rng, cg_loop):
    """The compiled solve is one while loop with no fixed trip count,
    whose condition reduces the per-column active mask."""
    a, b = _staggered_block(rng)
    text = jax.jit(lambda bb: cg(lambda v: a @ v, bb, tol=1e-5,
                                 max_iters=300)).lower(b).compile().as_text()
    loop, cond = cg_loop(text)
    assert "known_trip_count" not in loop
    assert any(re.search(r"= pred\[3\]\S* get-tuple-element\(", ln)
               for ln in cond), cond


def test_cg_warm_start_cuts_iterations(rng):
    """Warm-starting from the true solution exits without iterating;
    warm-starting from a nearby solve takes fewer iterations than cold
    and reaches the same answer. This is the refresh path's economics
    (gp/serve.refreeze)."""
    a = _spd(rng, 200)
    b = jnp.asarray(rng.normal(size=(200, 1)), jnp.float32)
    x_cold, info_cold = cg(lambda v: a @ v, b, tol=1e-5, max_iters=300)
    # a seed already within tolerance starts inactive: zero iterations.
    # (tol is looser than the cold solve's because the TRUE residual of
    # x_cold sits slightly above the recurrence residual it stopped on.)
    x_same, info_same = cg(lambda v: a @ v, b, tol=1e-4,
                           max_iters=300, x0=x_cold)
    assert int(info_same.iterations) == 0
    assert not bool(info_same.valid.any())
    np.testing.assert_array_equal(np.asarray(x_same), np.asarray(x_cold))
    # perturbed rhs: warm start from the old solution converges in fewer
    # iterations than the cold solve of the new system
    b2 = b + 0.01 * jnp.asarray(rng.normal(size=b.shape), jnp.float32)
    _, info_cold2 = cg(lambda v: a @ v, b2, tol=1e-5, max_iters=300)
    x_warm, info_warm = cg(lambda v: a @ v, b2, tol=1e-5,
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
    ``mvm.calls``/``mvm.cols`` counters of ``repro.obs``: the CG loop body
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
    assert calls == 1, calls  # one traced MVM in the loop body
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
