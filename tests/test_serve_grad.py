"""Differentiable frozen serving (gp/serve.predict_grad, DESIGN.md §15).

The gradcheck suite behind the PR's query-space-gradient contract:

  * ANALYTIC == NUMERIC: the served mean is piecewise-LINEAR and the
    LOVE variance piecewise-QUADRATIC in x*, so central differences are
    EXACT (up to f32 roundoff) whenever both probe points stay in the
    query's simplex cell — the FD check filters to same-cell pairs via
    the embed keys and then demands 1e-4, far below what a smooth-model
    gradcheck could ask of f32.
  * ANALYTIC == AUTODIFF: ``predict_grad`` (fused forward pass, no
    autodiff) matches ``jax.jacfwd`` of the serving core to f32 noise,
    and reverse-mode ``jax.grad`` works through the ``slice_only``
    custom JVP.
  * SURROGATE ~= MODEL: against the DENSE exact-GP analytic gradient
    (``gp.predict.exact_mean_grad``) on a target much smoother than the
    lattice cell, the frozen gradient is globally unbiased (unit scale
    fit) and pointwise aligned — the fences catch sign/scale/indexing
    bugs while allowing the O(cell) interpolation scatter.
  * MULTI-OUTPUT: ``freeze_multi`` is bit-exact against k independent
    ``freeze()`` calls (channels solve sequentially on the shared
    lattice), and the k-channel serving path pays ONE embed per batch.
  * BOUNDARIES: the positional tie-break (``lattice.descending_rank``)
    makes cell-boundary subgradients deterministic; ``grad_ok`` gates
    off-lattice queries.
  * ZERO-COLLECTIVE: query-space gradients under the replicated-table
    mesh contract stay collective-free (sharding/simplex.py).

CI runs this file as its own lane: ``pytest -m gradcheck``.
"""
import functools

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import filtering
from repro.core import lattice as L
from repro.core.kernels_math import PROFILES
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, exact_mean_grad,
                      freeze, freeze_multi)
from repro.gp.serve import (_predict_core, _predict_multi_core, predict,
                            predict_grad, predict_multi, predict_multi_grad)
from repro.sharding.simplex import collective_counts, data_mesh

pytestmark = pytest.mark.gradcheck

TIGHT = SimplexGPConfig(kernel="matern32", cg_tol_eval=3e-7,
                        max_cg_iters=400)
# in-cell FD step: large enough that the f32 roundoff of the two
# evaluations is ~1e-6 of the secant, small enough that most probe pairs
# stay inside one simplex cell (cell size ~ spacing * ls ~ 1.3)
FD_EPS = 2.5e-2


def _data(seed, n, d):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = (jnp.sin(2 * x[:, 0]) + 0.4 * x[:, 1] * x[:, d - 1]
         + 0.05 * jnp.asarray(rng.normal(size=n), jnp.float32))
    return x, y


@functools.lru_cache(maxsize=None)
def _frozen(d, n=500, rank=10):
    """One tight-config freeze per dimension, shared across the suite."""
    x, y = _data(0, n, d)
    model = SimplexGP(TIGHT)
    params = GPParams.init(d, noise=0.3)
    pred = freeze(model, params, x, y, key=jax.random.PRNGKey(0),
                  variance_rank=rank)
    return model, params, x, y, pred


def _same_cell(pred, model, xa, xb):
    """True per row iff xa and xb embed into the SAME simplex cell."""
    sp = model.stencil.spacing
    ka, _ = L.simplex_embed(xa / pred.lengthscale[None, :], sp)
    kb, _ = L.simplex_embed(xb / pred.lengthscale[None, :], sp)
    return np.asarray(jnp.all(ka == kb, axis=(1, 2)))


# -- analytic vs central differences (exact in-cell) -------------------------


@pytest.mark.parametrize("d", [2, 3, 5])
def test_fd_gradcheck_interior(d):
    """d(mean, var)/dx* == central differences to 1e-4 relative (scale
    floored at 1: mean/var are O(1) here) at strictly-interior queries —
    per coordinate, for d in {2, 3, 5}. Piecewise linear/quadratic means
    the in-cell secant IS the derivative; the tolerance is pure f32
    roundoff headroom."""
    model, _, x, _, pred = _frozen(d)
    xs = x[:80]
    g = predict_grad(pred, xs)
    ok = np.asarray(g.grad_ok)
    assert ok.sum() >= 40  # queries at train points are in-lattice
    used = 0
    for j in range(d):
        e = jnp.zeros(d, xs.dtype).at[j].set(FD_EPS)
        xp, xm = xs + e, xs - e
        keep = _same_cell(pred, model, xp, xm) & ok
        rp, rm = predict(pred, xp), predict(pred, xm)
        fdm = np.asarray((rp.mean - rm.mean) / (2 * FD_EPS))[keep]
        fdv = np.asarray((rp.var - rm.var) / (2 * FD_EPS))[keep]
        am = np.asarray(g.dmean[:, j])[keep]
        av = np.asarray(g.dvar[:, j])[keep]
        scale_m = np.maximum(np.abs(am), 1.0)
        scale_v = np.maximum(np.abs(av), 1.0)
        assert np.all(np.abs(fdm - am) / scale_m <= 1e-4), (d, j)
        assert np.all(np.abs(fdv - av) / scale_v <= 1e-4), (d, j)
        used += int(keep.sum())
    # the same-cell filter must not hollow the check out
    assert used >= 40 * d, used


def test_matches_dense_exact_gp_gradient():
    """Against the dense exact-GP analytic gradient oracle on a target
    much smoother than the lattice cell: globally unbiased (least-squares
    scale fit within 5% of 1) and pointwise aligned where the oracle
    gradient is strong (median relative error <= 0.2, median cosine
    >= 0.99). A missing 1/ls, transposed Jacobian, or sign flip fails
    all three fences; the allowed scatter is the documented O(cell)
    piecewise-linearization error (DESIGN.md §15)."""
    d, n = 2, 800
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.uniform(-4, 4, size=(n, d)), jnp.float32)
    y = jnp.sin(x[:, 0] * (2 * np.pi / 6.0)) \
        + 0.5 * jnp.cos(x[:, 1] * (2 * np.pi / 6.0))
    model = SimplexGP(TIGHT)
    params = GPParams.init(d, lengthscale=0.5, noise=0.01)
    pred = freeze(model, params, x, y, key=jax.random.PRNGKey(0),
                  variance_rank=10)
    xs = jnp.asarray(rng.uniform(-2.5, 2.5, size=(256, d)), jnp.float32)
    g = predict_grad(pred, xs)
    ls, os_, noise = model.constrained(params)
    oracle = exact_mean_grad(PROFILES["matern32"], x, y, xs,
                             lengthscale=ls, outputscale=os_, noise=noise)
    ok = np.asarray(g.grad_ok)
    gd, go = np.asarray(g.dmean)[ok], np.asarray(oracle)[ok]
    assert gd.shape[0] >= 200

    scale = float(np.sum(gd * go) / np.sum(go * go))
    assert 0.95 <= scale <= 1.05, scale

    mag = np.linalg.norm(go, axis=1)
    strong = mag >= np.median(mag)
    rel = np.linalg.norm(gd - go, axis=1)[strong] / mag[strong]
    assert np.median(rel) <= 0.2, np.median(rel)
    cos = np.sum(gd * go, axis=1) / (np.linalg.norm(gd, axis=1) * mag
                                     + 1e-12)
    assert np.median(cos[strong]) >= 0.99, np.median(cos[strong])


# -- analytic vs autodiff ----------------------------------------------------


def test_predict_grad_matches_jacfwd():
    """The fused analytic pass equals jax.jacfwd of the serving core —
    same custom JVP, no retrace, to f32 noise."""
    _, _, x, _, pred = _frozen(3)
    xs = x[:32]
    g = predict_grad(pred, xs)

    def core(q):
        mean, var, _ = _predict_core(pred, q[None, :], backend="slice_xla")
        return jnp.stack([mean[0], var[0]])

    jac = jax.vmap(jax.jacfwd(core))(xs)  # (b, 2, d)
    np.testing.assert_allclose(np.asarray(g.dmean), np.asarray(jac[:, 0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(g.dvar), np.asarray(jac[:, 1]),
                               atol=1e-6)


def test_reverse_mode_grad_through_predict():
    """jax.grad works through the frozen slice (the custom JVP is built
    from transposable XLA ops) and agrees with the analytic dmean."""
    _, _, x, _, pred = _frozen(3)
    xs = x[:16]

    def loss(q):
        mean, _, _ = _predict_core(pred, q, backend="slice_xla")
        return jnp.sum(mean)

    gr = jax.grad(loss)(xs)
    g = predict_grad(pred, xs)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(g.dmean),
                               atol=1e-6)


def test_tangent_xla_pallas_interpret_parity():
    """The fused Pallas tangent tier computes the same (out, out_dot,
    miss) as the XLA reference tier."""
    _, _, x, _, pred = _frozen(3)
    zq = x[:64] / pred.lengthscale[None, :]
    zdot = jnp.asarray(np.random.default_rng(3).normal(size=zq.shape),
                       jnp.float32)
    ox, dx_, mx = filtering.slice_only_tangent(
        pred.index, pred.tables, zq, zdot, spacing=pred.spacing,
        backend="slice_xla")
    op, dp, mp_ = filtering.slice_only_tangent(
        pred.index, pred.tables, zq, zdot, spacing=pred.spacing,
        backend="slice_pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(op), np.asarray(ox), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dx_), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(mp_), np.asarray(mx))


# -- multi-output freeze/serve -----------------------------------------------


def _multi_setup(k=3, n=300, d=3, rank=6, cap=4096):
    x, _ = _data(0, n, d)
    rng = np.random.default_rng(5)
    ys = jnp.asarray(rng.normal(size=(n, k)), jnp.float32) \
        + jnp.sin(x[:, :1] * jnp.arange(1, k + 1)[None, :])
    model = SimplexGP(TIGHT)
    params = GPParams.init(d, noise=0.3)
    key = jax.random.PRNGKey(7)
    return model, params, x, ys, key, cap, rank


def test_freeze_multi_bit_exact_vs_k_freezes():
    """One freeze_multi == k independent freeze() calls, bit for bit:
    same shared lattice, per-channel tables and alpha EXACTLY equal (the
    channels solve sequentially so CG stopping is identical — the
    documented reason freeze_multi does not batch the solves)."""
    model, params, x, ys, key, cap, rank = _multi_setup()
    k = ys.shape[1]
    mp = freeze_multi(model, params, x, ys, key=key, variance_rank=rank,
                      cap=cap)
    chan_keys = jax.random.split(key, k)
    r1 = mp.tables.shape[1] // k
    for j in range(k):
        pj = freeze(model, params, x, ys[:, j], key=chan_keys[j],
                    variance_rank=rank, cap=cap)
        np.testing.assert_array_equal(
            np.asarray(mp.tables[:, j * r1:(j + 1) * r1]),
            np.asarray(pj.tables))
        np.testing.assert_array_equal(np.asarray(mp.alpha[:, j]),
                                      np.asarray(pj.alpha))


def test_predict_multi_parity_and_grads():
    """predict_multi channel j == predict of the j-th single-channel
    Predictor (1-ulp fence: identical math, one reshape apart), and
    predict_multi_grad stacks per-channel predict_grad."""
    model, params, x, ys, key, cap, rank = _multi_setup()
    k = ys.shape[1]
    mp = freeze_multi(model, params, x, ys, key=key, variance_rank=rank,
                      cap=cap)
    xs = x[:48]
    mr = predict_multi(mp, xs)
    mg = predict_multi_grad(mp, xs)
    chan_keys = jax.random.split(key, k)
    for j in range(k):
        pj = freeze(model, params, x, ys[:, j], key=chan_keys[j],
                    variance_rank=rank, cap=cap)
        sr = predict(pj, xs)
        sg = predict_grad(pj, xs)
        np.testing.assert_allclose(np.asarray(mr.mean[:, j]),
                                   np.asarray(sr.mean), atol=1e-6)
        np.testing.assert_allclose(np.asarray(mr.var[:, j]),
                                   np.asarray(sr.var), atol=1e-6)
        np.testing.assert_allclose(np.asarray(mg.dmean[:, j]),
                                   np.asarray(sg.dmean), atol=1e-6)
        np.testing.assert_allclose(np.asarray(mg.dvar[:, j]),
                                   np.asarray(sg.dvar), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(mr.miss_mass),
                                  np.asarray(mg.miss_mass))


def test_multi_channel_serving_embeds_once():
    """The satellite op-count pin: tracing the k-channel serving core
    runs simplex_embed exactly ONCE per query batch — the channels share
    the embed/rank scratch and differ only in table columns."""
    model, params, x, ys, key, cap, rank = _multi_setup()
    mp = freeze_multi(model, params, x, ys, key=key, variance_rank=rank,
                      cap=cap)
    xs = x[:16]
    before = obs.counter("lattice.embeds")
    jax.make_jaxpr(
        lambda q: _predict_multi_core(mp, q, backend="slice_xla"))(xs)
    assert obs.counter("lattice.embeds") - before == 1
    # and the gradient pass too: one ranked embed serves primal + Jacobian
    before = obs.counter("lattice.embeds")
    jax.make_jaxpr(
        lambda q: filtering.slice_only_grad(mp.index, mp.tables,
                                            q, spacing=mp.spacing))(xs)
    assert obs.counter("lattice.embeds") - before == 1


# -- boundary / tie-break semantics ------------------------------------------


def test_boundary_tiebreak_deterministic():
    """On a simplex boundary the subgradient is the POSITIONAL tie-break
    of descending_rank: at a lattice vertex (full tie, z=0) the rank is
    arange(d+1), repeated and jitted evaluation is bit-identical, and the
    reported gradient is the one-sided derivative of that cell."""
    for d in (2, 3, 5):
        z0 = jnp.zeros((1, d), jnp.float32)
        _, _, rank = L.simplex_embed_ranked(z0, 1.0)
        np.testing.assert_array_equal(np.asarray(rank[0]),
                                      np.arange(d + 1))
    # tied differentials break by coordinate position (lower index first)
    diff = jnp.asarray([[0.5, 0.5, 0.5, 0.5]], jnp.float32)
    np.testing.assert_array_equal(np.asarray(L.descending_rank(diff)[0]),
                                  np.arange(4))
    _, _, x, _, pred = _frozen(3)
    # exact boundary query in x-space: a lattice vertex maps to z = 0
    xb = jnp.zeros((1, 3), jnp.float32)
    g1 = predict_grad(pred, xb)
    g2 = predict_grad(pred, xb)
    np.testing.assert_array_equal(np.asarray(g1.dmean), np.asarray(g2.dmean))
    g3 = jax.jit(lambda q: predict_grad(pred, q).dmean)(xb)
    np.testing.assert_array_equal(np.asarray(g1.dmean), np.asarray(g3))


# -- hypothesis-style properties ---------------------------------------------


@settings(max_examples=10)
@given(d=st.integers(2, 6), seed=st.integers(0, 10_000),
       scale=st.floats(0.1, 5.0))
def test_weight_jacobian_rows_sum_to_zero(d, seed, scale):
    """Barycentric weights sum to 1 identically, so every Jacobian row
    (summed over the d+1 vertices) is zero — for any cell, any rank
    pattern, any spacing regime the embed reaches."""
    rng = np.random.default_rng(seed)
    z = jnp.asarray(scale * rng.normal(size=(32, d)), jnp.float32)
    _, _, rank = L.simplex_embed_ranked(z, 1.0)
    jac = L.embed_weight_jacobian(rank, 1.0)  # (n, d+1, d)
    np.testing.assert_allclose(np.asarray(jac.sum(axis=1)), 0.0,
                               atol=2e-6 * scale)


@settings(max_examples=8)
@given(seed=st.integers(0, 10_000))
def test_gradient_locally_constant_within_cell(seed):
    """dmean is the slope of a piecewise-linear surface: CONSTANT within
    a cell — two queries in the same cell report it bit-close. dvar is
    the slope of a piecewise-QUADRATIC surface: affine within the cell,
    so it may drift proportionally to the in-cell shift (here 1e-3 with
    O(1) curvature) but no further."""
    model, _, x, _, pred = _frozen(3)
    rng = np.random.default_rng(seed)
    base = x[rng.integers(0, x.shape[0], size=24)]
    shift = base + jnp.asarray(1e-3 * rng.normal(size=base.shape),
                               jnp.float32)
    keep = _same_cell(pred, model, base, shift)
    ga, gb = predict_grad(pred, base), predict_grad(pred, shift)
    np.testing.assert_allclose(np.asarray(ga.dmean)[keep],
                               np.asarray(gb.dmean)[keep], atol=1e-5)
    np.testing.assert_allclose(np.asarray(ga.dvar)[keep],
                               np.asarray(gb.dvar)[keep], atol=1e-2)


@settings(max_examples=8)
@given(seed=st.integers(0, 10_000))
def test_gradients_permutation_invariant(seed):
    """Serving gradients are embarrassingly parallel: permuting the
    query batch permutes (mean, dmean, dvar, grad_ok) bit for bit."""
    _, _, x, _, pred = _frozen(3)
    xs = x[:64]
    perm = jnp.asarray(np.random.default_rng(seed).permutation(64))
    g = predict_grad(pred, xs)
    gp = predict_grad(pred, xs[perm])
    np.testing.assert_array_equal(np.asarray(g.dmean[perm]),
                                  np.asarray(gp.dmean))
    np.testing.assert_array_equal(np.asarray(g.dvar[perm]),
                                  np.asarray(gp.dvar))
    np.testing.assert_array_equal(np.asarray(g.grad_ok[perm]),
                                  np.asarray(gp.grad_ok))


@settings(max_examples=8)
@given(seed=st.integers(0, 10_000), shift=st.floats(50.0, 500.0))
def test_off_lattice_gradients_are_flagged(seed, shift):
    """grad_ok is exactly the miss_mass == 0 gate: off-lattice queries
    (which fall back toward the prior, a kinked surface) always report
    grad_ok=False; in-lattice train-point queries always pass."""
    _, _, x, _, pred = _frozen(3)
    far = x[:16] + jnp.float32(shift)
    g = predict_grad(pred, jnp.concatenate([x[16:32], far], axis=0))
    ok = np.asarray(g.grad_ok)
    miss = np.asarray(g.miss_mass)
    np.testing.assert_array_equal(ok, miss <= 0.0)
    assert not ok[16:].any()
    assert ok[:16].all()


# -- sharding: gradients stay zero-collective --------------------------------


def test_query_gradients_zero_collective():
    """The DESIGN.md §15 contract: d/dx* under the replicated-table mesh
    adds NO collectives — the table cotangent is partial-evaluated away
    (grad is taken w.r.t. the sharded queries only), so the gradient
    jaxpr is as collective-free as the forward serving jaxpr."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    _, _, x, _, pred = _frozen(3)
    mesh = data_mesh(1)

    def grad_core(p, q):
        f = lambda qq: jnp.sum(
            _predict_core(p, qq, backend="slice_xla")[0])
        return jax.grad(f)(q)

    fn = shard_map(grad_core, mesh=mesh, in_specs=(P(), P("data")),
                   out_specs=P("data"), check_vma=False)
    counts = collective_counts(fn, pred, jnp.zeros((64, 3), jnp.float32))
    assert all(v == 0 for v in counts.values()), counts
    # the fused analytic pass is likewise collective-free
    fn2 = shard_map(lambda p, q: predict_grad(p, q).dmean, mesh=mesh,
                    in_specs=(P(), P("data")), out_specs=P("data"),
                    check_vma=False)
    counts2 = collective_counts(fn2, pred,
                                jnp.zeros((64, 3), jnp.float32))
    assert all(v == 0 for v in counts2.values()), counts2
