"""Simplex-GP MVM vs the dense oracle (paper §3.1/§4.2; Fig 4 regime)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import filtering, kernels_math as km
from repro.core.lattice import build_lattice
from repro.core.stencil import make_stencil


def _data(rng, n, d, c=2):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    return x, v


def cosine_err(a, b):
    return 1.0 - float(jnp.vdot(a, b)
                       / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))


@pytest.mark.parametrize("d", [2, 4, 8])
@pytest.mark.parametrize("kernel", ["rbf", "matern32"])
def test_forward_matches_dense_oracle(rng, d, kernel):
    """Fig-4 regime: cosine error 1e-3..1e-1 at r=1.

    RBF is exactly separable across lattice directions, so it stays tight
    at high d; Matern is not, and its error grows with d (the paper's own
    Fig 4 spans up to ~1e-1)."""
    x, v = _data(rng, 500, d)
    st = make_stencil(kernel, 1)
    mv, lat = filtering.mvm_operator(x, st)
    ref = km.dense_mvm(km.get_profile(kernel), x, v)
    limit = 6e-2 if (kernel == "rbf" or d <= 4) else 2e-1
    assert cosine_err(mv(v), ref) < limit
    assert not bool(lat.overflow)


def test_order_tradeoff_not_monotone_claim(rng):
    """Fig 4's observation: higher r does not always reduce the error
    (blur truncation interacts with spacing) — but errors stay in the
    same decade."""
    x, v = _data(rng, 400, 3)
    errs = []
    for r in (1, 2, 3):
        st = make_stencil("rbf", r)
        mv, _ = filtering.mvm_operator(x, st)
        errs.append(cosine_err(mv(v), km.dense_mvm(km.RBF, x, v)))
    assert max(errs) < 10 * min(errs)
    assert max(errs) < 1e-1


def test_symmetrized_operator_is_symmetric(rng):
    x, _ = _data(rng, 300, 3)
    st = make_stencil("matern32", 1)
    mv, _ = filtering.mvm_operator(x, st, symmetrize=True)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(300, 1)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(2).normal(size=(300, 1)),
                    jnp.float32)
    lhs = float(jnp.vdot(w, mv(u)))
    rhs = float(jnp.vdot(u, mv(w)))
    assert abs(lhs - rhs) < 1e-3 * max(abs(lhs), 1.0)


def test_transpose_operator(rng):
    """filter_mvm_t is the exact adjoint of filter_mvm (unsymmetrized)."""
    x, _ = _data(rng, 250, 4)
    st = make_stencil("rbf", 1)
    lat = build_lattice(x, spacing=st.spacing, r=1)
    w = jnp.asarray(st.weights, jnp.float32)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(250, 2)),
                    jnp.float32)
    v = jnp.asarray(np.random.default_rng(4).normal(size=(250, 2)),
                    jnp.float32)
    fu = filtering.filter_mvm(lat, u, w, symmetrize=False)
    ftv = filtering.filter_mvm_t(lat, v, w, symmetrize=False)
    np.testing.assert_allclose(float(jnp.vdot(v, fu)),
                               float(jnp.vdot(u, ftv)), rtol=1e-4)


@pytest.mark.parametrize("kernel,r", [("rbf", 1), ("matern32", 2)])
def test_custom_vjp_dv_is_transpose(rng, kernel, r):
    """dL/dv through the custom VJP == F^T g exactly."""
    x, v = _data(rng, 200, 3)
    g = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    st = make_stencil(kernel, r)
    spec = filtering.spec_for(st)
    w = jnp.asarray(st.weights, jnp.float32)
    dw = jnp.asarray(st.dweights, jnp.float32)
    _, vjp = jax.vjp(lambda vv: filtering.lattice_filter(x, vv, w, dw,
                                                         spec), v)
    (dv,) = vjp(g)
    lat = build_lattice(x, spacing=st.spacing, r=r)
    want = filtering.filter_mvm_t(lat, g, w, symmetrize=spec.symmetrize)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kernel", ["rbf", "matern32"])
def test_paper_gradient_direction(rng, kernel):
    """§4.2 input-space gradient aligns with the dense-oracle gradient."""
    n, d, c = 300, 3, 2
    x, v = _data(rng, n, d, c)
    g = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    st = make_stencil(kernel, 2)
    spec = filtering.spec_for(st)
    w = jnp.asarray(st.weights, jnp.float32)
    dw = jnp.asarray(st.dweights, jnp.float32)
    dz = jax.grad(lambda z: jnp.vdot(
        g, filtering.lattice_filter(z, v, w, dw, spec)))(x)
    dz_ref = km.dense_grad_x(km.get_profile(kernel), x, v, g)
    cos = float(jnp.vdot(dz, dz_ref)
                / (jnp.linalg.norm(dz) * jnp.linalg.norm(dz_ref)))
    assert cos > 0.9


def test_autodiff_through_barycentric_weights(rng):
    """Beyond-paper grad mode: autodiff through the lattice operator runs
    and produces finite, nonzero gradients."""
    x, v = _data(rng, 200, 3)
    st = make_stencil("rbf", 1)
    w = jnp.asarray(st.weights, jnp.float32)

    def f(z):
        lat = build_lattice(z, spacing=st.spacing, r=1)
        return jnp.sum(filtering.filter_mvm(lat, v, w) ** 2)

    dz = jax.grad(f)(x)
    assert bool(jnp.all(jnp.isfinite(dz)))
    assert float(jnp.linalg.norm(dz)) > 0


def test_pallas_blur_path_matches_default(rng):
    x, v = _data(rng, 200, 3)
    st = make_stencil("rbf", 1)
    lat = build_lattice(x, spacing=st.spacing, r=1)
    w = jnp.asarray(st.weights, jnp.float32)
    a = filtering.filter_mvm(lat, v, w, backend="xla")
    b = filtering.filter_mvm(lat, v, w, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "fused_xla",
                                     "per_direction_pallas"])
def test_backend_tiers_agree(rng, backend):
    """Every dispatch tier computes the same operator (f32 noise apart)."""
    x, v = _data(rng, 250, 4)
    st = make_stencil("matern32", 1)
    lat = build_lattice(x, spacing=st.spacing, r=1)
    w = jnp.asarray(st.weights, jnp.float32)
    want = filtering.filter_mvm(lat, v, w, backend="xla")
    got = filtering.filter_mvm(lat, v, w, backend=backend,
                               taps=tuple(st.weights))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_filter_mvm_traced_weights_under_jit(rng):
    """Regression: traced weights under jit must not crash any backend
    resolution — the seed's use_pallas path called float() on tracers."""
    x, v = _data(rng, 150, 3)
    st = make_stencil("rbf", 1)
    lat = build_lattice(x, spacing=st.spacing, r=1)
    w = jnp.asarray(st.weights, jnp.float32)

    # auto: falls back to a taps-free tier instead of crashing
    got = jax.jit(lambda ww, vv: filtering.filter_mvm(lat, vv, ww))(w, v)
    want = filtering.filter_mvm(lat, v, w, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)

    # concrete taps via FilterSpec keep the Pallas tiers jit-compatible
    got2 = jax.jit(lambda ww, vv: filtering.filter_mvm(
        lat, vv, ww, use_pallas=True, taps=tuple(st.weights)))(w, v)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=1e-4, atol=1e-5)

    # a Pallas tier with ONLY traced weights is a loud error, not a crash
    with pytest.raises(ValueError, match="concrete stencil taps"):
        jax.jit(lambda ww, vv: filtering.filter_mvm(
            lat, vv, ww, backend="per_direction_pallas"))(w, v)


def test_lattice_filter_with_matches_rebuild(rng):
    """Shared-lattice entry point == rebuild-per-call: values AND §4.2
    grads (acceptance: max abs err <= 1e-6; in fact bit-identical, since
    the build is deterministic)."""
    x, v = _data(rng, 250, 3)
    g = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    st = make_stencil("matern32", 1)
    spec = filtering.spec_for(st)
    w = jnp.asarray(st.weights, jnp.float32)
    dw = jnp.asarray(st.dweights, jnp.float32)
    lat = build_lattice(x, spacing=st.spacing, r=st.r)

    a = filtering.lattice_filter(x, v, w, dw, spec)
    b = filtering.lattice_filter_with(lat, x, v, w, dw, spec)
    assert float(jnp.max(jnp.abs(a - b))) <= 1e-6

    f_re = lambda z, vv: jnp.vdot(g, filtering.lattice_filter(
        z, vv, w, dw, spec))
    f_sh = lambda z, vv: jnp.vdot(g, filtering.lattice_filter_with(
        lat, z, vv, w, dw, spec))
    dz_re, dv_re = jax.grad(f_re, argnums=(0, 1))(x, v)
    dz_sh, dv_sh = jax.grad(f_sh, argnums=(0, 1))(x, v)
    np.testing.assert_allclose(np.asarray(dz_sh), np.asarray(dz_re),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dv_sh), np.asarray(dv_re),
                               rtol=0, atol=1e-6)


def test_lattice_filter_with_jit_traced_lattice(rng):
    """The prebuilt-lattice VJP works with the lattice as a traced pytree
    (the in-jit training-step usage) and performs zero builds."""

    x, v = _data(rng, 150, 3)
    g = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    st = make_stencil("rbf", 1)
    spec = filtering.spec_for(st)
    w = jnp.asarray(st.weights, jnp.float32)
    dw = jnp.asarray(st.dweights, jnp.float32)
    lat = build_lattice(x, spacing=st.spacing, r=st.r)

    @jax.jit
    def grad_z(lt, z, vv):
        return jax.grad(lambda zz: jnp.vdot(g, filtering.lattice_filter_with(
            lt, zz, vv, w, dw, spec)))(z)

    c0 = obs.counter("lattice.builds")
    dz = grad_z(lat, x, v)
    assert obs.counter("lattice.builds") - c0 == 0
    want = jax.grad(lambda zz: jnp.vdot(g, filtering.lattice_filter(
        zz, v, w, dw, spec)))(x)
    np.testing.assert_allclose(np.asarray(dz), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lattice_cache_reuses_builds(rng):
    """Same concrete (point set, lengthscale, spacing, r, cap) -> one build;
    any key change -> fresh build; traced inputs bypass the memo."""

    x, _ = _data(rng, 120, 3)
    st = make_stencil("rbf", 1)
    cache = filtering.LatticeCache()
    tag = cache.point_set_tag(x)
    ls = jnp.ones((3,), jnp.float32)

    c0 = obs.counter("lattice.builds")
    l1 = cache.get(tag, x, spacing=st.spacing, r=st.r, cap=None, ls=ls)
    l2 = cache.get(tag, x, spacing=st.spacing, r=st.r, cap=None, ls=ls)
    assert l1 is l2
    assert obs.counter("lattice.builds") - c0 == 1
    assert cache.hits == 1 and cache.misses == 1

    # lengthscale moved -> rebuild
    l3 = cache.get(tag, x, spacing=st.spacing, r=st.r, cap=None,
                   ls=2.0 * ls)
    assert l3 is not l1
    assert obs.counter("lattice.builds") - c0 == 2

    # traced lengthscale -> bypass (fresh build, nothing cached)
    jax.jit(lambda s: cache.get(tag, x, spacing=st.spacing, r=st.r,
                                cap=None, ls=s).weights)(ls)
    assert cache.misses == 2  # unchanged by the traced call

    # traced points -> tag is None -> bypass (no crash under jit)
    jax.jit(lambda xx: cache.get(cache.point_set_tag(xx), xx,
                                 spacing=st.spacing, r=st.r, cap=None,
                                 ls=ls).weights)(x)
    assert cache.misses == 2

    # row order matters: the lattice's seg_ids/splat plan are
    # order-dependent, so a permuted point set must NOT hit the cache
    perm = x[::-1]
    assert cache.point_set_tag(perm) != tag
    l4 = cache.get(cache.point_set_tag(perm), perm, spacing=st.spacing,
                   r=st.r, cap=None, ls=ls)
    assert l4 is not l1


def test_lattice_cache_keys_on_sharding_layout(rng):
    """Regression (PR 3): the cache fingerprint includes the device/
    sharding layout, so a lattice built from a mesh-sharded ``z`` never
    aliases the unsharded build of the same bytes (the built arrays
    inherit z's placement — serving the wrong one silently resharded
    every MVM)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    x, _ = _data(rng, 64, 2)
    st = make_stencil("rbf", 1)
    cache = filtering.LatticeCache()
    tag = cache.point_set_tag(x)
    ls = jnp.ones((2,), jnp.float32)

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x_sharded = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    assert cache.point_set_tag(x_sharded) == tag  # same bytes, same tag
    assert (cache.layout_key(x_sharded) != cache.layout_key(x))

    l1 = cache.get(tag, x, spacing=st.spacing, r=st.r, cap=None, ls=ls)
    l2 = cache.get(tag, x_sharded, spacing=st.spacing, r=st.r, cap=None,
                   ls=ls)
    assert l2 is not l1  # layout differs -> distinct cache entries
    assert cache.misses == 2 and cache.hits == 0
    # and each layout still hits its own entry
    assert cache.get(tag, x, spacing=st.spacing, r=st.r, cap=None,
                     ls=ls) is l1
    assert cache.get(tag, x_sharded, spacing=st.spacing, r=st.r, cap=None,
                     ls=ls) is l2
    assert cache.hits == 2


def test_mvm_operator_auto_cap_and_backends(rng):
    """auto_cap right-sizes the table; fused backend matches the default."""
    from repro.core.lattice import default_capacity, suggest_capacity

    x, v = _data(rng, 300, 4, c=1)
    st = make_stencil("matern32", 1)
    mv, lat = filtering.mvm_operator(x, st, auto_cap=True)
    assert not bool(lat.overflow)
    assert lat.cap < default_capacity(300, 4)
    assert lat.cap >= int(lat.m)
    assert suggest_capacity(300, 4, st.spacing) <= default_capacity(300, 4)
    mv_ref, lat_ref = filtering.mvm_operator(x, st, backend="xla")
    np.testing.assert_allclose(np.asarray(mv(v)), np.asarray(mv_ref(v)),
                               rtol=1e-4, atol=1e-5)


def test_grow_and_retry_recovers_from_overflow(rng):
    """build_lattice_auto grows past an undersized initial capacity."""
    from repro.core.lattice import build_lattice_auto

    x = jnp.asarray(rng.normal(size=(400, 3)) * 4.0, jnp.float32)
    lat = build_lattice_auto(x, spacing=0.5, r=1, cap=16)
    assert not bool(lat.overflow)
    assert lat.cap >= int(lat.m)


def test_suggest_capacity_vmem_aware_rounding():
    """Regression: the power-of-two rounding must not silently pick a cap
    that defeats ``fits_vmem`` when the unrounded occupancy guess fits —
    that spill cost the fused-MVM tier for no occupancy benefit."""
    from repro.core.lattice import default_capacity, suggest_capacity
    from repro.kernels.blur.ops import fits_vmem, max_cap_for_vmem

    # find a size where the raw guess fits the fused VMEM plan but its
    # power-of-two round-up does not (exists: the plan is linear in cap)
    found = None
    for n in range(20000, 70000, 500):
        for d in (4, 8):
            guess = max(1024, int(n * (d + 1) / 8.0))
            pow2 = min(1 << (guess - 1).bit_length(), default_capacity(n, d))
            if fits_vmem(n, d, 1, guess + 1, 1) and \
                    not fits_vmem(n, d, 1, pow2 + 1, 1):
                found = (n, d, guess, pow2)
                break
        if found:
            break
    assert found is not None, "no spill-prone size in scan range"
    n, d, guess, pow2 = found

    cap = suggest_capacity(n, d, 1.0, r=1, c=1)
    assert cap < pow2  # the naive round-up was rejected
    assert cap >= guess  # never below the occupancy guess
    assert fits_vmem(n, d, 1, cap + 1, 1)  # and the clamp actually fits
    # the clamp target is exactly the largest fitting capacity
    assert fits_vmem(n, d, 1, max_cap_for_vmem(n, d, 1, 1) + 1, 1)
    assert not fits_vmem(n, d, 1, max_cap_for_vmem(n, d, 1, 1) + 2, 1)
    # opting out restores the plain power-of-two suggestion
    assert suggest_capacity(n, d, 1.0, r=1, c=1, vmem_aware=False) == pow2
    # a guess that itself spills is returned un-clamped (occupancy first)
    big_n = 200000
    cap_big = suggest_capacity(big_n, 8, 1.0, r=1, c=1)
    assert cap_big == min(1 << (max(1024, int(big_n * 9 / 8.0))
                                - 1).bit_length(),
                          default_capacity(big_n, 8))


def test_estimate_m_exact_on_full_sample(rng):
    """With sample >= n the estimator degenerates to an exact count."""
    from repro.core.lattice import build_lattice_auto, estimate_m

    z = jnp.asarray(rng.normal(size=(400, 3)), jnp.float32)
    lat = build_lattice_auto(z, spacing=1.0, r=1)
    assert estimate_m(z, 1.0, sample=400) == int(lat.m)


def test_estimate_m_multiscale_no_severe_underestimate(rng):
    """Regression for the 2-point estimator's multi-scale failure: tight
    clusters saturate small subsamples, so the single average slope
    underestimated m and the resulting cap paid a grow-and-retry
    rebuild. The 3-point fit's monotonicity check (convex log-log growth
    -> trust the tail slope) must keep the estimate near the true m."""
    from repro.core.lattice import build_lattice_auto, estimate_m

    n, d = 4000, 3
    n_bg = n // 10  # sparse background carries most distinct vertices
    z = np.concatenate([rng.normal(size=(n - n_bg, d)) * 0.05,
                        rng.normal(size=(n_bg, d)) * 20.0])
    z = jnp.asarray(z[rng.permutation(n)], jnp.float32)
    m = int(build_lattice_auto(z, spacing=1.0, r=1).m)
    assert estimate_m(z, 1.0, sample=512) >= 0.55 * m
    assert estimate_m(z, 1.0, sample=1024) >= 0.8 * m
    # ... without wrecking the uniform case with overestimates
    z2 = jnp.asarray(rng.normal(size=(n, d)) * 3.0, jnp.float32)
    m2 = int(build_lattice_auto(z2, spacing=1.0, r=1).m)
    assert estimate_m(z2, 1.0, sample=512) <= 3.0 * m2


def test_suggest_capacity_data_aware_tightens(rng):
    """The subsample-insert estimate right-sizes the cap on clustered
    data (where the constant-occupancy guess over-allocates heavily) and
    still covers the true m; the blind guess is unchanged without z."""
    from repro.core.lattice import (build_lattice_auto, default_capacity,
                                    suggest_capacity)

    n, d = 2000, 4
    # tightly clustered: very few occupied lattice points
    z = jnp.asarray(rng.normal(size=(n, d)) * 0.05, jnp.float32)
    lat = build_lattice_auto(z, spacing=1.0, r=1)
    m = int(lat.m)
    cap_blind = suggest_capacity(n, d, 1.0)
    cap_data = suggest_capacity(n, d, 1.0, z=z)
    assert m <= cap_data <= cap_blind
    assert cap_data < cap_blind  # actually tighter on this data
    assert cap_data <= default_capacity(n, d)
    # auto build (which now threads z through) lands on the tight cap
    assert lat.cap == cap_data
    assert not bool(lat.overflow)


def test_suggest_capacity_data_aware_underestimate_recovers(rng):
    """A low estimate is harmless: build_lattice_auto's grow-and-retry
    catches the overflow. (Sparse data where a small subsample badly
    under-predicts fresh-vertex growth.)"""
    from repro.core.lattice import build_lattice_auto

    z = jnp.asarray(rng.normal(size=(3000, 4)) * 3.0, jnp.float32)
    lat = build_lattice_auto(z, spacing=0.5, r=1)
    assert not bool(lat.overflow)
    assert lat.cap >= int(lat.m)
