"""Frozen-lattice serving (gp/serve.py + kernels/slice, DESIGN.md §12).

Pins the serving contract: (1) the frozen Predictor reproduces the
shared-lattice ``posterior`` on in-lattice queries once both CG solves
are converged (tight tolerance isolates the frozen math from CG stopping
noise); (2) off-lattice queries are fenced by the slice-miss diagnostic
— zero miss implies parity, full miss implies the prior; (3) serving is
embarrassingly parallel: permuting a batch permutes outputs bit-for-bit,
buckets don't change results, and the replicated-table mesh path is
collective-free and bit-identical.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import filtering
from repro.core import lattice as L
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, freeze,
                      posterior)
from repro.gp.serve import _predict_core, bucket_size, predict
from repro.sharding.simplex import collective_counts, data_mesh

TIGHT = SimplexGPConfig(kernel="matern32", cg_tol_eval=3e-7,
                        max_cg_iters=400)


def _data(rng, n, d):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = (jnp.sin(2 * x[:, 0]) + 0.4 * x[:, 1] * x[:, 2]
         + 0.05 * jnp.asarray(rng.normal(size=n), jnp.float32))
    return x, y


def _frozen(rng, n=500, d=3, cfg=TIGHT, rank=10):
    x, y = _data(rng, n, d)
    model = SimplexGP(cfg)
    # realistic noise level: keeps K_hat's condition number moderate, so
    # the two converged CG solves (train vs joint lattice, f32) agree to
    # well under the 1e-5 parity fence instead of sitting right on it
    params = GPParams.init(d, noise=0.3)
    key = jax.random.PRNGKey(0)
    pred = freeze(model, params, x, y, key=key, variance_rank=rank)
    return model, params, x, y, key, pred


def test_in_lattice_parity_vs_posterior(rng):
    """Mean <= 1e-5 and variance <= 1e-5 against the shared-lattice
    posterior on queries AT train points (their simplices are fully
    inside the frozen lattice, so the two paths compute the same
    quantity up to f32 noise)."""
    model, params, x, y, key, pred = _frozen(rng)
    xs = x[:64]
    sr = predict(pred, xs)
    post = posterior(model, params, x, y, xs, key=key, variance_rank=10)
    assert float(jnp.max(sr.miss_mass)) == 0.0
    np.testing.assert_allclose(np.asarray(sr.mean), np.asarray(post.mean),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sr.var), np.asarray(post.var),
                               atol=1e-5)


def test_off_lattice_fenced_by_miss_mass(rng):
    """The slice-miss diagnostic fences off-lattice behavior: fully
    off-lattice queries report miss 1 and fall back to the prior (zero
    mean, prior variance); zero-miss queries match the posterior to the
    in-lattice tolerance; everything in between stays bounded."""
    model, params, x, y, key, pred = _frozen(rng)
    os_ = float(pred.outputscale)

    far = x[:16] + 100.0
    sf = predict(pred, far)
    # all d+1 vertices miss: mass is the full weight sum (1 up to f32
    # normalization, clipped to the [0, 1] contract at the source)
    assert float(jnp.min(sf.miss_mass)) >= 1.0 - 1e-6
    assert float(jnp.max(sf.miss_mass)) <= 1.0
    np.testing.assert_allclose(np.asarray(sf.mean), 0.0, atol=0.0)
    np.testing.assert_allclose(np.asarray(sf.var), os_, atol=1e-6)

    near = x[:96] + 0.3
    sn = predict(pred, near)
    miss = np.asarray(sn.miss_mass)
    assert np.all((0.0 <= miss) & (miss <= 1.0))
    assert np.all(np.isfinite(np.asarray(sn.mean)))
    assert np.all((np.asarray(sn.var) > 0) & (np.asarray(sn.var) <= os_))
    # zero-miss queries add no lattice points, so a posterior over JUST
    # them runs on the same point set as the frozen lattice and must
    # agree; any miss > 0 query in the batch would refine the joint blur
    # graph and legitimately shift every prediction — exactly the hazard
    # the miss diagnostic exists to flag
    sel = miss == 0.0
    assert np.any(sel)
    xin = near[np.nonzero(sel)[0]]
    sin = predict(pred, xin)
    post = posterior(model, params, x, y, xin, key=key, variance_rank=10)
    np.testing.assert_allclose(np.asarray(sin.mean),
                               np.asarray(post.mean), atol=1e-5)


def test_permuting_queries_permutes_outputs(rng):
    """Serving is per-query independent: predict(xs[perm]) must equal
    predict(xs)[perm] BIT-FOR-BIT (same bucket, no cross-query state)."""
    _, _, x, _, _, pred = _frozen(rng, n=300)
    xs = jnp.asarray(rng.normal(size=(48, 3)), jnp.float32)
    base = predict(pred, xs)
    for seed in range(3):
        perm = np.random.default_rng(seed).permutation(48)
        out = predict(pred, xs[perm])
        assert bool(jnp.all(out.mean == base.mean[perm]))
        assert bool(jnp.all(out.var == base.var[perm]))
        assert bool(jnp.all(out.miss_mass == base.miss_mass[perm]))


def test_buckets_do_not_change_results(rng):
    """Different batch sizes land in different padding buckets; results
    for a given query must not depend on which bucket served it."""
    _, _, x, _, _, pred = _frozen(rng, n=300)
    xs = jnp.asarray(rng.normal(size=(70, 3)), jnp.float32)
    full = predict(pred, xs)  # bucket 256
    for b in (1, 7, 64, 65):  # buckets 64, 64, 64, 256
        part = predict(pred, xs[:b])
        assert part.mean.shape == (b,)
        assert bool(jnp.all(part.mean == full.mean[:b]))
        assert bool(jnp.all(part.var == full.var[:b]))
    assert bucket_size(1, (64, 256)) == 64
    assert bucket_size(65, (64, 256)) == 256
    assert bucket_size(300, (64, 256)) == 512  # pow2 growth past largest
    assert bucket_size(60, (64, 256), multiple=8) == 64
    assert bucket_size(65, (64,), multiple=3) == 129


def test_slice_pallas_interpret_matches_xla(rng):
    """The fused Pallas query kernel (interpret mode off-TPU) agrees with
    the XLA lookup+slice reference."""
    _, _, x, _, _, pred = _frozen(rng, n=300)
    zq = jnp.asarray(rng.normal(size=(40, 3)), jnp.float32)
    o_x, m_x = filtering.slice_only(pred.index, pred.tables, zq,
                                    spacing=pred.spacing,
                                    backend="slice_xla")
    o_p, m_p = filtering.slice_only(pred.index, pred.tables, zq,
                                    spacing=pred.spacing,
                                    backend="slice_pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_x))


def test_replicated_mesh_serving_zero_collectives(rng):
    """The DESIGN.md §12 serving contract: frozen tables replicated,
    queries sharded, ZERO collectives on the jaxpr, and results identical
    to single-device serving."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    _, _, x, _, _, pred = _frozen(rng, n=300)
    mesh = data_mesh(1)
    xs = x[:64]
    single = predict(pred, xs)
    sharded = predict(pred, xs, mesh=mesh)
    assert bool(jnp.all(single.mean == sharded.mean))
    assert bool(jnp.all(single.var == sharded.var))

    fn = shard_map(functools.partial(_predict_core, backend="slice_xla"),
                   mesh=mesh, in_specs=(P(), P("data")),
                   out_specs=P("data"), check_vma=False)
    counts = collective_counts(fn, pred, jnp.zeros((64, 3), jnp.float32))
    assert all(v == 0 for v in counts.values()), counts


def test_predictor_is_a_jit_safe_pytree(rng):
    """The Predictor round-trips through jit (serving runs inside jitted
    endpoints) and through tree flatten/unflatten (checkpointing)."""
    _, _, x, _, _, pred = _frozen(rng, n=300)
    leaves, treedef = jax.tree.flatten(pred)
    pred2 = jax.tree.unflatten(treedef, leaves)
    out = jax.jit(lambda p, q: _predict_core(p, q, backend="slice_xla"))(
        pred2, x[:16])
    assert out[0].shape == (16,)


def test_freeze_respects_cache_and_cap(rng):
    """freeze goes through LatticeCache when given one (no duplicate
    builds for the same point set) and honors an explicit cap."""
    x, y = _data(rng, 300, 3)
    model = SimplexGP(TIGHT)
    params = GPParams.init(3)
    key = jax.random.PRNGKey(0)
    cache = filtering.LatticeCache()
    c0 = obs.counter("lattice.builds")
    freeze(model, params, x, y, key=key, variance_rank=6, cap=2048,
           cache=cache)
    freeze(model, params, x, y, key=key, variance_rank=6, cap=2048,
           cache=cache)
    assert cache.hits == 1 and cache.misses == 1
    assert obs.counter("lattice.builds") - c0 == 1


def test_freeze_raises_on_overflowed_lattice(rng):
    """An under-capacity freeze must refuse to serve corrupt tables."""
    x, y = _data(rng, 400, 3)
    model = SimplexGP(TIGHT)
    with pytest.raises(RuntimeError, match="overflow"):
        freeze(model, GPParams.init(3), x, y, key=jax.random.PRNGKey(0),
               variance_rank=6, cap=8)
