"""Property tests for permutohedral-lattice invariants (hypothesis-style).

Four families from the build's contract:

  * barycentric weights are a valid simplex point (nonneg, sum to 1) and
    the vertex keys live on the lattice plane (coords sum to 0 mod d+1);
  * the dedup/build is permutation-invariant over input rows: the deduped
    point SET and the filtering OPERATOR commute with row permutations;
  * the 16-bit key packing round-trips exactly within its documented
    range (the last coordinate is recovered from the zero-sum constraint);
  * adversarial inputs raise the overflow/pack_overflow FLAGS instead of
    silently corrupting the table.
"""
from hypothesis import given, settings, strategies as st
import jax.numpy as jnp
import numpy as np

from repro.core import lattice as lat_mod
from repro.core.stencil import make_stencil


def _points(seed, n, d, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(scale * rng.normal(size=(n, d)), jnp.float32)


@settings(max_examples=15)
@given(d=st.integers(1, 6), seed=st.integers(0, 10_000),
       scale=st.floats(0.05, 20.0))
def test_barycentric_weights_are_simplex_point(d, seed, scale):
    z = _points(seed, 40, d, scale)
    keys, w = lat_mod.simplex_embed(z, spacing=1.0)
    w = np.asarray(w)
    assert np.all(w >= -1e-4), w.min()
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-4)
    # vertex keys live on the lattice plane: coords sum to zero
    sums = np.asarray(keys).sum(axis=-1)
    assert np.all(sums == 0), np.unique(sums)


@settings(max_examples=10)
@given(d=st.integers(1, 5), seed=st.integers(0, 10_000))
def test_build_is_permutation_invariant(d, seed):
    """Permuting input rows permutes the operator: the deduped point set
    is identical and F(P v) == P F(v) (the lattice has no row-order
    dependence beyond the per-point bookkeeping)."""
    rng = np.random.default_rng(seed)
    n = 48
    z = _points(seed, n, d)
    perm = jnp.asarray(rng.permutation(n))
    st_ = make_stencil("matern32", 1)
    lat = lat_mod.build_lattice(z, spacing=st_.spacing, r=st_.r)
    lat_p = lat_mod.build_lattice(z[perm], spacing=st_.spacing, r=st_.r)

    assert int(lat.m) == int(lat_p.m)
    coords = np.asarray(lat.coords)[np.asarray(lat.valid)]
    coords_p = np.asarray(lat_p.coords)[np.asarray(lat_p.valid)]
    as_set = lambda c: set(map(tuple, c.tolist()))
    assert as_set(coords) == as_set(coords_p)

    v = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    w = jnp.asarray(st_.weights, jnp.float32)
    from repro.kernels.blur.ops import lattice_mvm
    out = lattice_mvm(lat, v, w, backend="xla")
    out_p = lattice_mvm(lat_p, v[perm], w, backend="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out)[perm],
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=20)
@given(d=st.integers(1, 8), seed=st.integers(0, 10_000),
       magnitude=st.integers(1, lat_mod._PACK_LIMIT))
def test_unpack_key_cols_roundtrip(d, seed, magnitude):
    """_unpack_key_cols is the exact inverse of _pack_key_cols within the
    +/- 2^15 - 2 range, for any coordinate count (odd and even packing)."""
    rng = np.random.default_rng(seed)
    c = d + 1
    rest = rng.integers(-magnitude, magnitude + 1, size=(32, d))
    keys = np.concatenate([rest, -rest.sum(axis=1, keepdims=True)], axis=1)
    packed = jnp.stack(lat_mod._pack_key_cols(jnp.asarray(keys, jnp.int32)),
                       axis=1)
    back = lat_mod._unpack_key_cols(packed, c)
    np.testing.assert_array_equal(np.asarray(back), keys)


@settings(max_examples=10)
@given(seed=st.integers(0, 10_000), d=st.integers(1, 4))
def test_capacity_overflow_flag_fires(seed, d):
    """More unique lattice points than cap -> overflow set, pack_overflow
    clear, and the table stays structurally sound (dump row exists,
    seg_ids in range) instead of silently corrupting."""
    z = _points(seed, 64, d, scale=30.0)  # spread -> many unique points
    lat = lat_mod.build_lattice(z, spacing=0.5, r=1, cap=4)
    assert bool(lat.overflow)
    assert not bool(lat.pack_overflow)
    seg = np.asarray(lat.seg_ids)
    assert seg.min() >= 0 and seg.max() <= lat.cap
    assert lat.coords.shape == (lat.cap + 1, d + 1)


@settings(max_examples=10)
@given(seed=st.integers(0, 10_000), scale=st.floats(3e4, 3e5))
def test_pack_overflow_flag_fires(seed, scale):
    """Coordinates beyond +/- 2^15 set pack_overflow AND overflow (results
    invalid; growing cap cannot fix it) — the grow-and-retry contract's
    hard stop."""
    z = _points(seed, 16, 2, scale=scale)
    lat = lat_mod.build_lattice(z, spacing=0.5, r=1)
    assert bool(lat.pack_overflow)
    assert bool(lat.overflow)
    # build_lattice_auto must NOT grow its way out of a pack overflow
    lat_auto = lat_mod.build_lattice_auto(z, spacing=0.5, r=1, cap=8)
    assert bool(lat_auto.pack_overflow)
    assert lat_auto.cap <= lat_mod.default_capacity(16, 2)
