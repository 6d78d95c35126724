"""Permutohedral lattice geometry invariants (paper §3.2)."""
from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import lattice as L

DIMS = [1, 2, 3, 5, 8, 11]


def _points(rng, n, d, scale=1.0):
    return jnp.asarray(rng.normal(size=(n, d)) * scale, jnp.float32)


@pytest.mark.parametrize("d", DIMS)
def test_elevation_is_isometry(rng, d):
    """The scaled triangular elevation preserves distances (x alpha)."""
    x = _points(rng, 64, d)
    spacing = 1.3
    el = L.elevate(x, spacing)
    # rows sum to ~0 (lies in H_d)
    np.testing.assert_allclose(np.asarray(jnp.sum(el, axis=1)), 0.0,
                               atol=2e-3 * d)
    alpha = L.step_scale(d, spacing)
    d_in = np.linalg.norm(np.asarray(x[:1] - x[1:2]))
    d_el = np.linalg.norm(np.asarray(el[:1] - el[1:2]))
    assert abs(d_el / d_in - alpha) < 1e-3 * alpha


@pytest.mark.parametrize("d", DIMS)
def test_simplex_embed_invariants(rng, d):
    x = _points(rng, 256, d)
    keys, w = L.simplex_embed(x, spacing=1.0)
    w = np.asarray(w)
    keys = np.asarray(keys)
    # barycentric weights: sum to 1, in [0, 1]
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-4)
    assert w.min() > -1e-4 and w.max() < 1 + 1e-4
    # every vertex key lies on the lattice plane sum == 0
    assert (keys.sum(-1) == 0).all()
    # vertices have distinct remainders 0..d (mod d+1) — permutohedral
    rem = np.sort(keys[..., 0] % (d + 1), axis=1)
    np.testing.assert_array_equal(rem, np.arange(d + 1)[None, :]
                                  .repeat(keys.shape[0], 0))
    # barycentric reconstruction: sum_k w_k key_k ~= elevated point
    el = np.asarray(L.elevate(x, 1.0))
    recon = np.einsum("nkj,nk->nj", keys.astype(np.float64), w)
    np.testing.assert_allclose(recon, el, atol=5e-2 * max(d, 2))


@pytest.mark.parametrize("d", [2, 4, 7])
def test_dedup_matches_numpy_unique(rng, d):
    x = _points(rng, 300, d)
    lat = L.build_lattice(x, spacing=1.0, r=1)
    keys, _ = L.simplex_embed(x, spacing=1.0)
    uniq = np.unique(np.asarray(keys).reshape(-1, d + 1), axis=0)
    assert int(lat.m) == uniq.shape[0]
    assert not bool(lat.overflow)
    got = np.asarray(lat.coords)[np.asarray(lat.valid)]
    got = got[np.lexsort(got.T[::-1])]
    np.testing.assert_array_equal(got, uniq)


@pytest.mark.parametrize("d,r", [(2, 1), (3, 2), (6, 1)])
def test_neighbor_table_offsets(rng, d, r):
    x = _points(rng, 200, d)
    lat = L.build_lattice(x, spacing=1.0, r=r)
    coords = np.asarray(lat.coords)
    valid = np.asarray(lat.valid)
    nbr = np.asarray(lat.nbr)  # (d+1, cap+1, 2r)
    eye = np.eye(d + 1, dtype=np.int64)
    steps = np.concatenate([np.arange(-r, 0), np.arange(1, r + 1)])
    coord_set = {tuple(c) for c in coords[valid]}
    for a in range(d + 1):
        dirv = (d + 1) * eye[a] - 1
        for p in np.flatnonzero(valid)[:50]:
            for si, s in enumerate(steps):
                want = tuple(coords[p] + s * dirv)
                j = nbr[a, p, si]
                if j == lat.cap:  # miss: must really be absent
                    assert want not in coord_set
                else:
                    assert tuple(coords[j]) == want


def test_overflow_flag(rng):
    x = _points(rng, 128, 3, scale=5.0)
    lat = L.build_lattice(x, spacing=0.5, r=1, cap=8)
    assert bool(lat.overflow)


def test_capacity_default():
    assert L.default_capacity(100, 7) == 800


@settings(max_examples=25, deadline=None)
@given(d=st.integers(1, 6), seed=st.integers(0, 2 ** 16),
       scale=st.floats(0.1, 10.0))
def test_property_weights_and_plane(d, seed, scale):
    """Hypothesis: invariants hold for arbitrary dims/scales/seeds."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(32, d)) * scale, jnp.float32)
    keys, w = L.simplex_embed(x, spacing=1.0)
    w = np.asarray(w)
    assert np.all(np.abs(w.sum(1) - 1.0) < 1e-3)
    assert w.min() > -1e-3
    assert (np.asarray(keys).sum(-1) == 0).all()


@settings(max_examples=10, deadline=None)
@given(d=st.integers(1, 5), seed=st.integers(0, 999))
def test_property_splat_slice_mass(d, seed):
    """splat^T preserves total mass: sum(splat(v)) == sum(v)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(50, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(50, 2)), jnp.float32)
    lat = L.build_lattice(x, spacing=1.0, r=1)
    splatted = L.splat(lat, v)
    np.testing.assert_allclose(np.asarray(jnp.sum(splatted, axis=0)),
                               np.asarray(jnp.sum(v, axis=0)), rtol=2e-4,
                               atol=1e-4)


@pytest.mark.parametrize("d", DIMS)
def test_pack_unpack_roundtrip(rng, d):
    """C2 fast build path: the packed sort keys are lossless, so coords can
    be reconstructed from them after the dedup sort (no payload columns)."""
    keys = jnp.asarray(rng.integers(-500, 500, size=(200, d)), jnp.int32)
    keys = jnp.concatenate([keys, -jnp.sum(keys, axis=1, keepdims=True)],
                           axis=1)  # zero-sum like real lattice coords
    packed = jnp.stack(L._pack_key_cols(keys), axis=1)
    got = L._unpack_key_cols(packed, d + 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(keys))


def test_build_count_increments(rng):
    x = _points(rng, 40, 3)
    c0 = obs.counter("lattice.builds")
    L.build_lattice(x, spacing=1.0, r=1)
    L.build_lattice(x, spacing=1.0, r=1)
    assert obs.counter("lattice.builds") - c0 == 2


def test_pack_overflow_flag_distinct_from_capacity(rng):
    """Coordinate-range overflow sets BOTH flags (results invalid) and is
    reported separately, since growing cap cannot fix it; a plain capacity
    overflow leaves pack_overflow clear."""
    x = _points(rng, 64, 2, scale=5.0)
    lat = L.build_lattice(x, spacing=0.5, r=1, cap=8)
    assert bool(lat.overflow) and not bool(lat.pack_overflow)

    far = _points(rng, 64, 2, scale=3e4)  # coords blow past +/-2^15
    lat2 = L.build_lattice(far, spacing=0.5, r=1)
    assert bool(lat2.pack_overflow) and bool(lat2.overflow)
    # build_lattice_auto must not burn retries growing an unfixable table
    lat3 = L.build_lattice_auto(far, spacing=0.5, r=1, cap=16)
    assert bool(lat3.pack_overflow)
    assert lat3.cap <= 64  # no useless growth
