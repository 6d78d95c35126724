"""Hash-build vs sort-build equivalence suite (DESIGN.md §11).

The sort build is the bit-exact lex-ordered oracle; the hash build must
produce an operator-equivalent lattice: identical deduplicated point SET
and exact m, per-row slot->coordinate mapping, a neighbor graph that
matches through the slot permutation, MVM parity <= 1e-6 across
backends, permutation invariance, and identical overflow/pack_overflow
semantics — including collision-heavy key sets and >90% occupancy.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import lattice as L
from repro.core.stencil import make_stencil
from repro.kernels.blur.ops import lattice_mvm
from repro.kernels.hash import ops as hash_ops
from repro.kernels.hash import ref as hash_ref


def _points(rng, n, d, scale=1.0):
    return jnp.asarray(rng.normal(size=(n, d)) * scale, jnp.float32)


def _pair(x, *, spacing=1.0, r=1, cap=None):
    lat_s = L.build_lattice(x, spacing=spacing, r=r, cap=cap,
                            backend="sort")
    lat_h = L.build_lattice(x, spacing=spacing, r=r, cap=cap,
                            backend="hash_xla")
    return lat_s, lat_h


def _coord_set(lat):
    return set(map(tuple,
                   np.asarray(lat.coords)[np.asarray(lat.valid)].tolist()))


def _assert_structural_equiv(lat_s, lat_h):
    """Same dedup result and neighbor graph, up to slot permutation."""
    assert int(lat_s.m) == int(lat_h.m)
    assert bool(lat_s.overflow) == bool(lat_h.overflow)
    assert bool(lat_s.pack_overflow) == bool(lat_h.pack_overflow)
    assert _coord_set(lat_s) == _coord_set(lat_h)
    # every (input, vertex) row resolves to the same coordinates
    a = np.asarray(lat_s.coords)[np.asarray(lat_s.seg_ids)]
    b = np.asarray(lat_h.coords)[np.asarray(lat_h.seg_ids)]
    np.testing.assert_array_equal(a, b)
    # neighbor tables match through the coordinate-keyed slot permutation
    cap = lat_s.cap
    cs, ch = np.asarray(lat_s.coords), np.asarray(lat_h.coords)
    vs, vh = np.asarray(lat_s.valid), np.asarray(lat_h.valid)
    sort_slot = {tuple(cs[i]): i for i in np.flatnonzero(vs)}
    hv = np.flatnonzero(vh)
    h2s = np.full(cap + 1, cap, np.int64)
    for i in hv:
        h2s[i] = sort_slot[tuple(ch[i])]
    nb_s, nb_h = np.asarray(lat_s.nbr), np.asarray(lat_h.nbr)
    for a_ in range(lat_s.d + 1):
        lhs = np.where(nb_h[a_, hv] == cap, cap, h2s[nb_h[a_, hv]])
        np.testing.assert_array_equal(lhs, nb_s[a_, h2s[hv]],
                                      err_msg=f"direction {a_}")


@pytest.mark.parametrize("d", [1, 2, 4, 8])
def test_hash_build_matches_sort_oracle(rng, d):
    x = _points(rng, 300, d)
    lat_s, lat_h = _pair(x)
    assert lat_s.build_backend == "sort"
    assert lat_h.build_backend == "hash_xla"
    assert not bool(lat_h.overflow)
    _assert_structural_equiv(lat_s, lat_h)


@pytest.mark.parametrize("d,r", [(2, 1), (3, 2), (6, 1)])
def test_hash_neighbor_table_radii(rng, d, r):
    """Neighbor equivalence holds for r > 1 stencils too."""
    x = _points(rng, 200, d)
    lat_s, lat_h = _pair(x, r=r)
    _assert_structural_equiv(lat_s, lat_h)


@pytest.mark.parametrize("backend", ["xla", "fused_xla"])
def test_operator_parity_across_builds(rng, backend):
    """MVM parity <= 1e-6 between hash- and sort-built lattices (the
    fused_xla case exercises the hash build's single-column splat plan)."""
    x = _points(rng, 256, 4)
    v = jnp.asarray(rng.normal(size=(256, 3)), jnp.float32)
    st = make_stencil("matern32", 1)
    w = jnp.asarray(st.weights, jnp.float32)
    lat_s, lat_h = _pair(x, spacing=st.spacing, r=st.r)
    out_s = lattice_mvm(lat_s, v, w, backend=backend)
    out_h = lattice_mvm(lat_h, v, w, backend=backend)
    scale = float(jnp.abs(out_s).max())
    assert float(jnp.abs(out_s - out_h).max()) <= 1e-6 * max(scale, 1.0)


def test_splat_plan_consistency(rng):
    """The hash build's sorted splat plan computes the same linear map as
    the scatter splat (up to f32 scan noise)."""
    x = _points(rng, 400, 5)
    v = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    _, lat_h = _pair(x)
    s_ref = L.splat(lat_h, v)
    s_plan = L.splat_sorted(lat_h, v)
    np.testing.assert_allclose(np.asarray(s_plan), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


def test_hash_build_permutation_invariance(rng):
    """Permuting input rows permutes the operator (slot assignment may
    differ — only the operator must commute with the permutation)."""
    n, d = 96, 3
    x = _points(rng, n, d)
    perm = jnp.asarray(rng.permutation(n))
    st = make_stencil("matern32", 1)
    lat = L.build_lattice(x, spacing=st.spacing, r=st.r, backend="hash_xla")
    lat_p = L.build_lattice(x[perm], spacing=st.spacing, r=st.r,
                            backend="hash_xla")
    assert int(lat.m) == int(lat_p.m)
    assert _coord_set(lat) == _coord_set(lat_p)
    v = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    w = jnp.asarray(st.weights, jnp.float32)
    out = lattice_mvm(lat, v, w, backend="xla")
    out_p = lattice_mvm(lat_p, v[perm], w, backend="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out)[perm],
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Adversarial key sets, occupancy, and overflow semantics.
# ---------------------------------------------------------------------------


def test_collision_heavy_single_bucket_ref():
    """Keys engineered into ONE home bucket (max linear-probe clustering):
    insert places every distinct key, lookup finds each, absent keys miss."""
    hcap = 256
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.integers(0, 1 << 20, size=(4096, 2)), jnp.int32)
    h = np.asarray(hash_ref.initial_slots(pool, hcap))
    bucket = np.bincount(h, minlength=hcap).argmax()
    cand = np.flatnonzero(h == bucket)[:24]  # all share home slot
    keys = pool[jnp.asarray(cand)]
    dup = jnp.concatenate([keys, keys[::-1], keys[:7]], axis=0)
    owner, slot, ok = hash_ops.hash_insert(dup, hcap, backend="hash_xla")
    assert bool(jnp.all(ok))
    occ = int(jnp.sum(owner < dup.shape[0]))
    assert occ == len(cand)  # every distinct key placed exactly once
    tk = hash_ops.table_keys(owner, dup)
    found = hash_ops.hash_lookup(tk, keys, jnp.ones(len(cand), bool), hcap,
                                 backend="hash_xla")
    assert bool(jnp.all(found >= 0))
    got = np.asarray(tk)[np.asarray(found)]
    np.testing.assert_array_equal(got, np.asarray(keys))
    absent = keys + jnp.int32(1 << 21)
    missed = hash_ops.hash_lookup(tk, absent, jnp.ones(len(cand), bool),
                                  hcap, backend="hash_xla")
    assert bool(jnp.all(missed == -1))


def test_collision_chain_longer_than_epoch_budget():
    """Regression: claims serialize one-per-epoch on a shared cluster
    frontier, so a single-bucket chain needs ~chain-length epochs. A
    fixed probes/inner_rounds epoch budget spuriously reported overflow
    for chains past ~hcap/16 on a mostly-empty table; the fix iterates
    while any row is alive and fails a row only after it ADVANCED through
    every slot."""
    hcap = 1024
    rng_ = np.random.default_rng(1)
    pool = jnp.asarray(rng_.integers(0, 1 << 30, size=(400_000, 1)),
                       jnp.int32)
    h = np.asarray(hash_ref.initial_slots(pool, hcap))
    bucket = np.bincount(h, minlength=hcap).argmax()
    cand = np.flatnonzero(h == bucket)
    assert len(cand) >= 200  # chain far beyond the old ~72-epoch budget
    keys = pool[jnp.asarray(cand[:200])]
    owner, slot, ok = hash_ops.hash_insert(keys, hcap, backend="hash_xla")
    assert bool(jnp.all(ok))  # 200 distinct keys, 1024 slots: all place
    assert int(jnp.sum(owner < keys.shape[0])) == 200
    tk = hash_ops.table_keys(owner, keys)
    np.testing.assert_array_equal(np.asarray(tk)[np.asarray(slot)],
                                  np.asarray(keys))


def test_duplicate_heavy_degenerate_points(rng):
    """All points identical: one simplex worth of lattice points, massive
    duplication per key."""
    x = jnp.tile(_points(rng, 1, 6), (500, 1))
    lat_s, lat_h = _pair(x)
    assert int(lat_h.m) == int(lat_s.m) <= 7
    _assert_structural_equiv(lat_s, lat_h)


def test_overflow_flags_above_90pct_occupancy(rng):
    """Near-full tables: results stay exact just under cap; one unique
    point past cap flips overflow (uncorrupted seg_ids) — identically to
    the sort oracle."""
    x = _points(rng, 128, 3, scale=5.0)
    m = int(L.build_lattice(x, spacing=0.5, r=1, backend="sort").m)
    snug = int(np.floor(m / 0.95))  # ~95% of capacity used
    assert m / snug > 0.9
    lat_s, lat_h = _pair(x, spacing=0.5, cap=snug)
    assert not bool(lat_h.overflow)
    _assert_structural_equiv(lat_s, lat_h)

    lat_s2, lat_h2 = _pair(x, spacing=0.5, cap=m - 1)
    assert bool(lat_s2.overflow) and bool(lat_h2.overflow)
    assert not bool(lat_h2.pack_overflow)
    seg = np.asarray(lat_h2.seg_ids)
    assert seg.min() >= 0 and seg.max() <= lat_h2.cap


def test_pack_overflow_semantics_match(rng):
    """|coord| > 2^15 sets pack_overflow AND overflow on the hash path,
    and build_lattice_auto refuses to grow its way out — the sort
    contract, verbatim."""
    far = _points(rng, 64, 2, scale=3e4)
    lat = L.build_lattice(far, spacing=0.5, r=1, backend="hash_xla")
    assert bool(lat.pack_overflow) and bool(lat.overflow)
    lat_auto = L.build_lattice_auto(far, spacing=0.5, r=1, cap=16,
                                    backend="hash_xla")
    assert bool(lat_auto.pack_overflow)
    assert lat_auto.cap <= 64  # no useless growth


def test_build_lattice_auto_hash_grows(rng):
    """Grow-and-retry clears a capacity overflow under the hash backend."""
    x = _points(rng, 128, 3, scale=3.0)
    lat = L.build_lattice_auto(x, spacing=0.5, r=1, cap=16,
                               backend="hash_xla")
    assert not bool(lat.overflow)
    assert int(lat.m) <= lat.cap


# ---------------------------------------------------------------------------
# Pallas kernels (interpreter off-TPU) vs the XLA reference.
# ---------------------------------------------------------------------------


def test_pallas_insert_lookup_interpret_parity(rng):
    """The Pallas kernels implement the same table semantics: identical
    placed-key sets and per-row resolution (slot NUMBERING may differ:
    sequential first-come claims vs epoch min-id claims)."""
    hcap = 256  # 90 distinct keys -> occupancy 0.35, all must place
    keys = jnp.asarray(rng.integers(0, 1 << 15, size=(90, 3)), jnp.int32)
    keys = jnp.concatenate([keys, keys[:30]], axis=0)  # duplicates

    ow_x, sl_x, ok_x = hash_ops.hash_insert(keys, hcap, backend="hash_xla")
    ow_p, sl_p, ok_p = hash_ops.hash_insert(keys, hcap,
                                            backend="hash_pallas",
                                            interpret=True)
    assert bool(jnp.all(ok_x)) and bool(jnp.all(ok_p))
    tk_x = hash_ops.table_keys(ow_x, keys)
    tk_p = hash_ops.table_keys(ow_p, keys)
    placed = lambda tk, ow: set(
        map(tuple, np.asarray(tk)[np.asarray(ow) < keys.shape[0]].tolist()))
    assert placed(tk_x, ow_x) == placed(tk_p, ow_p)
    # each row resolves to its own key under both kernels
    np.testing.assert_array_equal(np.asarray(tk_p)[np.asarray(sl_p)],
                                  np.asarray(keys))

    # lookup: same hits/misses, and hits resolve to the right keys
    queries = jnp.concatenate([keys[:40], keys[:40] + jnp.int32(1 << 16)])
    active = jnp.ones((queries.shape[0],), bool)
    res_x = hash_ops.hash_lookup(tk_x, queries, active, hcap,
                                 backend="hash_xla")
    res_p = hash_ops.hash_lookup(tk_p, queries, active, hcap,
                                 backend="hash_pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(res_x) >= 0,
                                  np.asarray(res_p) >= 0)
    hits = np.asarray(res_p) >= 0
    np.testing.assert_array_equal(
        np.asarray(tk_p)[np.asarray(res_p)[hits]],
        np.asarray(queries)[hits])
    # inactive queries short-circuit to -1
    res_inact = hash_ops.hash_lookup(tk_x, queries, jnp.zeros_like(active),
                                     hcap, backend="hash_xla")
    assert bool(jnp.all(res_inact == -1))


def test_insert_full_table_reports_failure():
    """More distinct keys than slots: ok=False for the overflow rows, and
    the table itself stays uncorrupted (every placed slot holds a real
    key)."""
    hcap = 16
    keys = jnp.arange(64, dtype=jnp.int32)[:, None] * jnp.int32(7919)
    owner, slot, ok = hash_ops.hash_insert(keys, hcap, backend="hash_xla")
    assert not bool(jnp.all(ok))
    assert int(jnp.sum(owner < keys.shape[0])) == hcap  # full
    tk = hash_ops.table_keys(owner, keys)
    occ = np.asarray(owner) < keys.shape[0]
    placed = np.asarray(tk)[occ]
    all_keys = {int(k) for k in np.asarray(keys)[:, 0]}
    assert {int(k) for k in placed[:, 0]}.issubset(all_keys)


# ---------------------------------------------------------------------------
# Policy / cache / GP integration.
# ---------------------------------------------------------------------------


def test_build_backend_policy():
    assert hash_ops.resolve_build_backend("sort") == "sort"
    assert hash_ops.resolve_build_backend("hash_xla") == "hash_xla"
    # the Pallas build does not compile for the TPU (test_tpu_compile.py),
    # so auto resolves to the XLA tier on every host
    assert hash_ops.resolve_build_backend("auto") == "hash_xla"
    with pytest.raises(ValueError):
        hash_ops.resolve_build_backend("bogus")


def test_hash_capacity_invariants():
    for cap in (1, 7, 8, 1000, 4096):
        hcap = hash_ops.hash_capacity(cap)
        assert hcap >= 2 * cap  # occupancy <= 0.5 whenever m <= cap
        assert hcap & (hcap - 1) == 0  # power of two


def test_lattice_cache_keys_on_build_backend(rng):
    """Sort- and hash-built lattices for the SAME geometry must never
    alias in the cache (their slot numbering differs)."""
    from repro.core.filtering import LatticeCache
    x = _points(rng, 64, 3)
    cache = LatticeCache()
    tag = cache.point_set_tag(x)
    kw = dict(spacing=1.0, r=1, cap=256, ls=jnp.ones(3))
    lat_h = cache.get(tag, x, build_backend="hash_xla", **kw)
    lat_s = cache.get(tag, x, build_backend="sort", **kw)
    assert lat_h is not lat_s
    assert cache.misses == 2
    assert cache.get(tag, x, build_backend="hash_xla", **kw) is lat_h
    assert cache.get(tag, x, build_backend="sort", **kw) is lat_s
    assert cache.hits == 2
    # "auto" keys on its RESOLUTION: on this host it must HIT the
    # explicit hash entry, not build a duplicate lattice
    resolved = hash_ops.resolve_build_backend("auto")
    lat_auto = cache.get(tag, x, build_backend="auto", **kw)
    if resolved == "hash_xla":
        assert lat_auto is lat_h
        assert cache.hits == 3 and cache.misses == 2


def test_gp_pipeline_parity_across_build_backends(rng):
    """End to end: MLL value/grads and posterior agree between build
    backends to f32 solver noise."""
    from repro.gp import (GPParams, SimplexGP, SimplexGPConfig,
                          mll_value_and_grad, posterior)
    n, ns, d = 96, 24, 2
    x = _points(rng, n, d)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    xs = _points(rng, ns, d)
    params = GPParams.init(d)
    key = jax.random.PRNGKey(0)
    res, post = {}, {}
    for bk in ("sort", "hash_xla"):
        model = SimplexGP(SimplexGPConfig(kernel="matern32",
                                          max_cg_iters=200,
                                          cg_tol_eval=1e-4, num_probes=4,
                                          build_backend=bk))
        # tight tolerances: an UNCONVERGED CG iterate is path-sensitive,
        # so at the paper's loose tolerances f32-level operator noise
        # between equivalent builds legitimately shifts solve outputs
        res[bk] = mll_value_and_grad(model, params, x, y, key, tol=1e-6)
        post[bk] = posterior(model, params, x, y, xs, key=key,
                             variance_rank=8)
    assert np.isclose(float(res["sort"].mll), float(res["hash_xla"].mll),
                      rtol=2e-3, atol=1e-2)
    for g_s, g_h in zip(jax.tree.leaves(res["sort"].grads),
                        jax.tree.leaves(res["hash_xla"].grads)):
        np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_h),
                                   rtol=5e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(post["sort"].mean),
                               np.asarray(post["hash_xla"].mean),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(post["sort"].var),
                               np.asarray(post["hash_xla"].var),
                               rtol=1e-3, atol=1e-4)


def test_hash_build_jaxpr_is_sort_free(rng):
    """Acceptance regression (ISSUE 5): the hash build path — embed,
    dedup insert, neighbor lookup, AND the splat plan — contains ZERO
    ``lax.sort`` primitives, asserted recursively on the jaxpr. The
    embed's vertex ranking is a pairwise comparison count and the plan
    is the counting/partition construction; only the "sort" oracle
    backend may sort."""
    from repro.sharding.simplex import count_primitive

    x = _points(rng, 200, 4)
    jaxpr = jax.make_jaxpr(
        lambda z: L._build_lattice_hash_impl(z, spacing=1.0, r=1, cap=512,
                                             backend="hash_xla"))(x)
    assert count_primitive(jaxpr, "sort") == 0
    # the oracle still sorts (sanity check that the counter works at all)
    jaxpr_sort = jax.make_jaxpr(
        lambda z: L._build_lattice_impl(z, spacing=1.0, r=1, cap=512))(x)
    assert count_primitive(jaxpr_sort, "sort") > 0


@pytest.mark.parametrize("shape", [(97, 3), (400, 5), (64, 1)])
def test_counting_plan_matches_stable_sort(rng, shape):
    """The sort-free splat plan is BIT-IDENTICAL to the stable single-key
    sort it replaced (ascending slot, original row order within a slot),
    including non-multiple-of-block sizes and the dump slot."""
    n, d = shape
    x = _points(rng, n, d, scale=0.4)  # clustered: heavy duplication
    lat = L.build_lattice(x, spacing=1.0, r=1, backend="hash_xla")
    big = n * (d + 1)
    ss, sp = jax.lax.sort((lat.seg_ids, jnp.arange(big, dtype=jnp.int32)),
                          num_keys=1)
    cs, cp = L._splat_plan_counting(lat.seg_ids, big=big, cap=lat.cap)
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(cs))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(cp))


def test_counting_plan_degenerate_single_slot():
    """Every contribution in ONE slot (the worst case for any
    rank-by-counting scheme) still yields the identity-stable plan."""
    big, cap = 1000, 64
    seg = jnp.full((big,), 7, jnp.int32)
    cs, cp = L._splat_plan_counting(seg, big=big, cap=cap)
    np.testing.assert_array_equal(np.asarray(cs), np.full(big, 7))
    np.testing.assert_array_equal(np.asarray(cp), np.arange(big))
