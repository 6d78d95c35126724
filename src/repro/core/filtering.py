"""Lattice filtering = the Simplex-GP MVM (paper §4) with efficient gradients.

``lattice_filter`` evaluates ``u ≈ K(z) v`` for a stationary kernel whose
§4.1 stencil is supplied, via Splat -> Blur -> Slice on the permutohedral
lattice (= the SKI decomposition W K_UU W^T of paper Eq. 8). It rebuilds
the lattice per call; ``lattice_filter_with`` is the shared-lattice variant
(same values, same §4.2 VJP) closed over a prebuilt ``Lattice``, and
``LatticeCache`` memoizes builds across eager calls — together they are the
one-build-per-step pipeline of DESIGN.md §9.

Gradients follow the paper exactly:
  * w.r.t. ``v``: the transpose filter (reverse-order blur); with
    ``symmetrize=True`` the operator is 0.5 (F + F^T) and self-adjoint.
  * w.r.t. ``z`` (and hence lengthscales, by the chain rule outside): the
    §4.2 identity (Eqs. 11-13) — ONE extra filtering call with the
    derivative stencil ``k'`` applied to Concat([z⊙g, -g, z⊙v, -v]).

Note the §4.2 gradient is an approximation of the gradient of the *exact*
MVM (like the paper's), not the exact gradient of the approximation; it
deliberately does not differentiate through the integer lattice rounding.

``symmetrize`` is a beyond-paper robustness option (default on for GP
inference): the raw sequential blur B_d ... B_0 is very slightly
non-symmetric because directional blurs do not commute; averaging with the
reversed order restores exact symmetry so CG operates on a symmetric
operator. Cost: 2x blur (splat/slice shared).
"""
from __future__ import annotations

import collections
import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import lattice as lat_mod
from repro.core.lattice import Lattice
from repro.core.stencil import Stencil

Array = jax.Array


class FilterSpec(NamedTuple):
    """Static configuration of a lattice filter (hashable; jit-friendly).

    ``taps``/``dtaps`` carry the CONCRETE stencil values so Pallas/fused
    backends can bake them into the kernel even when the ``weights`` array
    reaching ``filter_mvm`` is traced under jit (converting a tracer with
    ``float()`` crashes — the seed's ``use_pallas`` bug).
    """

    spacing: float
    r: int
    cap: int | None
    symmetrize: bool
    dscale: float = 1.0  # amplitude of the derivative kernel k'(0)
    taps: tuple[float, ...] | None = None  # concrete forward stencil
    dtaps: tuple[float, ...] | None = None  # concrete derivative stencil
    backend: str = "auto"  # kernels/blur/ops.py backend policy
    build_backend: str = "auto"  # kernels/hash/ops.py build-path policy


def spec_for(stencil: Stencil, cap: int | None = None,
             symmetrize: bool = True, backend: str = "auto",
             build_backend: str = "auto") -> FilterSpec:
    return FilterSpec(spacing=stencil.spacing, r=stencil.r, cap=cap,
                      symmetrize=symmetrize, dscale=stencil.dscale,
                      taps=tuple(stencil.weights),
                      dtaps=tuple(stencil.dweights), backend=backend,
                      build_backend=build_backend)


def filter_mvm(lat: Lattice, v: Array, weights: Array | None = None, *,
               symmetrize: bool = True, backend: str = "auto",
               taps: tuple[float, ...] | None = None,
               use_pallas: bool = False, mesh=None,
               axis_name: str = "data") -> Array:
    """Apply the lattice operator W B W^T to (n, c) values, lattice given.

    This is the fast path for CG loops: build the lattice once per
    hyperparameter setting, then call this per iteration — the (n, c)
    block contract means a whole mBCG/LOVE RHS block rides ONE call.
    ``backend`` selects the kernels/blur/ops.py tier ("auto" = policy
    choice); ``use_pallas`` is the seed-compatible alias for the
    per-direction tier. Concrete ``taps`` enable the Pallas/fused tiers
    under jit. ``mesh`` engages the sharded data-parallel tier
    (one psum per MVM — DESIGN.md §10).
    """
    from repro.kernels.blur.ops import lattice_mvm
    if use_pallas:
        backend = "per_direction_pallas"
    return lattice_mvm(lat, v, weights, taps=taps, symmetrize=symmetrize,
                       backend=backend, mesh=mesh, axis_name=axis_name)


def filter_mvm_t(lat: Lattice, v: Array, weights: Array | None = None, *,
                 symmetrize: bool = True, backend: str = "auto",
                 taps: tuple[float, ...] | None = None, mesh=None,
                 axis_name: str = "data") -> Array:
    """Transpose operator F^T (== F when symmetrized).

    The fused backends give the transpose for free: it is the same kernel
    with the sweep order flipped.
    """
    from repro.kernels.blur.ops import lattice_mvm
    return lattice_mvm(lat, v, weights, taps=taps, symmetrize=symmetrize,
                       transpose=True, backend=backend, mesh=mesh,
                       axis_name=axis_name)


# ---------------------------------------------------------------------------
# Differentiable entry point.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def lattice_filter(z: Array, v: Array, weights: Array, dweights: Array,
                   spec: FilterSpec) -> Array:
    """u ≈ K(z) v with custom VJPs per paper §4.2.

    Args:
      z: (n, d) lengthscale-normalized inputs.
      v: (n, c) values to filter.
      weights: (2r+1,) §4.1 stencil of the kernel profile.
      dweights: (2r+1,) §4.1 stencil of k' (derivative wrt squared distance).
      spec: static filter configuration.
    """
    lat = lat_mod.build_lattice(z, spacing=spec.spacing, r=spec.r,
                                cap=spec.cap, backend=spec.build_backend)
    return filter_mvm(lat, v, weights, symmetrize=spec.symmetrize,
                      backend=spec.backend, taps=spec.taps)


def _filter_fwd(z, v, weights, dweights, spec):
    lat = lat_mod.build_lattice(z, spacing=spec.spacing, r=spec.r,
                                cap=spec.cap, backend=spec.build_backend)
    u = filter_mvm(lat, v, weights, symmetrize=spec.symmetrize,
                   backend=spec.backend, taps=spec.taps)
    return u, (z, v, weights, dweights, lat)


def _filter_bwd(spec, res, g):
    z, v, weights, dweights, lat = res
    return _filter_bwd_core(spec, lat, z, v, weights, dweights, g)


@obs.scope("grad")
def _filter_bwd_core(spec, lat, z, v, weights, dweights, g):
    """Shared §4.2 backward pass for both filter entry points."""
    n, d = z.shape
    c = v.shape[1]

    # dL/dv = F^T g — reuse the already-built lattice; the fused backends
    # run the transpose as the same kernel with the sweep order flipped.
    dv = filter_mvm_t(lat, g, weights, symmetrize=spec.symmetrize,
                      backend=spec.backend, taps=spec.taps)

    # dL/dz via Eq. 12/13: one filter call with the k' stencil on
    # Concat([z ⊙ g, g, z ⊙ v, v]) (signs folded into the combination).
    zg = (z[:, :, None] * g[:, None, :]).reshape(n, d * c)
    zv = (z[:, :, None] * v[:, None, :]).reshape(n, d * c)
    big = jnp.concatenate([zg, g, zv, v], axis=1)
    out = filter_mvm(lat, big, dweights, symmetrize=spec.symmetrize,
                     backend=spec.backend, taps=spec.dtaps)
    A = out[:, : d * c].reshape(n, d, c)  # F'(z ⊙ g)
    B = out[:, d * c: d * c + c]  # F' g
    C = out[:, d * c + c: 2 * d * c + c].reshape(n, d, c)  # F'(z ⊙ v)
    D = out[:, 2 * d * c + c:]  # F' v

    # NOTE: expanding Eq. 11 (verified against autodiff of the dense MVM in
    # tests/test_filtering.py) gives the OPPOSITE overall sign of the paper's
    # printed Eq. 12; we follow Eq. 11.
    dz = (2.0 * spec.dscale) * (
        z * jnp.sum(v * B, axis=1, keepdims=True)
        - jnp.einsum("nc,ndc->nd", v, A)
        + z * jnp.sum(g * D, axis=1, keepdims=True)
        - jnp.einsum("nc,ndc->nd", g, C)
    )
    zero_w = jnp.zeros_like(weights)
    zero_dw = jnp.zeros_like(dweights)
    return dz.astype(z.dtype), dv.astype(v.dtype), zero_w, zero_dw


lattice_filter.defvjp(_filter_fwd, _filter_bwd)


# ---------------------------------------------------------------------------
# Prebuilt-lattice entry point (DESIGN.md §9): same operator + same §4.2
# custom VJP, but closed over an existing Lattice instead of rebuilding one
# per call. This is what lets a training step / posterior run on exactly ONE
# lattice build: the solve path, the surrogate quad forms, and the §4.2
# backward pass all share the ``lat`` the caller built.
# ---------------------------------------------------------------------------


def _lattice_zero_cotangent(lat: Lattice):
    """Zero cotangent for the Lattice pytree (float0 for int/bool leaves).

    The lattice's integer structure is non-differentiable by construction
    (the §4.2 gradient deliberately ignores the rounding), so its cotangent
    is symbolically zero.
    """
    def zero(leaf):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.inexact):
            return jnp.zeros_like(leaf)
        return np.zeros(jnp.shape(leaf), jax.dtypes.float0)

    return jax.tree.map(zero, lat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lattice_filter_with(lat: Lattice, z: Array, v: Array, weights: Array,
                        dweights: Array, spec: FilterSpec) -> Array:
    """u ≈ K(z) v on a PREBUILT lattice, with the §4.2 custom VJP.

    Identical to ``lattice_filter`` except the lattice is supplied instead
    of rebuilt, so repeated quad forms within one step cost zero builds.
    The caller must guarantee ``lat`` was built from these ``z`` (same
    spacing/r); gradients w.r.t. ``z`` flow through the derivative-stencil
    identity exactly as in ``lattice_filter`` — the lattice itself gets a
    symbolic-zero cotangent, matching the §4.2 convention of not
    differentiating the integer rounding.
    """
    return filter_mvm(lat, v, weights, symmetrize=spec.symmetrize,
                      backend=spec.backend, taps=spec.taps)


def _filter_with_fwd(lat, z, v, weights, dweights, spec):
    u = filter_mvm(lat, v, weights, symmetrize=spec.symmetrize,
                   backend=spec.backend, taps=spec.taps)
    return u, (lat, z, v, weights, dweights)


def _filter_with_bwd(spec, res, g):
    lat, z, v, weights, dweights = res
    dz, dv, zero_w, zero_dw = _filter_bwd_core(spec, lat, z, v, weights,
                                               dweights, g)
    return _lattice_zero_cotangent(lat), dz, dv, zero_w, zero_dw


lattice_filter_with.defvjp(_filter_with_fwd, _filter_with_bwd)


# ---------------------------------------------------------------------------
# Cross-call lattice reuse (DESIGN.md §9).
# ---------------------------------------------------------------------------


def concrete_ls_key(ls) -> tuple | None:
    """Hashable cache key from a concrete lengthscale; None while traced.

    A rebuild is only *required* when the integer rounding of ``z = x / ls``
    changes, but detecting that is as expensive as rebuilding — so the cache
    keys conservatively on the exact concrete lengthscale values.
    """
    try:
        arr = np.asarray(ls, dtype=np.float64)
    except (jax.errors.ConcretizationTypeError, jax.errors.TracerArrayConversionError):
        return None
    return tuple(arr.reshape(-1).tolist())


class LatticeCache:
    """Small LRU memo of built lattices, keyed on the concrete geometry.

    Keys combine a caller-chosen point-set tag (which arrays were embedded),
    the concrete lengthscale values, and the static build parameters
    ``(spacing, r, cap)`` — the full determinants of the integer lattice.
    Under jit (traced lengthscales) the cache is transparently bypassed:
    within one traced step, reuse is instead structural (build once, pass the
    ``Lattice`` through ``operator(lat=...)`` / ``lattice_filter_with``).
    """

    def __init__(self, maxsize: int = 8):
        self._store: collections.OrderedDict = collections.OrderedDict()
        self._maxsize = maxsize
        self.hits = 0
        self.misses = 0

    @staticmethod
    def point_set_tag(*arrays: Array) -> tuple | None:
        """Content fingerprint of the (concrete) embedded point sets.

        Hashes the raw bytes, so it is ROW-ORDER SENSITIVE — the lattice's
        seg_ids/weights/splat plan depend on input order, so a reordered
        point set must miss the cache. Returns None for traced inputs
        (``get`` then bypasses the memo). Cost: one host transfer + hash,
        trivial next to a build.
        """
        parts = []
        for a in arrays:
            if isinstance(a, jax.core.Tracer):
                return None
            arr = np.asarray(a)
            parts.append((arr.shape, str(arr.dtype),
                          hashlib.blake2b(arr.tobytes(),
                                          digest_size=16).hexdigest()))
        return tuple(parts)

    @staticmethod
    def layout_key(z: Array) -> str:
        """Device/sharding fingerprint of the array the build starts from.

        The built lattice's arrays inherit ``z``'s placement and sharding
        (a shard_map/GSPMD consumer sees committed shardings), so a lattice
        built from an unsharded ``z`` must NOT be served to a request whose
        ``z`` is sharded over a mesh (or lives on different devices) — the
        MVM would silently reshard or, worse, mix layouts. str(sharding)
        covers both the device set and the partition spec.
        """
        sharding = getattr(z, "sharding", None)
        return "" if sharding is None else str(sharding)

    def get(self, tag, z: Array, *, spacing: float, r: int,
            cap: int | None, ls=None,
            build_backend: str = "auto", mesh=None) -> Lattice:
        """Return a cached lattice for this key, building on miss.

        ``tag`` identifies the point set(s) behind ``z`` (use
        ``point_set_tag``); ``ls`` is the concrete lengthscale the embedding
        divided by (traced -> bypass). The key also includes ``z``'s
        device/sharding layout so a sharded build never aliases an
        unsharded one, the build path (sort vs hash slot numbering
        differs, so lattices from different backends must never alias
        either — consumers may hold slot-indexed state), and the CONSUMER
        MESH the MVMs will run on (``mesh``): after an elastic mesh
        resize (DESIGN.md §16) a resumed run must never be served a
        lattice produced for the old device layout — downstream holds
        mesh-shaped compiled/sharded state keyed on these arrays, so the
        resume path misses here and rebuilds.
        """
        ls_key = concrete_ls_key(ls) if ls is not None else ()
        if tag is None or ls_key is None or isinstance(z, jax.core.Tracer):
            return lat_mod.build_lattice(z, spacing=spacing, r=r, cap=cap,
                                         backend=build_backend)
        # key on the RESOLVED backend (what build_lattice will actually
        # run), so "auto" and its explicit resolution share one entry —
        # and the key matches the stored Lattice.build_backend provenance
        from repro.kernels.hash import ops as hash_ops
        from repro.sharding.simplex import mesh_fingerprint
        resolved = hash_ops.resolve_build_backend(build_backend)
        key = (tag, ls_key, float(spacing), int(r),
               None if cap is None else int(cap), self.layout_key(z),
               resolved, mesh_fingerprint(mesh))
        hit = self._store.get(key)
        if hit is not None:
            self._store.move_to_end(key)
            self.hits += 1
            return hit
        self.misses += 1
        lat = lat_mod.build_lattice(z, spacing=spacing, r=r, cap=cap,
                                    backend=resolved)
        self._store[key] = lat
        while len(self._store) > self._maxsize:
            self._store.popitem(last=False)
        return lat


def _embed_queries(zq: Array, spacing: float, dtype):
    """Embed + pack + pack-overflow mask — the shared front half of every
    frozen-slice entry point. Returns (q_packed, weights, rank, active);
    ONE ``simplex_embed`` per call, which is what the multi-output predict
    path's one-embed-per-batch pin (the ``lattice.embeds`` counter) counts."""
    b, d = zq.shape
    keys, w, rank = lat_mod.simplex_embed_ranked(zq, spacing)
    q_packed = jnp.stack(lat_mod._pack_key_cols(keys), axis=1)
    # queries whose coordinates overflow the 16-bit packing could alias
    # real keys — force all their vertices to miss (reported as mass 1)
    ok = jnp.all(jnp.abs(keys) <= lat_mod._PACK_LIMIT, axis=(1, 2))
    active = jnp.repeat(ok, d + 1)
    return q_packed, w.astype(dtype), rank, active


def _slice_only_xla(index: "lat_mod.LatticeIndex", tables: Array, zq: Array,
                    spacing: float) -> tuple[Array, Array]:
    """Pure-XLA frozen slice — every op is differentiable/transposable.

    The body the custom JVP below traces: ``simplex_embed_ranked`` is
    JVP-exact w.r.t. ``zq`` by construction (rounding and ranks are
    piecewise constant with zero/stopped tangents; the weights are affine
    per cell), and gather + einsum are linear in ``tables``/``weights``.
    Keeping this path free of ``pallas_call`` (which has no transpose
    rule) is what makes reverse-mode ``jax.grad`` work through serving.
    """
    from repro.kernels.slice.ref import slice_query_xla
    q_packed, w, _, active = _embed_queries(zq, spacing, tables.dtype)
    return slice_query_xla(index.tkeys, index.row_of_slot, tables,
                           q_packed, w, active, index.hcap)


@functools.partial(jax.custom_jvp, nondiff_argnums=(3, 4, 5))
def _slice_only_prim(index, tables, zq, spacing, backend, interpret):
    from repro.kernels.slice.ops import slice_query
    q_packed, w, _, active = _embed_queries(zq, spacing, tables.dtype)
    return slice_query(index, tables, q_packed, w, active,
                       backend=backend, interpret=interpret)


@_slice_only_prim.defjvp
def _slice_only_jvp(spacing, backend, interpret, primals, tangents):
    """Query-space (and table-space) JVP of the frozen slice (§15).

    Differentiation re-traces the pure-XLA body — the weights are
    piecewise-linear in the query, so the tangent is the existing slice
    contraction against the analytic weight derivative (no new probes),
    and linearizing this rule gives reverse-mode for free. The fast
    serving tiers (Pallas fused probe) stay primal-only; forward-only
    consumers that want the fused primal+tangent kernel use
    ``slice_only_tangent`` instead. The index tangent (int leaves) is
    ignored; ``miss`` gets the traced body's true tangent (zero when the
    query's simplex fully hits, the tangent weight mass on the missing
    vertices otherwise — see the boundary semantics in DESIGN.md §15).
    """
    index, tables, zq = primals
    _, tables_dot, zq_dot = tangents
    return jax.jvp(lambda t, q: _slice_only_xla(index, t, q, spacing),
                   (tables, zq), (tables_dot, zq_dot))


def slice_only(index: "lat_mod.LatticeIndex", tables: Array, zq: Array, *,
               spacing: float, backend: str = "auto",
               interpret: bool | None = None) -> tuple[Array, Array]:
    """Slice FROZEN per-lattice-point tables at new points — no build, no
    solve (the serving entry point, DESIGN.md §12).

    Embeds ``zq`` ((b, d) lengthscale-normalized queries; O(d^2) per
    point, sort-free), probes the lattice hash index for each enclosing
    vertex, and barycentrically contracts the frozen ``tables`` rows.
    Lookup-miss semantics: vertices absent from the index contribute ZERO
    (the standard permutohedral slicing convention — the frozen lattice
    simply has no mass there), and each query's barycentric mass on
    absent vertices is returned as ``miss`` — the per-batch fidelity
    diagnostic (0 = the query's simplex is entirely inside the frozen
    lattice; 1 = completely off-lattice, prediction falls back to the
    prior). ``backend`` selects the kernels/slice/ops.py tier.

    DIFFERENTIABLE in ``zq`` and ``tables`` (DESIGN.md §15): a custom JVP
    reuses the piecewise-linearity of the barycentric weights, so both
    ``jax.jvp`` and ``jax.grad`` flow through serving; gradients are only
    meaningful where ``miss == 0`` (gate on it — absent vertices clamp
    their mass's contribution to zero).
    """
    return _slice_only_prim(index, tables, zq, float(spacing), backend,
                            interpret)


def slice_only_tangent(index: "lat_mod.LatticeIndex", tables: Array,
                       zq: Array, zq_dot: Array, *, spacing: float,
                       backend: str = "auto",
                       interpret: bool | None = None
                       ) -> tuple[Array, Array, Array]:
    """Fused primal + directional query-space tangent of the frozen slice.

    The forward-mode fast path (DESIGN.md §15): one embed, one analytic
    weight tangent (``lattice.embed_weight_tangent``), then the fused
    primal+tangent contraction tier (``kernels/slice/ops.py``'s
    ``slice_query_tangent`` — Pallas on TPU, XLA elsewhere: probe once,
    gather once, contract twice). Returns ``(out, out_dot, miss)``;
    ``out_dot`` is d(out)/d(zq) . zq_dot, valid where ``miss == 0``.
    """
    from repro.kernels.slice.ops import slice_query_tangent
    q_packed, w, rank, active = _embed_queries(zq, spacing, tables.dtype)
    w_dot = lat_mod.embed_weight_tangent(rank, zq_dot, spacing)
    return slice_query_tangent(index, tables, q_packed, w,
                               w_dot.astype(tables.dtype), active,
                               backend=backend, interpret=interpret)


def slice_only_grad(index: "lat_mod.LatticeIndex", tables: Array,
                    zq: Array, *, spacing: float
                    ) -> tuple[Array, Array, Array]:
    """One-pass primal + FULL query-space Jacobian of the frozen slice.

    Returns ``(out (b, c), jac (b, c, d), miss (b,))`` with
    ``jac[q, :, j] = d out[q] / d zq[q, j]`` — the d directional tangents
    share one embed/probe/gather (``kernels/slice/ops.py``'s
    ``slice_query_jacobian``). What ``gp/serve.predict_grad`` builds its
    analytic d(mean, var)/dx* from; valid where ``miss == 0``.
    """
    from repro.kernels.slice.ops import slice_query_jacobian
    q_packed, w, rank, active = _embed_queries(zq, spacing, tables.dtype)
    wjac = lat_mod.embed_weight_jacobian(rank, spacing, w.dtype)
    return slice_query_jacobian(index, tables, q_packed, w,
                                wjac.astype(tables.dtype), active)


def mvm_operator(z: Array, stencil: Stencil, *, cap: int | None = None,
                 symmetrize: bool = True, backend: str = "auto",
                 build_backend: str = "auto",
                 auto_cap: bool = False, mesh=None,
                 axis_name: str = "data"):
    """Build the lattice once and return (matvec, lattice).

    ``matvec`` maps (n, k) -> (n, k) — the multi-RHS block contract: CG,
    mBCG, Lanczos, and LOVE hand it their whole RHS block so each solver
    iteration costs exactly ONE lattice MVM regardless of k. It is NOT
    differentiable w.r.t. hyperparameters (use ``lattice_filter`` for the
    surrogate-loss terms). ``auto_cap`` right-sizes the table with
    grow-and-retry (syncs on the overflow flag, so only valid outside
    jit) — a much smaller table is what keeps the fused backend's VMEM
    plan viable at real scales. ``mesh`` makes every MVM data-parallel
    over its ``axis_name`` axis (sharding/simplex.py; one psum per call).
    """
    if auto_cap and cap is None:
        lat = lat_mod.build_lattice_auto(z, spacing=stencil.spacing,
                                         r=stencil.r, backend=build_backend)
    else:
        lat = lat_mod.build_lattice(z, spacing=stencil.spacing, r=stencil.r,
                                    cap=cap, backend=build_backend)
    w = jnp.asarray(stencil.weights, dtype=z.dtype)
    taps = tuple(stencil.weights)

    def matvec(v: Array) -> Array:
        return filter_mvm(lat, v, w, symmetrize=symmetrize, backend=backend,
                          taps=taps, mesh=mesh, axis_name=axis_name)

    return matvec, lat
