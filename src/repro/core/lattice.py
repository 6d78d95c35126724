"""Permutohedral lattice (paper §3.2), TPU-native static-shape formulation.

The reference CUDA implementation (Adams et al. 2010) builds a dynamic hash
table at splat time and probes it at blur time. TPUs have neither dynamic
allocation nor atomics, so this module re-derives the lattice with
static-shape primitives (see DESIGN.md §2):

  * every input emits the ``d+1`` vertex keys of its enclosing simplex;
  * keys are deduplicated into a fixed-capacity table, and blur neighbors
    are resolved ONCE at build time into a dense ``(d+1, cap, 2r)`` int32
    gather table — by one of two interchangeable build paths:
      - ``sort``: exact lexicographic ``lax.sort`` dedup + a merge-sort
        neighbor lookup (deterministic lex slot order; the oracle path);
      - ``hash`` (the default; DESIGN.md §11): a static-capacity
        open-addressing hash table (kernels/hash) — epoch-based
        scatter-min insert for dedup, gather-only probe lookup for
        neighbors — the CUDA design recovered without atomics, 2-5x
        faster per build on the host backend (BENCH_build.json);
  * splat is a ``segment_sum``, blur is ``gather + stencil reduction``,
    slice is ``take + barycentric contraction``.

Both paths produce operator-equivalent ``Lattice`` structures (same
deduplicated point set, seg structure, neighbor graph, and overflow
semantics) differing only in slot numbering. All shapes depend only on
``(n, d, r, cap)`` so the whole build is jittable.
A build is only required when the *integer* lattice geometry changes — i.e.
when the lengthscale/spacing moves enough to change the rounding of inputs
to simplex vertices — which in practice means once per hyperparameter
setting. Training/prediction share ONE build per step through
``filtering.lattice_filter_with`` / ``filtering.LatticeCache`` (DESIGN.md
§9); every build bumps the ``lattice.builds`` counter of ``repro.obs`` so
benchmarks and smoke tests can assert the builds-per-step contract.

Geometry facts used below (verified in tests/test_lattice.py):
  * the elevation basis E (paper Eq. 7 neighborhood) has orthogonal columns
    with norms sqrt((j+1)(j+2)); dividing by those norms makes elevation an
    isometry, so scaling inputs by ``alpha`` scales embedded distances by
    ``alpha``;
  * one lattice step along any of the ``d+1`` blur directions has embedded
    length ``sqrt(d(d+1))``; choosing ``alpha = sqrt(d(d+1)) / s`` makes a
    lattice step correspond to distance ``s`` in the (lengthscale-normalized)
    input space, which is how the §4.1 stencil spacing is realized.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.hash import ops as hash_ops

Array = jax.Array

INT_SENTINEL_A = jnp.iinfo(jnp.int32).max // 2  # padding keys for invalid table rows
INT_SENTINEL_B = jnp.iinfo(jnp.int32).min // 2  # padding keys for invalid queries


def step_scale(d: int, spacing: float) -> float:
    """Input-space scaling so one lattice step == ``spacing`` (DESIGN.md §2)."""
    return math.sqrt(d * (d + 1.0)) / spacing


def elevation_scales(d: int, spacing: float) -> jnp.ndarray:
    """Per-dimension scale factors folded into the triangular elevation."""
    j = jnp.arange(d, dtype=jnp.float32)
    return step_scale(d, spacing) / jnp.sqrt((j + 1.0) * (j + 2.0))


def elevate(z: Array, spacing: float) -> Array:
    """Embed (n, d) inputs into the hyperplane H_d in R^{d+1}.

    Triangular-basis elevation (paper §3.2 "Splat"): O(d) per point via
    suffix sums, equivalent to multiplying by the orthogonal-column basis E.
    """
    n, d = z.shape
    c = z * elevation_scales(d, spacing)[None, :]  # (n, d)
    # elevated[0] = sum_j c_j ; elevated[i] = sum_{j>=i} c_j - i * c_{i-1}
    suffix = jnp.cumsum(c[:, ::-1], axis=1)[:, ::-1]  # suffix[:, i] = sum_{j>=i} c_j
    suffix_full = jnp.concatenate([suffix, jnp.zeros((n, 1), c.dtype)], axis=1)
    i = jnp.arange(1, d + 1, dtype=c.dtype)
    elevated_rest = suffix_full[:, 1:] - i[None, :] * c  # rows i=1..d
    return jnp.concatenate([suffix_full[:, :1], elevated_rest], axis=1)


def descending_rank(diff: Array) -> Array:
    """Stable descending rank of the rounding differential (the tie-break).

    ``rank[i] = #{j : diff_j > diff_i} + #{j < i : diff_j == diff_i}`` — an
    O(d^2)-per-point pairwise comparison count instead of an argsort.
    Bit-identical to the stable argsort it replaces, but keeps the whole
    embed (and hence the hash build and the frozen serving path,
    DESIGN.md §12) free of `lax.sort`.

    THE deterministic tie-break of the lattice (DESIGN.md §15): when a
    query sits exactly on a simplex boundary, two or more differentials
    tie and the enclosing simplex is ambiguous. Ties are broken
    POSITIONALLY — among equal differentials the LOWER coordinate index
    takes the smaller (earlier) rank — so every backend (XLA reference,
    Pallas kernel, and the tangent/Jacobian helpers below) selects the
    SAME cell and hence the same one-sided subgradient. The integer
    lattice structure carries no gradient — stop_gradient keeps autodiff
    (which differentiates the piecewise-linear barycentric weights) from
    tracing through the comparisons.
    """
    d = diff.shape[1] - 1
    nd_ = jax.lax.stop_gradient(diff)
    pos = jnp.tril(jnp.ones((d + 1, d + 1), bool), k=-1)  # [a, b]: b < a
    bigger = nd_[:, None, :] > nd_[:, :, None]  # [n, a, b]: diff_b > diff_a
    ties = (nd_[:, None, :] == nd_[:, :, None]) & pos[None]
    return jnp.sum(bigger | ties, axis=2).astype(jnp.int32)


def _rank_scatter(rank: Array, vals: Array, affine: bool = False) -> Array:
    """Scatter per-coordinate contributions into barycentric vertex order.

    ``vals`` is (n, d+1[, ...]) in COORDINATE order; each coordinate i
    contributes ``+vals[:, i]`` to canonical vertex ``d - rank[:, i]`` and
    ``-vals[:, i]`` to vertex ``d + 1 - rank[:, i]``, with the overflow
    column d+1 folded into vertex 0 (the rounding algorithm's telescoping
    weight recurrence, vectorized). ``affine=True`` adds the constant 1 to
    vertex 0 — the primal barycentric weights; without it the result is
    the LINEAR part only, i.e. exactly the map tangents/Jacobians of the
    weights flow through (DESIGN.md §15).
    """
    n, dp1 = rank.shape
    d = dp1 - 1
    out = jnp.zeros((n, d + 2) + vals.shape[2:], dtype=vals.dtype)
    rows = jnp.arange(n)[:, None]
    out = out.at[rows, d - rank].add(vals)
    out = out.at[rows, d + 1 - rank].add(-vals)
    fold = 1.0 + out[:, d + 1] if affine else out[:, d + 1]
    out = out.at[:, 0].add(fold)
    return out[:, : d + 1]


def simplex_embed_ranked(z: Array, spacing: float):
    """``simplex_embed`` that also returns the coordinate ranks.

    The ranks identify the enclosing simplex cell; the analytic weight
    derivative helpers (``embed_weight_tangent``/``embed_weight_jacobian``)
    consume them so gradient callers pay the embed ONCE and reuse its
    scratch for the tangent scatter (DESIGN.md §15).

    Returns:
      keys:    (n, d+1, d+1) int32 — lattice coordinates of the d+1 vertices.
      weights: (n, d+1) float32 — barycentric weights (sum to 1).
      rank:    (n, d+1) int32 — fixed-up descending rank per coordinate.
    """
    # the ``lattice.embeds`` counter: the multi-output serving path
    # (gp/serve.predict_multi) is pinned to ONE embed per query batch
    # whatever the number of output channels (DESIGN.md §15)
    obs.count("lattice.embeds")
    n, d = z.shape
    el = elevate(z, spacing)  # (n, d+1)

    # Round to the nearest remainder-0 point (multiples of d+1).
    v = el / (d + 1.0)
    rem0 = jnp.round(v) * (d + 1.0)  # (n, d+1) float
    rank = descending_rank(el - rem0)

    # Fix up so coordinates sum to zero on the lattice plane.
    coordsum = jnp.round(jnp.sum(rem0, axis=1) / (d + 1.0)).astype(jnp.int32)
    rank = rank + coordsum[:, None]
    under = rank < 0
    over = rank > d
    rank = jnp.where(under, rank + (d + 1), jnp.where(over, rank - (d + 1), rank))
    rem0 = jnp.where(under, rem0 + (d + 1.0), jnp.where(over, rem0 - (d + 1.0), rem0))

    # Barycentric weights from the (fixed-up) differential, sorted by rank.
    delta = (el - rem0) / (d + 1.0)  # (n, d+1)
    weights = _rank_scatter(rank, delta, affine=True)  # (n, d+1)

    # Vertex keys: rem0 + canonical_k[rank] with
    # canonical_k[r] = k - (d+1) * (r + k > d).
    rem0_i = jnp.round(rem0).astype(jnp.int32)  # exact multiples of d+1
    k = jnp.arange(d + 1, dtype=jnp.int32)[None, :, None]  # (1, d+1, 1) vertex idx
    rk = rank[:, None, :]  # (1 -> n, 1, d+1) coordinate ranks
    canon = k - (d + 1) * ((rk + k) > d).astype(jnp.int32)  # (n, d+1, d+1)
    keys = rem0_i[:, None, :] + canon
    return keys, weights.astype(jnp.float32), rank


def simplex_embed(z: Array, spacing: float):
    """Find enclosing-simplex vertices + barycentric weights for each input.

    Vectorized port of the rounding algorithm of Adams et al. (2010) §3.
    Returns:
      keys:    (n, d+1, d+1) int32 — lattice coordinates of the d+1 vertices.
      weights: (n, d+1) float32 — barycentric interpolation weights (sum to 1).
    """
    keys, weights, _ = simplex_embed_ranked(z, spacing)
    return keys, weights


def embed_weight_tangent(rank: Array, z_dot: Array, spacing: float) -> Array:
    """Directional derivative of the barycentric weights (DESIGN.md §15).

    Within a simplex cell the weights are AFFINE in the query: the round
    and the ranks are locally constant, so the tangent is just the linear
    ``elevate`` of the direction pushed through the same rank scatter —
    O(d^2) per point, no rounding, no probes. On a cell boundary this is
    the one-sided derivative of the cell ``descending_rank`` selected.
    Each row sums to zero (the weights always sum to 1).

    Args: rank (n, d+1) from ``simplex_embed_ranked``; z_dot (n, d) the
    input-space direction. Returns dw (n, d+1).
    """
    d = z_dot.shape[1]
    ddelta = elevate(z_dot, spacing) / (d + 1.0)
    return _rank_scatter(rank, ddelta)


def embed_weight_jacobian(rank: Array, spacing: float,
                          dtype=jnp.float32) -> Array:
    """Full Jacobian dW/dz of the barycentric weights: (n, d+1, d).

    ``embed_weight_tangent`` evaluated on the d basis directions at once:
    the constant per-coordinate differential Jacobian ``d delta / d z``
    (elevation is linear, so it is rank-independent) scattered per point
    by the cell's ranks. Row k of each point's Jacobian is the gradient
    of weight w_k; columns sum to zero over k.
    """
    n, dp1 = rank.shape
    d = dp1 - 1
    ej = elevate(jnp.eye(d, dtype=dtype), spacing)  # (d, d+1): row j = del/dz_j
    dd = jnp.transpose(ej) / (d + 1.0)  # (d+1, d): dd[i, j] = ddelta_i/dz_j
    return _rank_scatter(rank, jnp.broadcast_to(dd[None], (n, dp1, d)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Lattice:
    """Static-shape lattice structure; a pytree safe to pass through jit.

    Rows ``0..cap-1`` of every per-lattice-point array are (potentially)
    valid slots; row ``cap`` is the dump/sentinel row, kept at zero so that
    out-of-range gathers contribute nothing.
    """

    coords: Array  # (cap+1, d+1) int32: lattice point coordinates
    valid: Array  # (cap+1,) bool
    m: Array  # () int32: number of unique lattice points (may exceed cap!)
    seg_ids: Array  # (n*(d+1),) int32 in [0, cap]: slot per (input, vertex)
    weights: Array  # (n, d+1) f32 barycentric
    nbr: Array  # (d+1, cap+1, 2r) int32 in [0, cap]: blur gather table
    overflow: Array  # () bool: results invalid (capacity OR pack overflow)
    pack_overflow: Array  # () bool: |coord| > 2^15 — growing cap CANNOT fix
    # --- sorted splat plan (DESIGN.md §8): the dedup sort already places all
    # (input, vertex) contributions of one lattice point contiguously; these
    # four arrays let splat run as gather + segmented prefix scan + gather,
    # with no scatter/atomics — the hot-path trick of the fused MVM kernel.
    sort_row: Array  # (n*(d+1),) int32: input row of the k-th sorted contribution
    sort_w: Array  # (n*(d+1),) f32: its barycentric weight
    seg_head: Array  # (n*(d+1),) bool: True at the first member of each slot
    row_last: Array  # (cap+1,) int32: sorted index of each slot's last member
    d: int = dataclasses.field(metadata=dict(static=True))
    r: int = dataclasses.field(metadata=dict(static=True))
    cap: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    # which build path produced this lattice ("sort" / "hash_xla" /
    # "hash_pallas"). Slot NUMBERING differs between paths (lex order vs
    # hash placement) while the operator is equivalent; caches must key on
    # it so lattices from different paths never alias.
    build_backend: str = dataclasses.field(default="sort",
                                           metadata=dict(static=True))


def _lex_sort(cols: Sequence[Array], payloads: Sequence[Array]):
    out = jax.lax.sort(tuple(cols) + tuple(payloads), num_keys=len(cols))
    return out[: len(cols)], out[len(cols):]


# --- packed sort keys (§Perf iteration C1/C2) -------------------------------
# Lattice coordinates sum to zero, so the last one is redundant; the first
# d are packed two-per-int32 (16-bit biased fields, wrap-tolerant: sorts
# need only group equal keys adjacently, not order them meaningfully).
# This halves (+1/(d+1)) the lex-sort key traffic of both the dedup sort
# and the neighbor-table merge sort — the dominant cost of the lattice
# build at houseelectric scale. C2 goes further: packing is lossless, so
# the dedup sort carries NO coordinate payload columns (coords are
# reconstructed by ``_unpack_key_cols`` after the sort), and the neighbor
# sort folds its tag and payload into a single column. Coordinates beyond
# +/-2^15 would corrupt the packing; they instead raise the existing
# ``overflow`` flag (the same grow-and-retry contract as capacity overflow).

_PACK_BIAS = 1 << 15
_PACK_LIMIT = (1 << 15) - 2
_TAG_SHIFT = 30  # neighbor-sort tag bit position (payload ids < 2^30)


def _pack_key_cols(keys: Array) -> list[Array]:
    """(..., d+1) int32 coords -> ceil(d/2) int32 sort columns, one entry
    per key in row-major order of the leading axes.

    Packs along the last axis BEFORE flattening the leading ones: on the
    TPU, reshaping the (..., d+1) coordinate array itself into key rows is
    a relayout whose compile time grows with the row count (about 90 s for
    protein's 203k training vertices), while the packed columns flatten
    for free.
    """
    c = keys.shape[-1]  # the last coord is -(sum of others): not packed
    cols = []
    for start in range(0, c - 1, 2):
        hi = keys[..., start].astype(jnp.int32) + _PACK_BIAS
        if start + 1 < c - 1:
            lo = keys[..., start + 1].astype(jnp.int32) + _PACK_BIAS
        else:
            lo = jnp.zeros_like(hi)
        cols.append(((hi << 16) | lo).reshape(-1))
    return cols


def _unpack_key_cols(packed: Array, c: int) -> Array:
    """(N, ceil((c-1)/2)) packed sort columns -> (N, c) int32 coords.

    Exact inverse of ``_pack_key_cols`` within the +/-_PACK_LIMIT range;
    the dropped last coordinate is recovered from the zero-sum constraint.
    """
    fields = []
    for j in range(c - 1):
        word = packed[:, j // 2]
        f = (word >> 16) if j % 2 == 0 else word
        fields.append((f & 0xFFFF).astype(jnp.int32) - _PACK_BIAS)
    rest = jnp.stack(fields, axis=1)
    last = -jnp.sum(rest, axis=1, keepdims=True)
    return jnp.concatenate([rest, last], axis=1)


def _pack_overflow(keys: Array) -> Array:
    return jnp.any(jnp.abs(keys) > _PACK_LIMIT)


def default_capacity(n: int, d: int) -> int:
    """Worst case m = n (d+1) (paper Table 3's L)."""
    return n * (d + 1)


@functools.partial(jax.jit, static_argnames=("hcap",))
def _distinct_keys(packed: Array, hcap: int) -> Array:
    owner, _, _ = hash_ops.hash_insert(packed, hcap, backend="hash_xla")
    return jnp.sum((owner < packed.shape[0]).astype(jnp.int32))


def estimate_m(z: Array, spacing: float, *, sample: int = 4096) -> int:
    """Estimate the deduplicated lattice size m by hash-inserting a subsample.

    ``suggest_capacity``'s constant-occupancy guess knows nothing about the
    data; this inserts the vertex keys of an evenly-strided subsample at
    THREE scales (s/4, s/2, s) and extrapolates with the fitted power law
    ``m(n) ~ n^gamma`` (gamma in [0, 1]: 0 = the subsample already saturated
    the lattice, 1 = every point contributes fresh vertices). Exact when
    ``sample >= n``. Eager-only (returns a concrete int); cost is one
    O(sample * d) insert per scale — trivial next to a full build.

    Why three points: on MULTI-SCALE data (tight clusters on a sparse
    background) the growth curve is convex in log-log — small subsamples
    saturate the within-cluster vertices, so the s/2 -> s slope is steeper
    than the s/4 -> s/2 one, and the old 2-point fit (which only saw the
    coarser average slope through the saturated regime, or worse,
    underestimated via a lucky flat segment) produced caps that overflow
    and pay the grow-and-retry rebuild. The estimator fits gamma by
    least squares over the three log-log points, then applies a
    MONOTONICITY SANITY CHECK on the segment slopes: the nested prefixes
    guarantee m(s/4) <= m(s/2) <= m(s), so if the tail slope exceeds the
    head slope (convex growth — the multi-scale signature), the tail
    slope is the better predictor of what extrapolation will meet and
    wins over the least-squares average. Underestimates only cost a
    grow-and-retry rebuild (the overflow flag catches them), so the
    check deliberately resolves ambiguity upward.
    """
    n, d = z.shape
    s = min(n, max(int(sample), 64))
    stride = max(1, n // s)
    zs = z[::stride][:s]
    s = int(zs.shape[0])

    def distinct(zz) -> int:
        keys, _ = simplex_embed(zz, spacing)
        packed = jnp.stack(_pack_key_cols(keys), axis=1)
        return int(_distinct_keys(
            packed, hash_ops.hash_capacity(zz.shape[0] * (d + 1))))

    m_s = distinct(zs)
    if s >= n:
        return m_s  # the "subsample" was the whole set: exact
    half = max(s // 2, 32)
    quarter = max(half // 2, 16)
    m_h = distinct(zs[:half])
    m_q = distinct(zs[:quarter]) if quarter < half else m_h
    # log-log samples; prefixes nest, so counts are non-decreasing by
    # construction — max() below only guards degenerate tiny samples
    pts = [(math.log(quarter), math.log(max(m_q, 1))),
           (math.log(half), math.log(max(min(m_h, m_s), m_q, 1))),
           (math.log(s), math.log(max(m_s, 1)))]
    xm = sum(p[0] for p in pts) / 3
    ym = sum(p[1] for p in pts) / 3
    den = sum((p[0] - xm) ** 2 for p in pts)
    gamma_lsq = sum((p[0] - xm) * (p[1] - ym) for p in pts) / max(den, 1e-12)
    g_head = (pts[1][1] - pts[0][1]) / max(pts[1][0] - pts[0][0], 1e-12)
    g_tail = (pts[2][1] - pts[1][1]) / max(pts[2][0] - pts[1][0], 1e-12)
    # monotonicity sanity check: convex growth (tail steeper than head)
    # means the least-squares slope is dragged down by the saturated
    # small-sample regime — trust the tail, the regime extrapolation
    # actually enters
    gamma = g_tail if g_tail > g_head else gamma_lsq
    gamma = min(max(gamma, 0.0), 1.0)
    return int(math.ceil(m_s * (n / s) ** gamma))


def suggest_capacity(n: int, d: int, spacing: float, *, r: int = 1,
                     c: int = 1, vmem_aware: bool = True,
                     z: Array | None = None, sample: int = 4096) -> int:
    """Heuristic starting capacity for grow-and-retry builds.

    The worst case m = n (d+1) is wildly pessimistic for real data (paper
    Table 3: m/L between 0.02 and 0.4), and every per-lattice-point array —
    the neighbor table above all — scales with cap, so over-allocating is
    the dominant build cost AND what keeps the fused kernel's table out of
    VMEM. Start from a constant-occupancy guess (wider stencil spacing means
    coarser cells, hence fewer of them), round up to a power of two, and let
    ``build_lattice_auto`` grow on overflow.

    ``z`` (the lengthscale-normalized points about to be embedded) switches
    to the data-aware guess: ``estimate_m`` hash-inserts a subsample and the
    cap starts at the estimate plus modest headroom, instead of the blind
    constant-occupancy formula — on clustered data this shrinks the
    neighbor table, the fused-MVM VMEM plan, and the frozen serving tables
    (DESIGN.md §12) by the m/guess ratio. Underestimates are safe: the
    grow-and-retry contract catches them via the overflow flag.

    ``vmem_aware`` guards the power-of-two rounding against silently
    defeating ``kernels.blur.ops.fits_vmem``: when the raw guess fits the
    fused MVM's VMEM plan (for ``r`` and ``c`` channels) but the rounded
    cap does not, the suggestion is clamped to the largest fitting cap
    instead of spilling the fusion. A guess that does not fit even
    unrounded is returned as-is — occupancy beats fusion (the blocked/XLA
    tiers handle oversized tables; under-capacity would corrupt results).
    The fused plan is that of the explicit ``fused_pallas`` tier; ``auto``
    runs ``fused_xla``, which has no VMEM plan (ROADMAP S2).
    """
    if z is not None and not isinstance(z, jax.core.Tracer):
        guess = max(1024, int(1.25 * estimate_m(z, spacing, sample=sample)))
    else:
        guess = max(1024, int(n * (d + 1) / (8.0 * max(spacing, 0.25))))
    # round up to a power of two, but never past the provable worst case
    cap = min(1 << (guess - 1).bit_length(), default_capacity(n, d))
    if vmem_aware:
        from repro.kernels.blur import ops as blur_ops  # cycle-safe: lazy
        if blur_ops.fits_vmem(n, d, r, guess + 1, c) and \
                not blur_ops.fits_vmem(n, d, r, cap + 1, c):
            cap = max(guess, min(cap, blur_ops.max_cap_for_vmem(n, d, r, c)))
    return min(cap, default_capacity(n, d))


def build_lattice_auto(z: Array, *, spacing: float, r: int = 1,
                       cap: int | None = None, growth: int = 4,
                       max_tries: int = 6,
                       backend: str = "auto") -> "Lattice":
    """Grow-and-retry wrapper: start at ``suggest_capacity`` and multiply by
    ``growth`` until the table fits (overflow flag clear).

    Syncs on the overflow flag, so call it OUTSIDE jit (amortized: once per
    hyperparameter setting). Inside jit, use ``build_lattice`` with a static
    cap as before. ``backend`` selects the build path (see
    ``build_lattice``); the overflow/grow contract is identical across
    paths.
    """
    n, d = z.shape
    worst = default_capacity(n, d)
    with obs.span("build_auto") as attrs:
        if cap is None:
            cap = suggest_capacity(n, d, spacing, r=r, z=z)
        for tries in range(1, max_tries + 1):
            attrs["tries"] = tries
            lat = build_lattice(z, spacing=spacing, r=r, cap=min(cap, worst),
                                backend=backend)
            if bool(lat.pack_overflow):
                # coordinate range, not capacity: growth cannot help —
                # return with the overflow flag set so the caller sees
                # invalid results
                return lat
            if not bool(lat.overflow) or cap >= worst:
                return lat
            cap *= growth
        return lat  # pragma: no cover - exhausts only past worst case


def build_lattice(z: Array, *, spacing: float, r: int = 1,
                  cap: int | None = None, backend: str = "auto") -> Lattice:
    """Construct the lattice for (already lengthscale-normalized) inputs.

    Args:
      z: (n, d) float32 — inputs in the normalized metric of the kernel.
      spacing: §4.1 stencil spacing s (input-space distance of a lattice step).
      r: stencil radius (paper's blur order; Appendix A uses r=1).
      cap: static table capacity; defaults to the worst case n*(d+1).
        Prefer an auto-sized cap (``build_lattice_auto`` outside jit) — every
        per-lattice-point array scales with it.
      backend: build path (kernels/hash/ops.py policy). "auto" resolves to
        the hash build (hash_xla on every host); "sort" keeps the
        original two-pass lexicographic-sort build as the bit-exact
        lex-ordered oracle. All paths produce operator-equivalent
        lattices (same splat->blur->slice results up to slot permutation
        + f32 accumulation noise) with identical overflow/pack_overflow
        semantics.
    """
    n, d = z.shape
    if cap is None:
        cap = default_capacity(n, d)
    # the ``lattice.builds`` counter: once per traced build under jit, i.e.
    # the constructions baked into the compiled program — what the
    # shared-lattice pipeline (DESIGN.md §9) drives to 1 per training step
    # and per posterior
    obs.count("lattice.builds")
    resolved = hash_ops.resolve_build_backend(backend)
    if resolved == "sort":
        return _build_lattice_impl(z, spacing=spacing, r=r, cap=cap)
    return _build_lattice_hash_impl(z, spacing=spacing, r=r, cap=cap,
                                    backend=resolved)


@functools.partial(jax.jit, static_argnames=("r", "cap"))
def _build_lattice_impl(z: Array, *, spacing: float, r: int,
                        cap: int) -> Lattice:
    n, d = z.shape
    big = n * (d + 1)
    with obs.scope("build"):
        with obs.scope("build.embed"):
            keys, weights = simplex_embed(z, spacing)  # (n,d+1,d+1), (n,d+1)
            cols = _pack_key_cols(keys)

        # ---- exact dedup via lexicographic sort over PACKED keys -----------
        # Packing is lossless (C2), so the only payload is the permutation;
        # the sorted coordinates come back out of the packed key columns.
        with obs.scope("build.dedup"):
            payload = jnp.arange(big, dtype=jnp.int32)
            sorted_cols, (perm,) = _lex_sort(cols, [payload])
            spacked = jnp.stack(sorted_cols, axis=1)
            skeys = _unpack_key_cols(spacked, d + 1)  # (big, d+1) sorted
            new_group = jnp.concatenate([
                jnp.ones((1,), bool),
                jnp.any(spacked[1:] != spacked[:-1], axis=1),
            ])
            uid_sorted = jnp.cumsum(new_group.astype(jnp.int32)) - 1
            m = uid_sorted[-1] + 1
            pack_ovf = _pack_overflow(keys)
            overflow = (m > cap) | pack_ovf
            # overflowed uniques -> dump row
            slot_sorted = jnp.minimum(uid_sorted, cap)

            # lattice point coords (every member of a group writes the same)
            coords = jnp.zeros((cap + 1, d + 1), jnp.int32).at[
                slot_sorted].set(skeys)
            valid = jnp.zeros((cap + 1,), bool).at[slot_sorted].set(True)
            valid = valid.at[cap].set(False)

            # per-(input, vertex) slot ids, back in original order
            seg_ids = jnp.zeros((big,), jnp.int32).at[perm].set(slot_sorted)

        # ---- sorted splat plan (DESIGN.md §8) ------------------------------
        # Contributions in sorted order: original flat index f = i*(d+1) + k,
        # so the input row is f // (d+1); segment boundaries are the dedup
        # groups; the last member per slot indexes the segmented prefix
        # scan's result.
        with obs.scope("build.splat_plan"):
            sort_row = perm // (d + 1)
            sort_w = weights.reshape(big)[perm]
            idx = jnp.arange(big, dtype=jnp.int32)
            row_last = jnp.zeros((cap + 1,), jnp.int32).at[slot_sorted].max(
                idx)

        # ---- blur neighbor table via merge-sort lookup ---------------------
        with obs.scope("build.neighbours"):
            nbr = _neighbor_table(coords, valid, d=d, r=r, cap=cap)

    return Lattice(coords=coords, valid=valid, m=m, seg_ids=seg_ids,
                   weights=weights, nbr=nbr, overflow=overflow,
                   pack_overflow=pack_ovf, sort_row=sort_row, sort_w=sort_w,
                   seg_head=new_group, row_last=row_last, d=d, r=r, cap=cap,
                   n=n)


def _neighbor_queries(coords: Array, valid: Array, *, d: int, r: int,
                      cap: int):
    """Packed ``±1..±r`` neighbor-query keys for every (direction, slot).

    Shared by BOTH build paths (sort merge-lookup and hash lookup) and by
    the build benchmark's phase breakdown, so the query grid — offsets,
    flattening order, validity masking — can never desynchronize between
    the oracle and the fast path. Queries are ordered (direction, step,
    slot) with the slot fastest: on the TPU, flattening a grid whose
    minor axis is the 2r steps is a relayout whose compile time grows
    with cap, so ``_neighbor_layout`` moves the steps minor only after
    the lookup, on the (d+1, 2r, cap+1) result. Returns:
      q_packed:  ((d+1)(2r)(cap+1), npk) int32 packed query keys;
      src_valid: same leading shape, bool — whether the SOURCE slot of
        each query is a valid lattice point (invalid sources must miss).
    """
    # offsets along direction a: -1 everywhere, +d at coordinate a
    eye = jnp.eye(d + 1, dtype=jnp.int32)
    dirs = (d + 1) * eye - 1  # (d+1, d+1): dirs[a] = offset of +1 step along a

    steps = jnp.concatenate([jnp.arange(-r, 0), jnp.arange(1, r + 1)])  # (2r,)
    # queries[a, s, p] = coords[p] + steps[s] * dirs[a]: (d+1, 2r, cap+1, d+1)
    table = coords[: cap + 1]  # includes dump row; masked via src_valid
    q = (table[None, None, :, :]
         + steps[None, :, None, None] * dirs[:, None, None, :])
    q_packed = jnp.stack(_pack_key_cols(q), axis=1)
    src_valid = jnp.tile(valid[: cap + 1], (d + 1) * 2 * r)
    return q_packed, src_valid


def _neighbor_layout(found: Array, *, d: int, r: int, cap: int) -> Array:
    """Per-query results in ``_neighbor_queries`` order -> (d+1, cap+1, 2r)."""
    return jnp.transpose(found.reshape(d + 1, 2 * r, cap + 1), (0, 2, 1))


def _neighbor_table(coords: Array, valid: Array, *, d: int, r: int,
                    cap: int) -> Array:
    """Resolve, for each lattice point and direction, the slots of its
    ``±1..±r`` neighbors. Returns (d+1, cap+1, 2r) int32 with misses -> cap.

    Strategy: concat [table entries (tag 0), neighbor queries (tag 1)],
    lex-sort by (coords..., tag); every query's match, if present, is the
    closest preceding tag-0 entry with identical coordinates.
    """
    q_packed, src_valid = _neighbor_queries(coords, valid, d=d, r=r, cap=cap)
    nq = q_packed.shape[0]
    t_packed = jnp.stack(_pack_key_cols(coords[: cap + 1]), axis=1)
    # invalid sources/entries get out-of-band packed cols
    q_packed = jnp.where(src_valid[:, None], q_packed, INT_SENTINEL_B)
    t_packed = jnp.where(valid[:, None], t_packed, INT_SENTINEL_A)

    all_keys = jnp.concatenate([t_packed, q_packed], axis=0)
    npk = all_keys.shape[1]
    # C2: tag and payload share one sort column — tag in the top bits so
    # table entries (tag 0) still sort before queries within a coordinate
    # group, payload in the low 30. One fewer comparator/payload column.
    assert nq < (1 << _TAG_SHIFT), "query id would overflow the tag packing"
    comb = jnp.concatenate([
        jnp.arange(cap + 1, dtype=jnp.int32),  # tag 0 | table slot
        (1 << _TAG_SHIFT) + jnp.arange(nq, dtype=jnp.int32),  # tag 1 | qid
    ])
    key_cols = [all_keys[:, j] for j in range(npk)] + [comb]
    sorted_cols, _ = _lex_sort(key_cols, [])
    scoords = jnp.stack(sorted_cols[: npk], axis=1)  # (N, npk) packed
    stag = sorted_cols[npk] >> _TAG_SHIFT
    spayload = sorted_cols[npk] & ((1 << _TAG_SHIFT) - 1)

    nfull = scoords.shape[0]
    pos = jnp.arange(nfull, dtype=jnp.int32)
    # forward-fill the position of the most recent table entry; a query
    # matches iff that entry has identical coordinates (tag 0 sorts first
    # within a coordinate group, and table entries are unique).
    last_a_pos = jax.lax.cummax(jnp.where(stag == 0, pos, -1))
    cand = jnp.maximum(last_a_pos, 0)
    same = jnp.all(scoords[cand] == scoords, axis=1) & (last_a_pos >= 0)
    matched_slot = jnp.where(same & (stag == 1), spayload[cand], cap)

    # scatter back: query id -> matched slot (non-queries dropped via OOB)
    is_q = stag == 1
    out = jnp.full((nq,), cap, jnp.int32).at[
        jnp.where(is_q, spayload, nq)
    ].set(matched_slot, mode="drop")
    return _neighbor_layout(out, d=d, r=r, cap=cap)


# ---------------------------------------------------------------------------
# Hash-based build (DESIGN.md §11): same Lattice, no lexicographic sorts.
# ---------------------------------------------------------------------------


def _counting_plan_shape(dom: int) -> tuple[int, int]:
    """(block, unroll) for ``_splat_plan_counting``, tuned on this host:
    small count states amortize the scan with deep unrolling; large ones
    are carry-copy bound and prefer fewer, wider steps."""
    return (64, 32) if dom <= (1 << 15) + 2 else (128, 8)


def _splat_plan_counting(seg_ids: Array, *, big: int, cap: int):
    """Group contributions by slot for the §8 splat plan — NO ``lax.sort``.

    A stable counting/partition construction over the already-known slot
    ids (ROADMAP item; replaces the single-column ``(slot << bits) | row``
    sort AND its two-array fallback): each contribution's destination is
    ``start[slot] + rank``, where ``start`` is the exclusive cumsum of the
    per-slot counts and ``rank`` is the contribution's stable index among
    same-slot predecessors. The rank — the only genuinely hard part of a
    sort-free counting sort — splits across ``B``-element blocks:

      * within a block: a lower-triangular pairwise equality count
        (``big * B`` comparisons, fully vectorized);
      * across blocks: ONE ``lax.scan`` over blocks carrying the running
        per-slot count table — gather-before-update yields each element's
        count over strictly earlier blocks, and the carry aliases in
        place, so the sweep is O(big) work + O(big / B) sequential steps
        (``K`` blocks unrolled per step to amortize loop overhead) with
        no (blocks x domain) histogram ever materialized.

    The resulting order is bit-identical to the stable sort it replaces
    (ascending slot, original row order within a slot), so the splat plan
    — and the fused kernel's segmented scan — are unchanged. All
    primitives are gathers, scatters, and cumsums; the hash build's jaxpr
    is asserted sort-free in tests/test_lattice_hash.py.
    """
    dom = cap + 2  # slots 0..cap, plus a padding value colliding with nothing
    bsz, unroll = _counting_plan_shape(dom)
    chunk = bsz * unroll
    padded = -(-big // chunk) * chunk
    seg_p = seg_ids if padded == big else jnp.concatenate(
        [seg_ids, jnp.full((padded - big,), cap + 1, jnp.int32)])
    blocks = seg_p.reshape(padded // bsz, bsz)

    # stable rank within each block: #{j < i in block : seg_j == seg_i}
    tri = jnp.tril(jnp.ones((bsz, bsz), bool), k=-1)  # [i, j]: j < i
    eq = blocks[:, :, None] == blocks[:, None, :]  # [b, i, j]
    local = jnp.sum(eq & tri[None], axis=2).astype(jnp.int32).reshape(padded)

    # cross-block prefix: count of each slot over all EARLIER blocks,
    # carried through the scan (read the count, then add the block)
    def body(cnt, bs):  # bs: (unroll, bsz)
        crosses = []
        for k in range(unroll):
            crosses.append(cnt[bs[k]])
            cnt = cnt.at[bs[k]].add(1)
        return cnt, jnp.stack(crosses)

    cnt, cross = jax.lax.scan(body, jnp.zeros((dom,), jnp.int32),
                              seg_p.reshape(padded // chunk, unroll, bsz))
    rank = (cross.reshape(padded) + local)[:big]

    # destination = slot's exclusive start + stable rank; a bijection on
    # [0, big), so one permutation scatter materializes the plan
    starts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(cnt[: cap + 1])[:-1].astype(jnp.int32)])
    dest = starts[seg_ids] + rank
    perm = jnp.zeros((big,), jnp.int32).at[dest].set(
        jnp.arange(big, dtype=jnp.int32))
    seg_sorted = jnp.zeros((big,), jnp.int32).at[dest].set(seg_ids)
    return seg_sorted, perm


@functools.partial(jax.jit, static_argnames=("r", "cap", "backend"))
def _build_lattice_hash_impl(z: Array, *, spacing: float, r: int, cap: int,
                             backend: str) -> Lattice:
    """Open-addressing build: insert for dedup, lookup for neighbors.

    Replaces both ``_lex_sort`` passes of ``_build_lattice_impl`` with the
    kernels/hash table — O(n d · probes) with near-constant probes at
    <= 0.5 occupancy — and derives the sorted splat plan from the
    counting/partition construction (``_splat_plan_counting``), making the
    whole hash build — embed, dedup, neighbors, plan — free of
    ``lax.sort``. Produces an operator-equivalent ``Lattice``: identical
    deduplicated point set, seg structure, neighbor graph, and
    overflow/pack_overflow semantics; only the slot NUMBERING (hash
    placement vs lex order) differs.
    """
    n, d = z.shape
    big = n * (d + 1)
    hcap = hash_ops.hash_capacity(cap)
    with obs.scope("build"):
        with obs.scope("build.embed"):
            keys, weights = simplex_embed(z, spacing)  # (n,d+1,d+1), (n,d+1)
            packed = jnp.stack(_pack_key_cols(keys), axis=1)  # (big, npk)

        # ---- dedup via hash insert ----------------------------------------
        with obs.scope("build.dedup"):
            owner, slot_row, row_ok = hash_ops.hash_insert(packed, hcap,
                                                           backend=backend)
            occ = owner < big  # occupied hash slots (owner id < N, EMPTY N)
            m = jnp.sum(occ.astype(jnp.int32))
            dense = jnp.cumsum(occ.astype(jnp.int32)) - 1  # slot -> dense id
            dense_of = jnp.where(occ, jnp.minimum(dense, cap), cap)
            tkeys = hash_ops.table_keys(owner, packed)  # empty -> SENT
            pack_ovf = _pack_overflow(keys)
            overflow = (m > cap) | ~jnp.all(row_ok) | pack_ovf

            # per-(input, vertex) slot ids, already in original order
            seg_ids = jnp.where(row_ok, dense_of[slot_row], cap)

            # dense lattice-point table (scatter over hcap rows only)
            dense_clip = jnp.where(occ & (dense < cap), dense, cap)
            coords = jnp.zeros((cap + 1, d + 1), jnp.int32).at[
                dense_clip].set(
                jnp.where(occ[:, None], _unpack_key_cols(tkeys, d + 1), 0))
            valid = jnp.zeros((cap + 1,), bool).at[dense_clip].set(occ)
            valid = valid.at[cap].set(False)

        # ---- sorted splat plan (DESIGN.md §8) ------------------------------
        with obs.scope("build.splat_plan"):
            seg_sorted, perm = _splat_plan_counting(seg_ids, big=big, cap=cap)
            sort_row = perm // (d + 1)
            sort_w = weights.reshape(big)[perm]
            seg_head = jnp.concatenate([jnp.ones((1,), bool),
                                        seg_sorted[1:] != seg_sorted[:-1]])
            # last sorted index per slot via binary search (no scatter):
            # seg_sorted is sorted, so right-boundary - 1 is each slot's last
            row_last = jnp.clip(
                jnp.searchsorted(seg_sorted,
                                 jnp.arange(cap + 1, dtype=jnp.int32),
                                 side="right").astype(jnp.int32) - 1,
                0, big - 1)

        # ---- blur neighbor table via hash lookup ---------------------------
        with obs.scope("build.neighbours"):
            q_packed, src_valid = _neighbor_queries(coords, valid, d=d, r=r,
                                                    cap=cap)
            hres = hash_ops.hash_lookup(tkeys, q_packed, src_valid, hcap,
                                        backend=backend)
            nbr = _neighbor_layout(
                jnp.where(src_valid & (hres >= 0),
                          dense_of[jnp.clip(hres, 0, hcap - 1)], cap),
                d=d, r=r, cap=cap)

    return Lattice(coords=coords, valid=valid, m=m, seg_ids=seg_ids,
                   weights=weights, nbr=nbr, overflow=overflow,
                   pack_overflow=pack_ovf, sort_row=sort_row, sort_w=sort_w,
                   seg_head=seg_head, row_last=row_last, d=d, r=r, cap=cap,
                   n=n, build_backend=backend)


# ---------------------------------------------------------------------------
# Splat / Blur / Slice (paper §3.2) — the three SKI factors W^T, K_UU, W.
# ---------------------------------------------------------------------------


# Splat and slice touch the d+1 vertices of each point one vertex at a
# time: materializing all n(d+1) contributions as an (n, d+1, c) array and
# reshaping it to rows is a TPU relayout whose compile time grows with n
# (78 s for splat, 17 s for slice at protein's training size, against 18 s
# and 2 s this way).


def splat_rows(v: Array, seg: Array, weights: Array, rows: int) -> Array:
    """Scatter-add ``weights[:, k] * v`` into rows ``seg[:, k]`` for every
    vertex k: (n, c) values, (n, d+1) slots and weights -> (rows, c)."""
    out = jnp.zeros((rows, v.shape[1]), v.dtype)
    for k in range(seg.shape[1]):
        out = out.at[seg[:, k]].add(weights[:, k:k + 1] * v)
    return out


def slice_rows(vals: Array, seg: Array, weights: Array) -> Array:
    """Barycentric contraction ``sum_k weights[:, k] * vals[seg[:, k]]``:
    (rows, c) table, (n, d+1) slots and weights -> (n, c)."""
    out = weights[:, :1] * vals[seg[:, 0]]
    for k in range(1, seg.shape[1]):
        out = out + weights[:, k:k + 1] * vals[seg[:, k]]
    return out


def splat(lat: Lattice, v: Array) -> Array:
    """W^T v: scatter barycentric-weighted values onto lattice points.

    v: (n, c) -> (cap+1, c); dump row forced to zero.
    """
    out = splat_rows(v, lat.seg_ids.reshape(lat.n, lat.d + 1), lat.weights,
                     lat.cap + 1)
    return out.at[lat.cap].set(0.0)


def splat_sorted(lat: Lattice, v: Array) -> Array:
    """W^T v without any scatter: the fused-backend splat (DESIGN.md §8).

    Uses the build-time sorted plan: gather each sorted contribution's input
    row, run a segmented inclusive prefix scan (log-depth, pure vector ops —
    the XLA analogue of the fused Pallas kernel's in-VMEM Hillis-Steele
    sweep), and read each slot's total at its last member. Equivalent to
    ``splat`` as a linear map; summation order differs, so results agree to
    f32 accumulation noise only.
    """
    c = v.shape[1]
    contrib = lat.sort_w[:, None] * jnp.take(v, lat.sort_row, axis=0)
    carry = jnp.where(lat.seg_head, 0.0, 1.0)[:, None].astype(v.dtype)

    def comb(a, b):
        g1, v1 = a
        g2, v2 = b
        return g1 * g2, v2 + g2 * v1

    _, scanned = jax.lax.associative_scan(comb, (carry, contrib), axis=0)
    out = jnp.take(scanned, lat.row_last, axis=0)
    out = jnp.where(lat.valid[:, None], out, jnp.zeros((1, c), v.dtype))
    return out.at[lat.cap].set(0.0)


def blur_one_direction(lat: Lattice, vals: Array, stencil: Array,
                       direction: Array) -> Array:
    """Convolve lattice values with the stencil along one lattice direction."""
    nb = lat.nbr[direction]  # (cap+1, 2r)
    r = lat.r
    out = vals * stencil[r]
    gathered = vals[nb]  # (cap+1, 2r, c) ; dump row is zero
    w = jnp.concatenate([stencil[:r], stencil[r + 1:]])  # (2r,)
    out = out + jnp.einsum("prc,r->pc", gathered, w)
    return out.at[lat.cap].set(0.0)


def blur(lat: Lattice, vals: Array, stencil: Array, *,
         reverse: bool = False) -> Array:
    """Sequential separable blur along the d+1 lattice directions.

    ``reverse=True`` runs directions in the opposite order, which is exactly
    the transpose of the forward blur (each directional blur is symmetric) —
    used for the adjoint in lattice_filter's custom VJP and for the
    symmetrized operator 0.5 (F + F^T).
    """
    order = jnp.arange(lat.d + 1)
    if reverse:
        order = order[::-1]

    def body(carry, a):
        return blur_one_direction(lat, carry, stencil, a), None

    out, _ = jax.lax.scan(body, vals, order)
    return out


def slice_(lat: Lattice, vals: Array) -> Array:
    """W u: barycentric resampling back at the input locations. -> (n, c)"""
    return slice_rows(vals, lat.seg_ids.reshape(lat.n, lat.d + 1),
                      lat.weights)


# ---------------------------------------------------------------------------
# Frozen lattice index (DESIGN.md §12): slice-only queries at NEW points.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LatticeIndex:
    """Hash index over a built lattice's occupied points.

    The serving-path complement of ``Lattice``: where the build resolves
    blur neighbors once for the training points, the index lets FROZEN
    per-lattice-point tables be sliced at arbitrary new points — embed the
    query, probe ``tkeys`` for each of its d+1 enclosing vertices, map hits
    to dense rows via ``row_of_slot``. Vertices absent from the index map
    to the zero row ``m`` and contribute nothing (the standard
    permutohedral slicing semantics); their barycentric mass is the
    query's "slice miss" diagnostic. Build-path agnostic: constructed from
    the deduplicated coords, so sort- and hash-built lattices index
    identically (up to the dense row permutation, which the compacted
    tables absorb).
    """

    tkeys: Array  # (hcap, npk) int32 packed keys; empty -> ref.KEY_SENTINEL
    row_of_slot: Array  # (hcap,) int32: hash slot -> dense row in [0, m]
    slots: Array  # (m,) int32: lattice slot of each dense row (for compact)
    d: int = dataclasses.field(metadata=dict(static=True))
    hcap: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))


def lattice_index(lat: Lattice) -> LatticeIndex:
    """Build the frozen query index for ``lat``. Eager-only: the dense
    table size is the CONCRETE occupied count m (not the static cap), so
    frozen tables shrink from (cap+1) to (m+1) rows — the right-sizing
    that keeps serving tables VMEM-resident."""
    valid = np.asarray(lat.valid)
    slots = np.nonzero(valid)[0].astype(np.int32)
    m = int(slots.shape[0])
    if m == 0:
        raise ValueError("cannot index an empty lattice")
    coords = jnp.asarray(np.asarray(lat.coords)[slots])
    packed = jnp.stack(_pack_key_cols(coords), axis=1)
    hcap = hash_ops.hash_capacity(m)
    owner, _, ok = hash_ops.hash_insert(packed, hcap, backend="hash_xla")
    if not bool(jnp.all(ok)):  # pragma: no cover - unique keys, occ <= 0.5
        raise RuntimeError("lattice_index insert failed on unique keys")
    occ = owner < m
    # keys are unique, so each occupied slot's owner IS its dense row id
    row_of_slot = jnp.where(occ, owner, m).astype(jnp.int32)
    return LatticeIndex(tkeys=hash_ops.table_keys(owner, packed),
                        row_of_slot=row_of_slot, slots=jnp.asarray(slots),
                        d=lat.d, hcap=hcap, m=m)


def compact_table(index: LatticeIndex, table: Array) -> Array:
    """(cap+1, c) per-lattice-point values -> (m+1, c) dense serving table.

    Row ``m`` is the zero miss row every absent-vertex lookup lands on.
    """
    vals = jnp.take(table, index.slots, axis=0)
    return jnp.concatenate(
        [vals, jnp.zeros((1, table.shape[1]), table.dtype)], axis=0)
