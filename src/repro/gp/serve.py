"""Frozen-lattice serving (DESIGN.md §12): precomputed Simplex-GP predictor.

``gp/predict.posterior`` pays a joint-lattice build plus CG/Lanczos solves
for EVERY query batch — fine for benchmarking, fatal for serving. But SKI
prediction reduces to interpolating precomputed grid quantities (KISS-GP,
Wilson & Nickisch 2015; Yadav et al. 2021 decouple query cost from n
entirely), and on the permutohedral lattice the analogue is exact:

  mean(x*)  = k_{*,X} alpha            with alpha = K_hat^{-1} y
            = w(x*)^T  [B W^T alpha]   — slice of a PRECOMPUTED table
  var(x*)   = k(0) - || w(x*)^T [B W^T R] ||^2
            with R = Q (T + eps I)^{-1/2} the LOVE root from k Lanczos
            iterations (the same T/Q ``posterior`` uses; the inverse
            square root via the k x k eigendecomposition)

so ``freeze`` solves ONCE at train time, splats [alpha | R] onto the
train lattice, runs the 2(d+1) blur sweeps ONCE (batched over the 1 + k
channels), and keeps only the blurred value tables — compacted to the
m + 1 occupied rows — plus the hash index for vertex lookup. Per query,
``predict`` is embed (O(d^2), sort-free) + d+1 hash probes + a batched
multi-channel barycentric slice: no build, no solve, no collective, cost
independent of n. Queries landing outside the frozen lattice lose the
mass of their absent vertices (standard slicing semantics) and report it
as the ``miss_mass`` fidelity diagnostic.

Serving mechanics: ``predict`` pads each batch to a fixed bucket size
(``SimplexGPConfig.serve_buckets``) so jit compiles once per bucket
rather than once per batch shape, donates the padded query buffer, and
optionally fans queries over a device mesh with the frozen tables
REPLICATED — zero collectives, linear throughput scaling
(sharding/simplex.py's serving contract).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import pathlib
import shutil
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import filtering
from repro.core import lattice as lat_mod
from repro.core.filtering import LatticeCache
from repro.core.lattice import LatticeIndex
from repro.gp.models import GPParams, SimplexGP
from repro.runtime.checkpoint import (CheckpointCorruptError, load_blobs,
                                      read_manifest, save_blobs)
from repro.solvers.cg import cg as cg_solve
from repro.solvers.lanczos import lanczos as lanczos_run

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Predictor:
    """Immutable frozen-model state: everything a query needs, nothing else.

    ``tables`` column 0 is the mean channel (os * blurred splat of alpha);
    columns 1..k are the LOVE variance channels (os * blurred splat of the
    root R), so var = outputscale - sum_j table_j(x*)^2. A pytree — safe
    to pass through jit, replicate across a mesh, or checkpoint.

    Beyond the query tables, a Predictor carries what the serving RUNTIME
    (DESIGN.md §13) needs: the raw ``alpha`` solution so the next refresh
    can warm-start its CG solve, and the solve diagnostics
    (``cg_converged``/``cg_residual``/``cg_iterations``) so the
    ``validate_predictor`` publication gate can refuse a candidate whose
    solve silently failed. All are DATA fields — re-freezing never changes
    the treedef, so bucket compilations survive hot swaps whenever the
    array shapes (n, m, k) are unchanged.
    """

    index: LatticeIndex  # hash index over the frozen train lattice
    tables: Array  # (m+1, 1+k) f32 blurred [mean | LOVE root] channels
    lengthscale: Array  # (d,)
    outputscale: Array  # ()
    noise: Array  # () — for predictive-y variance (latent var + noise)
    alpha: Array  # (n,) K_hat^{-1} y — the warm-start seed for refreeze
    cg_converged: Array  # () bool: alpha solve hit tolerance
    cg_residual: Array  # () final relative residual of the alpha solve
    cg_iterations: Array  # () int32 iterations the alpha solve used
    spacing: float = dataclasses.field(metadata=dict(static=True))
    backend: str = dataclasses.field(default="auto",
                                     metadata=dict(static=True))
    buckets: tuple[int, ...] = dataclasses.field(
        default=(64, 256, 1024, 4096), metadata=dict(static=True))
    n_train: int = dataclasses.field(default=0, metadata=dict(static=True))


class ServeResult(NamedTuple):
    mean: Array  # (b,)
    var: Array  # (b,) latent-f variance (add pred.noise for predictive y)
    miss_mass: Array  # (b,) in [0, 1]: barycentric mass on absent vertices


class ServeGradResult(NamedTuple):
    """``predict_grad`` output: predictions + analytic query-space gradients.

    ``grad_ok`` gates validity (DESIGN.md §15): a query with miss_mass > 0
    has vertices clamped to the zero row, so its surrogate surface is
    kinked by the frozen lattice's support boundary — the gradients are
    still the exact gradients OF THE SERVED SURROGATE, but no longer
    approximate the GP posterior's. Callers must gate on ``grad_ok``
    rather than consume silently-degraded gradients.
    """

    mean: Array  # (b,) [or (b, k) from predict_multi_grad]
    var: Array  # (b,) latent-f variance
    dmean: Array  # (b, d) [or (b, k, d)] d mean / d x*
    dvar: Array  # (b, d) [or (b, k, d)] d var / d x*
    miss_mass: Array  # (b,) in [0, 1]
    grad_ok: Array  # (b,) bool: miss_mass == 0 -> gradients trustworthy


class MultiServeResult(NamedTuple):
    mean: Array  # (b, k)
    var: Array  # (b, k) latent-f variance per output channel
    miss_mass: Array  # (b,) shared across channels (one embed, one probe)


@functools.partial(jax.jit, static_argnames=("model", "variance_rank"))
def _freeze_tables(model: SimplexGP, params: GPParams, lat, x: Array,
                   y: Array, key: Array, variance_rank: int,
                   x0: Array | None = None):
    """alpha + LOVE-root solves and the one batched splat->blur sweep.

    Returns ``(tables, alpha, cg_info)`` — the solve diagnostics ride out
    so ``freeze`` can record them on the Predictor (the publication gate
    refuses silently-failed solves). ``x0`` warm-starts the alpha CG from
    a previous Predictor's solution; the early-exit solver then pays only
    the iterations the data CHANGE needs, not a cold solve.
    """
    cfg = model.config
    st = model.stencil
    n = x.shape[0]
    _, os_, _ = model.constrained(params)
    op = model.operator(params, x, lat=lat)

    u, cg_info = cg_solve(op.mvm, y[:, None], tol=cfg.cg_tol_eval,
                          max_iters=cfg.max_cg_iters, x0=x0)

    # LOVE basis — the same y-seeded Lanczos run ``posterior`` does
    q0 = y[:, None] + 1e-3 * jax.random.normal(key, (n, 1), x.dtype)
    lres = lanczos_run(op.mvm, q0, variance_rank)
    q = lres.q[:, :, 0].T  # (n, k)
    tdense = (jnp.diag(jnp.where(lres.valid[:, 0], lres.alphas[:, 0], 1.0))
              + jnp.diag(lres.betas[:-1, 0] * lres.valid[:-1, 0]
                         * lres.valid[1:, 0], 1)
              + jnp.diag(lres.betas[:-1, 0] * lres.valid[:-1, 0]
                         * lres.valid[1:, 0], -1))
    # (T + eps I)^{-1/2} via the k x k eigendecomposition: identical
    # quadratic form to posterior's (T + eps I)^{-1} solve
    e, vecs = jnp.linalg.eigh(
        tdense + 1e-6 * jnp.eye(tdense.shape[0], dtype=x.dtype))
    root = q @ (vecs * jnp.where(e > 1e-10,
                                 jax.lax.rsqrt(jnp.maximum(e, 1e-10)),
                                 0.0)[None, :])

    # ONE batched splat + 2(d+1) blur sweeps for all 1 + k channels
    chans = jnp.concatenate([u, root], axis=1)
    w = jnp.asarray(st.weights, x.dtype)
    table = lat_mod.splat_sorted(lat, chans)
    blurred = lat_mod.blur(lat, table, w)
    if cfg.symmetrize:
        blurred = 0.5 * (blurred + lat_mod.blur(lat, table, w, reverse=True))
    return os_ * blurred, u[:, 0], cg_info  # (cap+1, 1+k), (n,), info


def _freeze_lattice(model: SimplexGP, params: GPParams, x: Array, *,
                    cap: int | None, cache: LatticeCache | None):
    """The one train-lattice build every freeze flavor shares.

    ``freeze`` and ``freeze_multi`` MUST run the identical build branch:
    the multi-output bit-exact-parity contract (DESIGN.md §15) holds
    because each channel of ``freeze_multi`` reuses this lattice, which
    is byte-identical to what k independent ``freeze`` calls would build
    from the same (x, params, cap) — only built once.
    """
    cfg = model.config
    st = model.stencil
    ls, _, _ = model.constrained(params)
    z = x / ls[None, :]
    if cap is None and cache is None:
        lat = lat_mod.build_lattice_auto(z, spacing=st.spacing, r=st.r,
                                         backend=cfg.build_backend)
    elif cache is not None:
        n, d = x.shape
        cap_val = model.capacity(n, d) if cap is None else cap
        lat = cache.get(cache.point_set_tag(x), z, spacing=st.spacing,
                        r=st.r, cap=cap_val, ls=ls,
                        build_backend=cfg.build_backend)
    else:
        lat = lat_mod.build_lattice(z, spacing=st.spacing, r=st.r, cap=cap,
                                    backend=cfg.build_backend)
    if bool(lat.pack_overflow):
        raise RuntimeError("freeze: lattice coordinate range overflow "
                           "(|coord| > 2^15) — rescale inputs or bound "
                           "the lengthscale")
    if bool(lat.overflow):
        raise RuntimeError("freeze: lattice capacity overflow — pass a "
                           "larger cap (or let build_lattice_auto size it)")
    return lat


def freeze(model: SimplexGP, params: GPParams, x: Array, y: Array, *,
           key: Array, variance_rank: int = 30, cap: int | None = None,
           cache: LatticeCache | None = None,
           warm_start: Array | None = None,
           reuse_index: LatticeIndex | None = None,
           on_nonconverged: str = "flag") -> Predictor:
    """Freeze a trained model into an immutable serving ``Predictor``.

    One-time cost (amortized over every future query): a train-lattice
    build (auto-sized unless ``cap`` given; ``cache`` memoizes it), the
    alpha/LOVE solves, one batched blur sweep, and the hash-index build.
    Eager-only: the dense tables are sized by the CONCRETE occupied count
    m, which is what keeps them small enough to stay VMEM-resident.

    Refresh hooks (used by ``refreeze``/the serving engine): ``warm_start``
    seeds the alpha CG with a previous solution (valid for ANY seed — CG
    converges regardless; a good seed from an old Predictor just makes it
    exit early). ``reuse_index`` skips the eager hash-index rebuild when
    the lattice is unchanged (a y-only refresh); it is VERIFIED against
    the freshly built lattice's occupied slots and silently rebuilt on
    mismatch — never trusted. ``on_nonconverged``: "flag" records the
    failed solve in the diagnostics (the ``validate_predictor`` gate
    refuses it at publication time); "raise" fails fast here.
    """
    lat = _freeze_lattice(model, params, x, cap=cap, cache=cache)
    ls, os_, noise = model.constrained(params)
    cfg = model.config
    st = model.stencil
    x0 = None
    if warm_start is not None and warm_start.shape[0] == x.shape[0]:
        x0 = jnp.asarray(warm_start, x.dtype)[:, None]
    blurred, alpha, cg_info = _freeze_tables(model, params, lat, x, y, key,
                                             variance_rank, x0)
    converged = bool(jnp.all(cg_info.converged))
    if not converged and on_nonconverged == "raise":
        raise RuntimeError(
            "freeze: alpha CG solve did not converge "
            f"(relative residual {float(jnp.max(cg_info.residual_norms)):.2e}"
            f" > tol {cfg.cg_tol_eval} after "
            f"{int(cg_info.iterations)} iterations)")
    index = _verified_index(lat, reuse_index)
    tables = lat_mod.compact_table(index, blurred)
    return Predictor(index=index, tables=tables, lengthscale=ls,
                     outputscale=os_, noise=noise, alpha=alpha,
                     cg_converged=jnp.asarray(converged),
                     cg_residual=jnp.max(cg_info.residual_norms),
                     cg_iterations=cg_info.iterations,
                     spacing=st.spacing,
                     backend=cfg.serve_backend,
                     buckets=tuple(cfg.serve_buckets),
                     n_train=x.shape[0])


def _verified_index(lat, reuse_index: LatticeIndex | None) -> LatticeIndex:
    """``reuse_index`` if it provably indexes ``lat``, else a fresh build.

    Reuse is only sound if BOTH maps still hold against the freshly built
    lattice: (a) ``slots`` (dense row -> lattice slot, what
    ``compact_table`` gathers with) must land on exactly the occupied
    slots, and (b) each dense row's PACKED COORDINATES in the index's
    probe table must equal the new lattice's coordinates at that slot.
    Slot ids alone are NOT enough: the hash build numbers slots by
    placement order, so two builds of different capacity can occupy the
    identical slot-id set 0..m-1 with different coord->slot assignments —
    an id-level check would pass and silently serve permuted rows. The
    key-level check makes a stale index impossible to reuse; on any
    mismatch a fresh index is built (never an error — reuse is an
    optimization, not a contract).
    """
    if reuse_index is None:
        return lat_mod.lattice_index(lat)
    occupied = np.nonzero(np.asarray(lat.valid))[0]
    slots = np.asarray(reuse_index.slots)
    if (reuse_index.m != occupied.shape[0]
            or not np.array_equal(np.sort(slots), occupied)):
        return lat_mod.lattice_index(lat)
    # (b) packed keys of the new lattice at the index's slots, per dense row
    coords = jnp.asarray(np.asarray(lat.coords)[slots])
    packed_new = np.stack(
        [np.asarray(c) for c in lat_mod._pack_key_cols(coords)], axis=1)
    ros = np.asarray(reuse_index.row_of_slot)
    tkeys = np.asarray(reuse_index.tkeys)
    occ = ros < reuse_index.m
    if int(occ.sum()) != reuse_index.m:
        return lat_mod.lattice_index(lat)
    packed_idx = np.zeros_like(packed_new)
    packed_idx[ros[occ]] = tkeys[occ]
    if not np.array_equal(packed_idx, packed_new):
        return lat_mod.lattice_index(lat)
    return reuse_index


def refreeze(model: SimplexGP, params: GPParams, x: Array, y: Array, *,
             key: Array, old: Predictor, cache: LatticeCache | None = None,
             variance_rank: int | None = None, cap: int | None = None,
             on_nonconverged: str = "flag") -> Predictor:
    """Warm-started re-freeze for a data refresh (DESIGN.md §13).

    The incremental path ROADMAP item 1 calls for: seed the alpha CG from
    ``old.alpha`` (early-exit solver — a y-perturbation refresh pays a
    few iterations, not a cold solve) and offer ``old.index`` for reuse
    (verified inside ``freeze``; a y-only update leaves the lattice — and
    hence the index — unchanged, skipping the eager hash-index rebuild).
    Produces the SAME Predictor a cold ``freeze`` on (x, y) would, up to
    CG stopping noise — pinned to 1e-5 by tests/test_serve_engine.py.

    Pass the engine's ``cache`` so an unchanged (x, lengthscale) hits the
    memoized lattice instead of rebuilding. ``variance_rank`` defaults to
    the old Predictor's rank (table shapes stay stable -> no bucket
    recompiles after the hot swap).
    """
    if variance_rank is None:
        variance_rank = old.tables.shape[1] - 1
    warm = old.alpha if old.n_train == x.shape[0] else None
    return freeze(model, params, x, y, key=key, variance_rank=variance_rank,
                  cap=cap, cache=cache, warm_start=warm,
                  reuse_index=old.index, on_nonconverged=on_nonconverged)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MultiPredictor:
    """Stacked multi-output frozen state: k channels, ONE lattice index.

    The dynamics-model layout (DESIGN.md §15): all k outputs share the
    input space, hyperparameters, and hence the lattice geometry, so one
    hash index serves every channel and the per-channel value tables are
    stacked column-wise into a single ``(m+1, k*(1+r))`` buffer —
    channel j occupies the contiguous block ``[j*(1+r), (j+1)*(1+r))``,
    column 0 of the block is its mean channel and the remaining r its
    LOVE root. One embed + d+1 probes + one batched contraction serve
    ALL k outputs per query (``predict_multi``). Per-channel solve
    diagnostics ride along as (k,) vectors for the publication gate.
    """

    index: LatticeIndex
    tables: Array  # (m+1, k*(1+r)) stacked per-channel [mean | root] blocks
    lengthscale: Array  # (d,) shared across channels
    outputscale: Array  # () shared
    noise: Array  # () shared
    alpha: Array  # (n, k) per-channel K_hat^{-1} y_j — refresh warm starts
    cg_converged: Array  # (k,) bool per channel
    cg_residual: Array  # (k,)
    cg_iterations: Array  # (k,) int32
    spacing: float = dataclasses.field(metadata=dict(static=True))
    backend: str = dataclasses.field(default="auto",
                                     metadata=dict(static=True))
    buckets: tuple[int, ...] = dataclasses.field(
        default=(64, 256, 1024, 4096), metadata=dict(static=True))
    n_train: int = dataclasses.field(default=0, metadata=dict(static=True))
    n_outputs: int = dataclasses.field(default=1,
                                       metadata=dict(static=True))


def freeze_multi(model: SimplexGP, params: GPParams, x: Array, ys: Array, *,
                 key: Array, variance_rank: int = 30, cap: int | None = None,
                 cache: LatticeCache | None = None,
                 warm_start: Array | None = None,
                 reuse_index: LatticeIndex | None = None,
                 on_nonconverged: str = "flag") -> MultiPredictor:
    """Freeze k output channels over ONE shared lattice (DESIGN.md §15).

    ``ys`` is (n, k) — e.g. the per-state-dimension targets of a dynamics
    model. All channels share (x, hyperparameters), so the lattice build,
    overflow checks, and hash index are paid ONCE; each channel then runs
    the same ``_freeze_tables`` solve an independent ``freeze`` would
    (channel j is seeded with ``jax.random.split(key, k)[j]``), and the
    compacted tables are stacked column-wise. The per-channel tables are
    therefore BIT-EXACT equal to k independent ``freeze(model, params,
    x, ys[:, j], key=split[j], cap=cap)`` calls — pinned by
    tests/test_serve_grad.py. Batching the k CG solves into one block
    solve would couple their stopping decisions and break that parity,
    which is why the channels solve sequentially.

    ``warm_start`` takes a previous MultiPredictor's (n, k) ``alpha``.
    ``on_nonconverged="raise"`` fails if ANY channel's solve missed
    tolerance; the default flags it in ``cg_converged`` for the gate.
    """
    if ys.ndim != 2:
        raise ValueError(f"freeze_multi wants ys of shape (n, k); got "
                         f"{ys.shape} — use freeze() for a single output")
    k_out = ys.shape[1]
    cfg = model.config
    st = model.stencil
    ls, os_, noise = model.constrained(params)
    lat = _freeze_lattice(model, params, x, cap=cap, cache=cache)
    chan_keys = jax.random.split(key, k_out)

    warm = None
    if warm_start is not None and warm_start.shape == (x.shape[0], k_out):
        warm = jnp.asarray(warm_start, x.dtype)
    blurred_list, alphas, infos = [], [], []
    for j in range(k_out):
        x0 = warm[:, j][:, None] if warm is not None else None
        blurred, alpha, cg_info = _freeze_tables(
            model, params, lat, x, ys[:, j], chan_keys[j], variance_rank,
            x0)
        blurred_list.append(blurred)
        alphas.append(alpha)
        infos.append(cg_info)
    converged = [bool(jnp.all(i.converged)) for i in infos]
    if not all(converged) and on_nonconverged == "raise":
        bad = [j for j, c in enumerate(converged) if not c]
        raise RuntimeError(
            f"freeze_multi: alpha CG did not converge for channel(s) {bad} "
            f"(tol {cfg.cg_tol_eval})")
    index = _verified_index(lat, reuse_index)
    tables = jnp.concatenate(
        [lat_mod.compact_table(index, b) for b in blurred_list], axis=1)
    return MultiPredictor(
        index=index, tables=tables, lengthscale=ls, outputscale=os_,
        noise=noise, alpha=jnp.stack(alphas, axis=1),
        cg_converged=jnp.asarray(converged),
        cg_residual=jnp.stack([jnp.max(i.residual_norms) for i in infos]),
        cg_iterations=jnp.stack([i.iterations for i in infos]),
        spacing=st.spacing, backend=cfg.serve_backend,
        buckets=tuple(cfg.serve_buckets), n_train=x.shape[0],
        n_outputs=k_out)


class ValidationReport(NamedTuple):
    ok: bool
    failures: tuple[str, ...]


def validate_predictor(pred: Predictor, *,
                       require_converged: bool = True) -> ValidationReport:
    """The publication gate: is this Predictor safe to serve?

    Runs host-side on the CANDIDATE before it is swapped in (never on the
    query path), so every failure mode it catches is refused before any
    query can observe it: non-finite tables/alpha (NaN-poisoned solve or
    buffer), a non-converged alpha solve, an index whose shapes/row map
    cannot be consistent with the tables, non-positive hyperparameters,
    and a corrupted zero miss row. Returns every failure, not just the
    first — the serving engine surfaces the list in its health status.
    """
    fails: list[str] = []
    tables = np.asarray(pred.tables)
    if not bool(np.isfinite(tables).all()):
        fails.append("tables contain non-finite values")
    if not bool(np.isfinite(np.asarray(pred.alpha)).all()):
        fails.append("alpha solution contains non-finite values")
    if require_converged and not bool(pred.cg_converged):
        fails.append(
            f"alpha CG solve not converged (relative residual "
            f"{float(pred.cg_residual):.2e} after "
            f"{int(pred.cg_iterations)} iterations)")
    if tables.shape[0] != pred.index.m + 1:
        fails.append(f"tables have {tables.shape[0]} rows, index expects "
                     f"m+1={pred.index.m + 1}")
    row_of_slot = np.asarray(pred.index.row_of_slot)
    if row_of_slot.shape != (pred.index.hcap,) or (
            row_of_slot.size and (row_of_slot.min() < 0
                                  or row_of_slot.max() > pred.index.m)):
        fails.append("index row_of_slot outside [0, m]")
    if tables.shape[0] > 0 and not bool((tables[-1] == 0).all()):
        fails.append("zero miss row is non-zero")
    ls = np.asarray(pred.lengthscale)
    if not (bool(np.isfinite(ls).all()) and bool((ls > 0).all())):
        fails.append("lengthscale not finite-positive")
    for name in ("outputscale", "noise"):
        v = float(getattr(pred, name))
        if not (math.isfinite(v) and v > 0):
            fails.append(f"{name} not finite-positive ({v})")
    if not pred.spacing > 0:
        fails.append(f"spacing not positive ({pred.spacing})")
    return ValidationReport(ok=not fails, failures=tuple(fails))


@obs.scope("serve.slice")
def _predict_core(pred: Predictor, xs: Array, *, backend: str,
                  interpret: bool | None = None):
    zq = xs / pred.lengthscale[None, :]
    out, miss = filtering.slice_only(pred.index, pred.tables, zq,
                                     spacing=pred.spacing, backend=backend,
                                     interpret=interpret)
    mean = out[:, 0]
    var = pred.outputscale - jnp.sum(out[:, 1:] ** 2, axis=1)
    var = jnp.clip(var, 1e-6, pred.outputscale)
    return mean, var, miss


# NOTE on buffer donation: the padded query buffer is freshly allocated
# per call and dead after the embed, but XLA input-output aliasing (what
# donate_argnums provides) needs a same-shape/dtype OUTPUT to alias onto —
# and the serving outputs are three (b,) vectors, never (b, d). Donating
# would only emit "donated buffers were not usable" warnings on every
# bucket compile, so the buffer is left to XLA's ordinary liveness
# analysis, which already reuses it after the embed.
@functools.partial(jax.jit, static_argnames=("backend",))
def _predict_padded(pred: Predictor, xs: Array, backend: str):
    return _predict_core(pred, xs, backend=backend)


def bucket_size(b: int, buckets: tuple[int, ...], multiple: int = 1) -> int:
    """Smallest serving bucket >= b (power-of-two growth past the largest),
    rounded up to ``multiple`` (mesh divisibility)."""
    nb = 0
    for s in sorted(buckets):
        if b <= s:
            nb = s
            break
    if nb == 0:
        biggest = max(buckets)
        nb = biggest * (1 << max(0, math.ceil(math.log2(b / biggest))))
    return -(-nb // multiple) * multiple


# jitted replicated-serving closures, keyed per (core, mesh, axis, backend)
# so repeated batches reuse one compilation instead of re-wrapping shard_map
_SHARDED_CACHE: dict = {}


def _sharded_predict_fn(mesh, axis_name: str, backend: str, core=None):
    core = _predict_core if core is None else core
    key = (core, mesh, axis_name, backend)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        from repro.sharding.simplex import replicated_table_serve
        fn = replicated_table_serve(
            functools.partial(core, backend=backend), mesh, axis_name)
        _SHARDED_CACHE[key] = fn
    return fn


def predict(pred: Predictor, xs: Array, *, backend: str | None = None,
            mesh=None, axis_name: str = "data") -> ServeResult:
    """Serve one query batch from the frozen predictor.

    The batch is padded to a fixed bucket (``pred.buckets``) so jit
    compiles once per bucket, not once per batch shape; the padded buffer
    is freshly materialized per call and dies after the embed (see the
    donation note above ``_predict_padded``). Padding rows are served
    like any query (all identical, so their probes converge) and sliced
    away before returning. ``mesh`` fans the batch over its ``axis_name``
    axis with the frozen tables replicated — zero collectives, so
    throughput scales linearly in devices (DESIGN.md §12).
    """
    b, d = xs.shape
    backend = pred.backend if backend is None else backend
    ndev = int(mesh.shape[axis_name]) if mesh is not None else 1
    nb = bucket_size(b, pred.buckets, multiple=ndev)
    xs_pad = jnp.zeros((nb, d), xs.dtype).at[:b].set(xs)
    if mesh is None:
        mean, var, miss = _predict_padded(pred, xs_pad, backend)
    else:
        mean, var, miss = _sharded_predict_fn(mesh, axis_name,
                                              backend)(pred, xs_pad)
    return ServeResult(mean=mean[:b], var=var[:b], miss_mass=miss[:b])


# -- Multi-output serving (DESIGN.md §15) ------------------------------------


@obs.scope("serve.slice")
def _predict_multi_core(mp: MultiPredictor, xs: Array, *, backend: str,
                        interpret: bool | None = None):
    """One embed + probe + batched contraction for ALL k channels.

    The hoisted multi-channel path: the embed/rank scratch is computed
    once per query batch inside the single ``slice_only`` call, not once
    per output (pinned by the ``lattice.embeds`` counter test) — the k
    channels differ only in which table columns the one gathered row set
    contracts against.
    """
    zq = xs / mp.lengthscale[None, :]
    out, miss = filtering.slice_only(mp.index, mp.tables, zq,
                                     spacing=mp.spacing, backend=backend,
                                     interpret=interpret)
    out = out.reshape(xs.shape[0], mp.n_outputs, -1)
    mean = out[:, :, 0]
    var = mp.outputscale - jnp.sum(out[:, :, 1:] ** 2, axis=2)
    var = jnp.clip(var, 1e-6, mp.outputscale)
    return mean, var, miss


@functools.partial(jax.jit, static_argnames=("backend",))
def _predict_multi_padded(mp: MultiPredictor, xs: Array, backend: str):
    return _predict_multi_core(mp, xs, backend=backend)


def predict_multi(mp: MultiPredictor, xs: Array, *,
                  backend: str | None = None, mesh=None,
                  axis_name: str = "data") -> MultiServeResult:
    """Serve all k output channels of one query batch from one probe.

    Same bucketing/mesh contract as ``predict``; returns (b, k) mean and
    latent variance plus the shared per-query ``miss_mass`` (the channels
    share the lattice, so they miss together). Differentiable in ``xs``
    (the ``slice_only`` custom JVP) — a PILCO-style rollout can
    ``jax.grad`` straight through it; see also ``predict_multi_grad`` for
    the one-pass analytic Jacobian.
    """
    b, d = xs.shape
    backend = mp.backend if backend is None else backend
    ndev = int(mesh.shape[axis_name]) if mesh is not None else 1
    nb = bucket_size(b, mp.buckets, multiple=ndev)
    xs_pad = jnp.zeros((nb, d), xs.dtype).at[:b].set(xs)
    if mesh is None:
        mean, var, miss = _predict_multi_padded(mp, xs_pad, backend)
    else:
        mean, var, miss = _sharded_predict_fn(
            mesh, axis_name, backend, core=_predict_multi_core)(mp, xs_pad)
    return MultiServeResult(mean=mean[:b], var=var[:b], miss_mass=miss[:b])


# -- Analytic query-space gradients (DESIGN.md §15) --------------------------


@obs.scope("serve.slice")
def _grad_blocks(index: LatticeIndex, tables: Array, xs: Array, ls: Array,
                 os_: Array, spacing: float, k_out: int):
    """Shared analytic d(mean, var)/dx* core for 1 and k output channels.

    One ``slice_only_grad`` pass (embed + d+1 probes + one gather + d+1
    contractions) yields the primal AND the full query-space Jacobian of
    every table channel; the chain rule through zq = x/ls and the
    variance's quadratic form are applied here. Where the variance clip
    is active (var_raw outside [1e-6, outputscale] — off-model queries)
    the reported dvar is 0, the true subgradient of the clipped surface.
    """
    zq = xs / ls[None, :]
    out, jac, miss = filtering.slice_only_grad(index, tables, zq,
                                               spacing=spacing)
    b = xs.shape[0]
    out = out.reshape(b, k_out, -1)
    jac = (jac / ls[None, None, :]).reshape(b, k_out, out.shape[2],
                                            ls.shape[0])
    mean = out[:, :, 0]
    dmean = jac[:, :, 0, :]
    roots = out[:, :, 1:]
    var_raw = os_ - jnp.sum(roots ** 2, axis=2)
    dvar = -2.0 * jnp.einsum("bkr,bkrj->bkj", roots, jac[:, :, 1:, :])
    clipped = (var_raw < 1e-6) | (var_raw > os_)
    var = jnp.clip(var_raw, 1e-6, os_)
    dvar = jnp.where(clipped[:, :, None], 0.0, dvar)
    return mean, var, dmean, dvar, miss


@jax.jit
def _predict_grad_padded(pred: Predictor, xs: Array):
    mean, var, dmean, dvar, miss = _grad_blocks(
        pred.index, pred.tables, xs, pred.lengthscale, pred.outputscale,
        pred.spacing, 1)
    return mean[:, 0], var[:, 0], dmean[:, 0], dvar[:, 0], miss


@jax.jit
def _predict_multi_grad_padded(mp: MultiPredictor, xs: Array):
    return _grad_blocks(mp.index, mp.tables, xs, mp.lengthscale,
                        mp.outputscale, mp.spacing, mp.n_outputs)


def predict_grad(pred: Predictor, xs: Array) -> ServeGradResult:
    """Predictions + analytic d(mean, var)/dx* in one fused pass.

    The forward-only fast path for gradient consumers (BO acquisition
    ascent, rollout sensitivity): one embed, d+1 probes, one table
    gather — the Jacobian contraction reuses the primal's gathered rows,
    so the pair costs O(d^2 (1+r)) per query with NO extra probes and no
    autodiff retrace. Equals ``jax.jacfwd`` of ``predict`` exactly
    (tests/test_serve_grad.py); strictly-interior queries (miss 0, away
    from cell boundaries) match central differences to f32 exactness
    because mean is piecewise-linear and var piecewise-quadratic in x*.
    Gate on ``grad_ok`` — see ``ServeGradResult``.
    """
    b, d = xs.shape
    nb = bucket_size(b, pred.buckets)
    xs_pad = jnp.zeros((nb, d), xs.dtype).at[:b].set(xs)
    mean, var, dmean, dvar, miss = _predict_grad_padded(pred, xs_pad)
    return ServeGradResult(mean=mean[:b], var=var[:b], dmean=dmean[:b],
                           dvar=dvar[:b], miss_mass=miss[:b],
                           grad_ok=miss[:b] <= 0.0)


def predict_multi_grad(mp: MultiPredictor, xs: Array) -> ServeGradResult:
    """``predict_grad`` over all k channels of a ``MultiPredictor``.

    Returns (b, k) mean/var and (b, k, d) dmean/dvar from ONE
    embed/probe/gather — the per-state-dimension Jacobian a dynamics
    rollout consumes at each step.
    """
    b, d = xs.shape
    nb = bucket_size(b, mp.buckets)
    xs_pad = jnp.zeros((nb, d), xs.dtype).at[:b].set(xs)
    mean, var, dmean, dvar, miss = _predict_multi_grad_padded(mp, xs_pad)
    return ServeGradResult(mean=mean[:b], var=var[:b], dmean=dmean[:b],
                           dvar=dvar[:b], miss_mass=miss[:b],
                           grad_ok=miss[:b] <= 0.0)


# -- Predictor persistence (DESIGN.md §14) -----------------------------------

PREDICTOR_FORMAT = "simplex-gp-predictor"
PREDICTOR_SCHEMA = 1


class PredictorLoadError(CheckpointCorruptError):
    """A saved Predictor failed integrity or validation at load.

    Subclasses ``CheckpointCorruptError`` so generation-fallback code can
    treat "corrupt training checkpoint" and "corrupt Predictor" with one
    except clause. A Predictor that raises this was NEVER eligible to
    serve — the load gate runs before any registry/publish step.
    """


def _predictor_arrays(pred: Predictor) -> dict[str, np.ndarray]:
    return {
        "tables": np.asarray(pred.tables),
        "lengthscale": np.asarray(pred.lengthscale),
        "outputscale": np.asarray(pred.outputscale),
        "noise": np.asarray(pred.noise),
        "alpha": np.asarray(pred.alpha),
        "cg_converged": np.asarray(pred.cg_converged),
        "cg_residual": np.asarray(pred.cg_residual),
        "cg_iterations": np.asarray(pred.cg_iterations),
        "index/tkeys": np.asarray(pred.index.tkeys),
        "index/row_of_slot": np.asarray(pred.index.row_of_slot),
        "index/slots": np.asarray(pred.index.slots),
    }


def save_predictor(pred: Predictor, path: str | pathlib.Path, *,
                   extra: dict | None = None, faults=None) -> pathlib.Path:
    """Atomically persist a Predictor to directory ``path``.

    Layout mirrors runtime/checkpoint.py: one .npy blob per array leaf
    plus a versioned ``manifest.json`` recording per-blob byte size and
    CRC32 alongside the static fields (spacing/backend/buckets/n_train
    and the index geometry). Writes land in ``<path>.tmp`` and publish
    via ``os.replace`` — the atomicity boundary: a crash mid-write
    leaves at most a dead ``.tmp`` (never a half-valid Predictor), a
    crash after the rename leaves a fully valid one. ``faults`` (a
    runtime/faults.FaultInjector) arms the kill-before/after-publish
    crash sites the recovery harness exercises.
    """
    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.parent / (path.name + ".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    manifest = {
        "format": PREDICTOR_FORMAT,
        "schema": PREDICTOR_SCHEMA,
        "static": {
            "spacing": pred.spacing,
            "backend": pred.backend,
            "buckets": list(pred.buckets),
            "n_train": pred.n_train,
            "index": {"d": pred.index.d, "hcap": pred.index.hcap,
                      "m": pred.index.m},
        },
        "extra": extra or {},
        "leaves": save_blobs(tmp, _predictor_arrays(pred)),
    }
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if faults is not None:
        faults.kill_if_armed("persist_before_publish")
    if path.exists():
        shutil.rmtree(path)
    os.replace(tmp, path)  # atomic publish
    if faults is not None:
        faults.kill_if_armed("persist_after_publish")
    return path


def self_probe(pred: Predictor, *, sample: int = 16,
               check_end_to_end: bool = True) -> None:
    """In-lattice self-probe: prove the loaded Predictor can actually
    serve its own lattice before it becomes eligible to serve anyone.

    ``validate_predictor`` checks VALUES (finiteness, ranges, shapes);
    this checks BEHAVIOR, with no training data needed:

      1. row-map bijection — the occupied entries of ``row_of_slot``
         must hit every dense row 0..m-1 exactly once (a permuted or
         duplicated row map passes the range check but serves the wrong
         vertices);
      2. hash round-trip — a sample of the index's own stored keys,
         looked up through the REAL probe path (kernels/hash), must land
         back on their own rows (catches a tkeys/row_of_slot pair that
         was torn from two different generations);
      3. end-to-end slice — ``predict`` on a tiny synthetic batch must
         return finite mean/var with ``miss_mass`` in [0, 1] (catches a
         static-field/blob mismatch that only explodes inside the jitted
         slice kernel).

    Raises ``PredictorLoadError`` on any failure; returns None when the
    Predictor is fit to serve.
    """
    from repro.kernels.hash import ops as hash_ops

    idx = pred.index
    ros = np.asarray(idx.row_of_slot)
    occ = np.nonzero(ros < idx.m)[0]
    rows = ros[occ]
    if occ.shape[0] != idx.m or not np.array_equal(np.sort(rows),
                                                   np.arange(idx.m)):
        raise PredictorLoadError(
            "self-probe: index row_of_slot is not a bijection onto "
            f"dense rows 0..{idx.m - 1} ({occ.shape[0]} occupied slots)")
    take = occ[:: max(1, occ.shape[0] // max(sample, 1))][:sample]
    tkeys = jnp.asarray(idx.tkeys)
    queries = tkeys[jnp.asarray(take)]
    found = np.asarray(hash_ops.hash_lookup(
        tkeys, queries, jnp.ones((take.shape[0],), bool), idx.hcap,
        backend="hash_xla"))
    if (found < 0).any() or not np.array_equal(
            ros[np.maximum(found, 0)], ros[take]):
        raise PredictorLoadError(
            "self-probe: the index's own keys do not look up to their "
            "own rows — tkeys/row_of_slot are inconsistent")
    gathered = np.asarray(pred.tables)[ros[take]]
    if not np.isfinite(gathered).all():
        raise PredictorLoadError(
            "self-probe: probed table rows contain non-finite values")
    if check_end_to_end:
        d = int(pred.lengthscale.shape[0])
        zs = np.zeros((2, d), np.float32)
        zs[1] = 0.37  # off-origin: exercises nontrivial barycentric ranks
        try:
            res = predict(pred, jnp.asarray(zs))
            mean = np.asarray(res.mean)
            var = np.asarray(res.var)
            miss = np.asarray(res.miss_mass)
        except Exception as e:
            raise PredictorLoadError(
                f"self-probe: end-to-end predict failed "
                f"({type(e).__name__}: {e})") from e
        if not (np.isfinite(mean).all() and np.isfinite(var).all()):
            raise PredictorLoadError(
                "self-probe: end-to-end predict returned non-finite "
                "mean/var")
        if not ((miss >= 0) & (miss <= 1)).all():
            raise PredictorLoadError(
                f"self-probe: miss_mass outside [0, 1] ({miss})")


def load_predictor(path: str | pathlib.Path, *, validate: bool = True,
                   require_converged: bool = True) -> Predictor:
    """Load a persisted Predictor; gate it before it can ever serve.

    The load path enforces the §14 validation-before-serve rule in three
    layers, all BEFORE the Predictor is returned to any registry:
    blob integrity (existence / recorded size / CRC32 / parse — a
    truncated or bit-flipped file raises here), the existing
    ``validate_predictor`` value gate, and the ``self_probe`` behavior
    gate. Any failure raises ``PredictorLoadError`` — a corrupted file
    is rejected, never served. ``validate=False`` skips the two gates
    (integrity checks always run) for diagnostic tooling only.
    """
    path = pathlib.Path(path)
    try:
        man = read_manifest(path / "manifest.json",
                            expect_format=PREDICTOR_FORMAT)
        if man.get("schema", 0) > PREDICTOR_SCHEMA:
            raise CheckpointCorruptError(
                f"{path}: predictor schema {man.get('schema')} is newer "
                f"than this reader ({PREDICTOR_SCHEMA})")
        static = man.get("static")
        if not isinstance(static, dict) or not isinstance(
                static.get("index"), dict):
            raise CheckpointCorruptError(
                f"{path}: manifest missing the static-field table")
        flat = load_blobs(path, man["leaves"])
        missing = set(_REQUIRED_LEAVES) - set(flat)
        if missing:
            raise CheckpointCorruptError(
                f"{path}: manifest lists no blob for {sorted(missing)}")
    except PredictorLoadError:
        raise
    except CheckpointCorruptError as e:
        raise PredictorLoadError(str(e)) from e

    try:
        idx_static = static["index"]
        index = LatticeIndex(
            tkeys=jnp.asarray(flat["index/tkeys"]),
            row_of_slot=jnp.asarray(flat["index/row_of_slot"]),
            slots=jnp.asarray(flat["index/slots"]),
            d=int(idx_static["d"]), hcap=int(idx_static["hcap"]),
            m=int(idx_static["m"]))
        pred = Predictor(
            index=index,
            tables=jnp.asarray(flat["tables"]),
            lengthscale=jnp.asarray(flat["lengthscale"]),
            outputscale=jnp.asarray(flat["outputscale"]),
            noise=jnp.asarray(flat["noise"]),
            alpha=jnp.asarray(flat["alpha"]),
            cg_converged=jnp.asarray(flat["cg_converged"]),
            cg_residual=jnp.asarray(flat["cg_residual"]),
            cg_iterations=jnp.asarray(flat["cg_iterations"]),
            spacing=float(static["spacing"]),
            backend=str(static["backend"]),
            buckets=tuple(int(b) for b in static["buckets"]),
            n_train=int(static["n_train"]))
    except (KeyError, TypeError, ValueError) as e:
        raise PredictorLoadError(
            f"{path}: manifest/blob structure unusable "
            f"({type(e).__name__}: {e})") from e

    if validate:
        rep = validate_predictor(pred, require_converged=require_converged)
        if not rep.ok:
            raise PredictorLoadError(
                f"{path}: loaded predictor failed validation: "
                + "; ".join(rep.failures))
        self_probe(pred)
    return pred


_REQUIRED_LEAVES = tuple(sorted((
    "tables", "lengthscale", "outputscale", "noise", "alpha",
    "cg_converged", "cg_residual", "cg_iterations",
    "index/tkeys", "index/row_of_slot", "index/slots")))
