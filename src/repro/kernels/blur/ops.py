"""Public lattice-MVM ops: backend policy + dispatch for the full operator.

Backend tiers (DESIGN.md §8):

  fused_pallas         one pallas_call for splat -> 2(d+1) sweeps -> slice;
                       the value table lives in VMEM scratch throughout
                       (fused.py). Runs in interpret mode only: Mosaic
                       does not compile it for the TPU.
  per_direction_pallas one pallas_call per directional sweep (kernel.py),
                       XLA splat/slice around them. Resident gather source
                       when the table fits; grid-blocked streaming variant
                       for moderately oversized tables. Interpret mode
                       only, like fused_pallas.
  fused_xla            single-jit XLA composition with the scatter-free
                       sorted-segment splat (lattice.splat_sorted) — what
                       ``auto`` resolves to on every host, the TPU
                       included.
  xla                  the legacy reference composition (scatter-add splat
                       + scan blur + slice). Keeps the seed semantics;
                       always available, any table size, traced weights OK.

Pallas tiers need CONCRETE stencil taps (they are baked into the
kernel); pass them via ``taps=`` (e.g. from ``FilterSpec`` /
``Stencil.weights``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import lattice as lat_mod
from repro.core.lattice import Lattice
from repro.kernels.blur.fused import fused_filter_pallas
from repro.kernels.blur.kernel import (DEFAULT_BLOCK_P,
                                       blur_direction_blocked_pallas,
                                       blur_direction_pallas)

Array = jax.Array

BACKENDS = ("auto", "fused_pallas", "per_direction_pallas", "fused_xla",
            "xla")

# VMEM budget for Pallas residency decisions. 16 MB/core physical; leave
# headroom for the pipeline's double buffers and compiler spill.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# resident-source per-direction tier: the table is the only large resident
_TABLE_BUDGET_BYTES = 8 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_vmem_bytes(n: int, d: int, r: int, cap1: int, c: int,
                     itemsize: int = 4) -> int:
    """Total resident bytes of the fused kernel's memory plan (fused.py)."""
    big = n * (d + 1)
    table = 3 * cap1 * c            # table + work + accum scratch
    splat_plan = big * (c + 3)      # contrib scan + sort_row/sort_w/head
    slice_plan = 2 * n * (d + 1)    # seg_ids + weights
    io = 2 * n * c                  # v + out
    nbr_tiles = 2 * cap1 * 2 * r    # double-buffered direction tiles
    misc = 2 * cap1                 # row_last + valid
    return itemsize * (table + splat_plan + slice_plan + io + nbr_tiles
                       + misc)


def fits_vmem(n: int, d: int, r: int, cap1: int, c: int, *,
              budget: int = VMEM_BUDGET_BYTES) -> bool:
    """Gate for the fused kernel: ALL residents (not just the table) fit.

    Callers should size ``cap`` realistically (lattice.suggest_capacity +
    build_lattice_auto), not at the worst case n(d+1) — paper Table 3 shows
    m is usually a small fraction of it, and this gate is exactly where
    over-allocation turns into a lost fusion.
    """
    return fused_vmem_bytes(n, d, r, cap1, c) <= budget


def table_fits_vmem(cap1: int, c: int, itemsize: int = 4) -> bool:
    return cap1 * c * itemsize <= _TABLE_BUDGET_BYTES


def max_cap_for_vmem(n: int, d: int, r: int, c: int, *,
                     budget: int = VMEM_BUDGET_BYTES,
                     itemsize: int = 4) -> int:
    """Largest table capacity whose fused-kernel memory plan fits ``budget``.

    Inverts ``fused_vmem_bytes`` (linear in cap1). 0 when even an empty
    table spills — the fixed per-point residents alone exceed the budget.
    Used by ``lattice.suggest_capacity`` to keep its power-of-two rounding
    from silently defeating ``fits_vmem``.
    """
    big = n * (d + 1)
    fixed_words = big * (c + 3) + 2 * big + 2 * n * c
    per_cap1_words = 3 * c + 4 * r + 2
    cap1 = (budget // itemsize - fixed_words) // per_cap1_words
    return max(int(cap1) - 1, 0)


def pick_block_p(cap1: int, c: int = 1) -> int:
    """Heuristic block_p: large enough to amortize per-step overhead, small
    enough that a handful of tiles fit next to the resident table. Override
    with REPRO_BLUR_BLOCK_P; ``autotune_block_p`` measures candidates."""
    env = os.environ.get("REPRO_BLUR_BLOCK_P")
    if env:
        return int(env)
    best = 256
    for cand in (512, 1024, 2048, 4096):
        if cand <= max(256, cap1 // 4) and cand * (c + 8) * 4 <= 1 << 20:
            best = cand
    return best


_AUTOTUNE_CACHE: dict[tuple, int] = {}


def autotune_block_p(lat: Lattice, c: int, taps: tuple[float, ...], *,
                     candidates: tuple[int, ...] = (256, 512, 1024, 2048),
                     iters: int = 3) -> int:
    """Measure the per-direction kernel across block sizes on this device.

    Only meaningful where the kernel compiles (TPU); elsewhere returns the
    heuristic (timing the interpreter would autotune the wrong thing).
    Cached per (platform, table-size bucket, c, r).
    """
    cap1 = lat.cap + 1
    key = (jax.default_backend(), cap1.bit_length(), c, lat.r)
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    if not _on_tpu():
        best = pick_block_p(cap1, c)
        _AUTOTUNE_CACHE[key] = best
        return best
    import time
    vals = jnp.zeros((cap1, c), jnp.float32)
    best, best_t = None, float("inf")
    for bp in candidates:
        fn = jax.jit(functools.partial(blur_direction_pallas,
                                       stencil=taps, block_p=bp))
        jax.block_until_ready(fn(vals, lat.nbr[0]))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(vals, lat.nbr[0]))
        dt = time.perf_counter() - t0
        if dt < best_t:
            best, best_t = bp, dt
    _AUTOTUNE_CACHE[key] = best
    return best


def resolve_backend(backend: str) -> str:
    """The concrete backend a ``lattice_mvm`` call runs.

    ``auto`` is ``fused_xla`` on every host, the TPU included: the TPU
    kernel compiler (Mosaic) lowers neither Pallas tier. Their vector row
    gather from a VMEM-resident table (``jnp.take(ref[...], idx, axis=0)``)
    is refused with "Shape mismatch in input, indices and output"
    (tests/test_tpu_compile.py). They stay reachable through an explicit
    ``backend=``, which on a TPU raises that compiler error.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of "
                         f"{BACKENDS}")
    return "fused_xla" if backend == "auto" else backend


# ---------------------------------------------------------------------------
# Blur-only entry point (kept for kernel tests and the per-direction tier).
# ---------------------------------------------------------------------------


def blur_pallas(lat: Lattice, vals: Array, stencil: tuple[float, ...], *,
                reverse: bool = False, block_p: int | None = None,
                interpret: bool | None = None) -> Array:
    """Sequential separable blur via the Pallas kernels (one call/direction).

    Off-TPU this dispatches to the XLA blur — running the Pallas
    interpreter by default was orders of magnitude slower than XLA; set
    ``interpret=True`` explicitly to exercise the kernels in tests.
    """
    if interpret is None:
        if not _on_tpu():
            w = jnp.asarray(stencil, vals.dtype)
            return lat_mod.blur(lat, vals, w, reverse=reverse)
        interpret = False
    cap1, c = vals.shape
    if block_p is None:
        # measured on-device where the kernel compiles (cached per shape
        # bucket); interpret mode gets the cheap heuristic
        block_p = (pick_block_p(cap1, c) if interpret
                   else autotune_block_p(lat, c, tuple(stencil)))
    blocked = not table_fits_vmem(cap1, c)
    fn = blur_direction_blocked_pallas if blocked else blur_direction_pallas
    order = range(lat.d + 1)
    if reverse:
        order = reversed(list(order))
    for a in order:
        vals = fn(vals, lat.nbr[a], stencil, block_p=block_p,
                  interpret=interpret)
    return vals


# ---------------------------------------------------------------------------
# Full-operator dispatch.
# ---------------------------------------------------------------------------


def _blur_both(blur_fn, table: Array, *, symmetrize: bool,
               transpose: bool) -> Array:
    """``blur_fn(table, reverse)`` in the operator's order, averaged with
    the opposite order when symmetrized."""
    with obs.scope("mvm.blur"):
        blurred = blur_fn(table, transpose)
        if symmetrize:
            blurred = 0.5 * (blurred + blur_fn(table, not transpose))
    return blurred


@functools.partial(jax.jit, static_argnames=("symmetrize", "transpose"))
def _fused_xla(lat: Lattice, v: Array, weights: Array, *,
               symmetrize: bool, transpose: bool) -> Array:
    with obs.scope("mvm.splat"):
        table = lat_mod.splat_sorted(lat, v)
    blurred = _blur_both(
        lambda t, rev: lat_mod.blur(lat, t, weights, reverse=rev), table,
        symmetrize=symmetrize, transpose=transpose)
    with obs.scope("mvm.slice"):
        return lat_mod.slice_(lat, blurred)


def _xla_reference(lat: Lattice, v: Array, weights: Array, *,
                   symmetrize: bool, transpose: bool) -> Array:
    with obs.scope("mvm.splat"):
        splatted = lat_mod.splat(lat, v)
    blurred = _blur_both(
        lambda t, rev: lat_mod.blur(lat, t, weights, reverse=rev), splatted,
        symmetrize=symmetrize, transpose=transpose)
    with obs.scope("mvm.slice"):
        return lat_mod.slice_(lat, blurred)


def _concrete_taps(weights, taps):
    """Concrete stencil taps, or None when only traced values exist."""
    if taps is not None:
        return tuple(float(t) for t in taps)
    if weights is None:
        return None
    try:
        return tuple(float(w) for w in jax.core.concrete_or_error(
            None, weights, "lattice_mvm taps"))
    except jax.errors.ConcretizationTypeError:
        return None


def lattice_mvm(lat: Lattice, v: Array, weights: Array | None = None, *,
                taps: tuple[float, ...] | None = None,
                symmetrize: bool = True, transpose: bool = False,
                backend: str = "auto", block_p: int | None = None,
                interpret: bool | None = None, mesh=None,
                axis_name: str = "data") -> Array:
    """Apply W B W^T (or its transpose / symmetrization) with one of the
    policy backends. ``weights`` (traced OK) and/or concrete ``taps`` must
    describe the same (2r+1) stencil.

    ``mesh`` selects the data-parallel tier (sharding/simplex.py): rows of
    ``v`` shard over the mesh's ``axis_name`` axis, the blur table is
    replicated, and the whole MVM costs ONE psum. The per-device compute is
    plain XLA, so ``backend`` is ignored on that path.
    """
    backend = resolve_backend(backend)
    if weights is None and taps is None:
        raise ValueError("lattice_mvm needs a stencil: pass weights= "
                         "(array) and/or taps= (concrete tuple)")
    # trace-level under jit/scan: the MVMs baked into the compiled program
    # and their channel widths, so a solver that batches k RHS into ONE
    # (n, k) MVM per iteration shows calls=1, cols=k where a per-column
    # loop would show calls=k (tests/test_solvers.py pins mBCG with this)
    obs.count("mvm.calls")
    obs.count("mvm.cols", int(v.shape[1]))
    with obs.scope("mvm"):
        return _dispatch(lat, v, weights, taps=taps, symmetrize=symmetrize,
                         transpose=transpose, backend=backend,
                         block_p=block_p, interpret=interpret, mesh=mesh,
                         axis_name=axis_name)


def _dispatch(lat, v, weights, *, taps, symmetrize, transpose, backend,
              block_p, interpret, mesh, axis_name):
    if mesh is not None:
        from repro.sharding.simplex import sharded_lattice_mvm
        return sharded_lattice_mvm(lat, v, weights, taps=taps,
                                   mesh=mesh, axis_name=axis_name,
                                   symmetrize=symmetrize,
                                   transpose=transpose)
    concrete = _concrete_taps(weights, taps)
    if backend in ("fused_pallas", "per_direction_pallas") and concrete is None:
        raise ValueError(
            f"backend {backend!r} needs concrete stencil taps; pass taps= "
            "(e.g. Stencil.weights / FilterSpec.taps) instead of traced "
            "weights")
    if weights is None:
        weights = jnp.asarray(concrete, v.dtype)

    if backend == "fused_pallas":
        # one kernel: splat, blur and slice share the ``simplex.mvm`` scope
        run_interp = (not _on_tpu()) if interpret is None else interpret
        return fused_filter_pallas(lat, v, concrete, symmetrize=symmetrize,
                                   transpose=transpose, interpret=run_interp)
    if backend == "per_direction_pallas":
        with obs.scope("mvm.splat"):
            splatted = lat_mod.splat(lat, v)
        blurred = _blur_both(
            lambda t, rev: blur_pallas(lat, t, concrete, reverse=rev,
                                       block_p=block_p, interpret=interpret),
            splatted, symmetrize=symmetrize, transpose=transpose)
        with obs.scope("mvm.slice"):
            return lat_mod.slice_(lat, blurred)
    if backend == "fused_xla":
        return _fused_xla(lat, v, weights, symmetrize=symmetrize,
                          transpose=transpose)
    return _xla_reference(lat, v, weights, symmetrize=symmetrize,
                          transpose=transpose)
