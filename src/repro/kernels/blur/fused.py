"""Fused lattice MVM: splat -> (d+1)-blur -> slice in ONE pallas_call.

This is the TPU analogue of the paper's fused CUDA filter (§4): the whole
symmetrized operator W 0.5(B + B^T) W^T runs with the lattice value table
resident in VMEM scratch the entire time, instead of round-tripping HBM
once per directional blur plus separate splat/slice dispatches (~2d+4
kernels on the old path).

Memory plan (DESIGN.md §8) for the fits-VMEM variant:

  grid = (T,),  T = 2(d+1) sweeps when symmetrized else d+1
  persistent VMEM scratch:
    table  (cap+1, c)  splat result, kept for the reverse sweep's restart
    work   (cap+1, c)  current sweep state
    accum  (cap+1, c)  forward-sweep result while the reverse sweep runs
  streamed per grid step (auto double-buffered by the Pallas pipeline):
    nbr    (1, cap+1, 2r) — the step's directional gather tile; the sweep
           order is palindromic (0..d, d..0) so the middle tile is reused
           across the fwd->rev boundary without a re-fetch, and the
           forward and reverse sweeps share the single resident table load.
  resident inputs: v (n, c), the sorted splat plan (3 x (n(d+1), 1)),
    row_last/valid (cap+1, 1), seg_ids/weights (n, d+1) for the slice.

Stage schedule on grid step t:
  t == 0        splat: gather sorted contributions, segmented Hillis-Steele
                prefix scan in VMEM (no scatter, no atomics — build-time
                sorting makes every lattice point's members contiguous),
                boundary-gather into `table`; start the forward sweep.
  every t       one directional stencil sweep on `work`.
  t == d+1      (symmetrized) park forward result in `accum`, restart the
                reverse sweep from `table`.
  t == T-1      combine 0.5(accum + work), barycentric slice, write (n, c).

ops.py gates this kernel on a VMEM budget over ALL residents (not just the
table) and picks the per-direction or XLA tiers otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _shift_down(x: Array, s: int) -> Array:
    """Shift rows down by s, zero-filling the top (static s)."""
    return jnp.concatenate(
        [jnp.zeros((s, x.shape[1]), x.dtype), x[:-s]], axis=0)


def _fused_kernel(v_ref, srow_ref, sw_ref, head_ref, rlast_ref, valid_ref,
                  seg_ref, wts_ref, nbr_ref, out_ref,
                  table_ref, work_ref, accum_ref, *,
                  taps: tuple[float, ...], d: int, n: int, c: int,
                  cap1: int, big: int, symmetrize: bool):
    t = pl.program_id(0)
    num_steps = 2 * (d + 1) if symmetrize else d + 1
    dump_row = cap1 - 1
    r = len(taps) // 2

    @pl.when(t == 0)
    def _splat():
        # gather + segmented Hillis-Steele scan over sorted contributions
        contrib = sw_ref[...] * jnp.take(v_ref[...], srow_ref[...][:, 0],
                                         axis=0)  # (big, c)
        carry = 1.0 - head_ref[...]  # (big, 1): 0 at segment heads
        shift = 1
        while shift < big:
            contrib = contrib + carry * _shift_down(contrib, shift)
            carry = carry * _shift_down(carry, shift)
            shift *= 2
        table = jnp.take(contrib, rlast_ref[...][:, 0], axis=0)  # (cap1, c)
        table = table * valid_ref[...]  # empty slots and dump row -> 0
        table_ref[...] = table
        work_ref[...] = table

    if symmetrize:
        @pl.when(t == d + 1)
        def _restart_reverse():
            accum_ref[...] = work_ref[...]
            work_ref[...] = table_ref[...]

    # one directional stencil sweep (the step's nbr tile picks the direction)
    vals = work_ref[...]
    nbr = nbr_ref[...][0]  # (cap1, 2r)
    swept = vals * taps[r]
    side = list(taps[:r]) + list(taps[r + 1:])
    for s, w in enumerate(side):
        swept = swept + w * jnp.take(vals, nbr[:, s], axis=0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (cap1, 1), 0)
    work_ref[...] = jnp.where(rows == dump_row, 0.0, swept)

    @pl.when(t == num_steps - 1)
    def _slice():
        if symmetrize:
            final = 0.5 * (accum_ref[...] + work_ref[...])
        else:
            final = work_ref[...]
        out = jnp.zeros((n, c), out_ref.dtype)
        for k in range(d + 1):
            out = out + (wts_ref[...][:, k][:, None]
                         * jnp.take(final, seg_ref[...][:, k], axis=0))
        out_ref[...] = out


def fused_filter_pallas(lat, v: Array, taps: tuple[float, ...], *,
                        symmetrize: bool = True, transpose: bool = False,
                        interpret: bool = False) -> Array:
    """Run the whole lattice MVM as one Pallas kernel.

    ``transpose`` flips the sweep order (F^T); with ``symmetrize`` the
    operator is self-adjoint and the flag is a no-op by construction.
    Requires concrete (non-traced) ``taps``.
    """
    n, c = v.shape
    d, cap1 = lat.d, lat.cap + 1
    big = n * (d + 1)
    num_steps = 2 * (d + 1) if symmetrize else d + 1
    two_r = lat.nbr.shape[-1]

    # palindromic sweep order: fwd 0..d then rev d..0 (swapped on transpose)
    if symmetrize:
        def dir_map(t):
            a = jnp.where(t <= d, t, 2 * d + 1 - t)
            return (a, 0, 0)
    elif transpose:
        def dir_map(t):
            return (d - t, 0, 0)
    else:
        def dir_map(t):
            return (t, 0, 0)

    kernel = functools.partial(
        _fused_kernel, taps=tuple(taps), d=d, n=n, c=c, cap1=cap1, big=big,
        symmetrize=symmetrize)

    col = lambda a, dt: a.reshape(-1, 1).astype(dt)  # noqa: E731
    resident = lambda shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape))  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid=(num_steps,),
        in_specs=[
            resident((n, c)),          # v
            resident((big, 1)),        # sort_row
            resident((big, 1)),        # sort_w
            resident((big, 1)),        # seg_head (f32)
            resident((cap1, 1)),       # row_last
            resident((cap1, 1)),       # valid (f32)
            resident((n, d + 1)),      # seg_ids
            resident((n, d + 1)),      # weights
            pl.BlockSpec((1, cap1, two_r), dir_map),  # streamed nbr tile
        ],
        out_specs=resident((n, c)),
        out_shape=jax.ShapeDtypeStruct((n, c), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((cap1, c), v.dtype),  # table
            pltpu.VMEM((cap1, c), v.dtype),  # work
            pltpu.VMEM((cap1, c), v.dtype),  # accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        v,
        col(lat.sort_row, jnp.int32),
        col(lat.sort_w, v.dtype),
        col(lat.seg_head, v.dtype),
        col(lat.row_last, jnp.int32),
        col(lat.valid, v.dtype),
        lat.seg_ids.reshape(n, d + 1),
        lat.weights.astype(v.dtype),
        lat.nbr,
    )
    return out
