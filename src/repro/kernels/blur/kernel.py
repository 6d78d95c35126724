"""Pallas lattice-blur kernel — the hot spot the paper's CUDA kernel targets.

The CUDA blur probes a hash table per (point, neighbor); the TPU-native
reformulation (DESIGN.md §2) precomputes the neighbor table, so blur is a
*gather + stencil reduction*. This kernel blocks over lattice points:

  grid = (ceil(cap+1 / block_p),)
  per step VMEM holds: the full value table (cap+1, c) [gather source],
  one (block_p, 2r) index tile, and one (block_p, c) output tile.

The gather source stays resident across grid steps (its index_map is
constant, so Mosaic keeps it in VMEM rather than re-streaming it), which is
the right trade for c-small GP filtering: the value table for m = 500k
lattice points x 4 channels is 8 MB < 16 MB VMEM. ops.py falls back to the
XLA path when the table cannot fit.

Why one direction per pallas_call: the d+1 directional blurs are strictly
sequential (each consumes the previous output). This file is the
PER-DIRECTION tier of the backend policy (ops.py): the fully fused
splat->blur->slice kernel lives in fused.py and keeps the table resident
across all sweeps; this tier re-streams it once per direction but tolerates
a larger table. Two variants:

  * ``blur_direction_pallas`` — gather source resident in VMEM (table fits).
  * ``blur_direction_blocked_pallas`` — grid-blocked fallback for tables
    past the VMEM budget: lattice points tiled over the output grid axis,
    the gather source streamed tile-by-tile over a second (arbitrary) grid
    axis with contributions masked to the resident source tile. Traffic is
    O(num_src_tiles) x the table, so ops.py only engages it for moderately
    oversized tables and otherwise falls back to XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BLOCK_P = 1024


def _blur_kernel(vals_ref, nbr_ref, out_ref, *, taps: tuple[float, ...],
                 dump_row: int, block_p: int):
    """One direction, one block of lattice points."""
    i = pl.program_id(0)
    vals = vals_ref[...]  # (cap1, c) — resident gather source
    nbr = nbr_ref[...]  # (block_p, 2r)
    r = len(taps) // 2
    base = vals_ref[pl.dslice(i * block_p, block_p), :]  # this block's rows
    acc = base * taps[r]
    side = list(taps[:r]) + list(taps[r + 1:])
    for s, w in enumerate(side):
        acc = acc + w * jnp.take(vals, nbr[:, s], axis=0)
    # zero the dump row if it falls inside this block
    rows = i * block_p + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_p, 1), 0)
    acc = jnp.where(rows == dump_row, 0.0, acc)
    out_ref[...] = acc


def blur_direction_pallas(vals: Array, nbr_dir: Array,
                          stencil: tuple[float, ...], *,
                          block_p: int = DEFAULT_BLOCK_P,
                          interpret: bool = False) -> Array:
    """One directional blur. vals: (cap+1, c); nbr_dir: (cap+1, 2r)."""
    cap1, c = vals.shape
    dump_row = cap1 - 1
    pad = (-cap1) % block_p
    if pad:
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad, c), vals.dtype)], axis=0)
        nbr_dir = jnp.concatenate(
            [nbr_dir, jnp.full((pad, nbr_dir.shape[1]), dump_row,
                               nbr_dir.dtype)], axis=0)
    padded = cap1 + pad
    grid = (padded // block_p,)

    kernel = functools.partial(_blur_kernel, taps=tuple(stencil),
                               dump_row=dump_row, block_p=block_p)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # full table resident (constant index_map -> loaded once)
            pl.BlockSpec((padded, c), lambda i: (0, 0)),
            pl.BlockSpec((block_p, nbr_dir.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_p, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, c), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(vals, nbr_dir)
    return out[:cap1]


# ---------------------------------------------------------------------------
# Grid-blocked fallback: gather source streamed, never fully resident.
# ---------------------------------------------------------------------------


def _blur_blocked_kernel(src_ref, nbr_ref, out_ref, *,
                         taps: tuple[float, ...], dump_row: int,
                         block_p: int):
    """(out tile i, src tile j): accumulate the taps whose gather index
    lands inside src tile j. The out tile stays resident across the j axis
    (constant index_map), so `out_ref` accumulates read-modify-write."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    src = src_ref[...]  # (block_p, c) — rows [j*block_p, (j+1)*block_p)
    nbr = nbr_ref[...]  # (block_p, 2r) — for out rows of tile i
    r = len(taps) // 2
    acc = out_ref[...]

    # center tap: an out row's own value lives in src tile j == i
    acc = acc + jnp.where(j == i, taps[r], 0.0) * src

    base = j * block_p
    side = list(taps[:r]) + list(taps[r + 1:])
    for s, w in enumerate(side):
        loc = nbr[:, s] - base
        in_tile = (loc >= 0) & (loc < block_p)
        gathered = jnp.take(src, jnp.clip(loc, 0, block_p - 1), axis=0)
        acc = acc + w * jnp.where(in_tile[:, None], gathered, 0.0)

    # the dump row only ever accumulates zeros (its nbr entries all miss),
    # so unconditional zeroing on every pass is safe and keeps one store
    rows = i * block_p + jax.lax.broadcasted_iota(jnp.int32, (block_p, 1), 0)
    out_ref[...] = jnp.where(rows == dump_row, 0.0, acc)


def blur_direction_blocked_pallas(vals: Array, nbr_dir: Array,
                                  stencil: tuple[float, ...], *,
                                  block_p: int = DEFAULT_BLOCK_P,
                                  interpret: bool = False) -> Array:
    """Streaming-source directional blur for tables past the VMEM budget."""
    cap1, c = vals.shape
    dump_row = cap1 - 1
    pad = (-cap1) % block_p
    if pad:
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad, c), vals.dtype)], axis=0)
        nbr_dir = jnp.concatenate(
            [nbr_dir, jnp.full((pad, nbr_dir.shape[1]), dump_row,
                               nbr_dir.dtype)], axis=0)
    padded = cap1 + pad
    num_src = padded // block_p
    grid = (num_src, num_src)

    kernel = functools.partial(_blur_blocked_kernel, taps=tuple(stencil),
                               dump_row=dump_row, block_p=block_p)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_p, c), lambda i, j: (j, 0)),  # src stream
            pl.BlockSpec((block_p, nbr_dir.shape[1]), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_p, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, c), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(vals, nbr_dir)
    return out[:cap1]
