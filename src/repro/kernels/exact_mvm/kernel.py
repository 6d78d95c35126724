"""Tiled exact stationary-kernel MVM (the paper's KeOps baseline, §5.1/Fig 6).

TPU mapping of the "never materialize K" trick: the (n x n) kernel matrix is
produced tile-by-tile in VMEM and immediately contracted against v.

Grid: (n/bn row-tiles, n/bm col-tiles), row-parallel, cols sequential
(accumulation). Per step the kernel holds
    x_i (bn, d) + x_j (bm, d) + v_j (bm, c) + out (bn, c) + K-tile (bn, bm)
in VMEM; with bn = bm = 256, d,c <= 128 that is ~0.5 MB — far under the
16 MB/core budget, and the (bn x bm) distance matmul x_i @ x_j^T runs on the
MXU with 128-aligned tiles.

Arithmetic intensity: the K-tile costs O(bn bm d) FLOPs for O((bn+bm) d)
bytes — compute-bound for n >> bn, exactly why the paper's exact baseline
saturates GPU FLOPs and why Fig 6's crossover sits at ~1e5 points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_math import KernelProfile

Array = jax.Array


DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_M = 256


def _mvm_kernel(x_i_ref, x_j_ref, v_j_ref, o_ref, *, profile: KernelProfile,
                num_col_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xi = x_i_ref[...]  # (bn, d)
    xj = x_j_ref[...]  # (bm, d)
    vj = v_j_ref[...]  # (bm, c)
    # pairwise squared distances via the MXU: |xi|^2 + |xj|^2 - 2 xi xj^T
    ni = jnp.sum(xi * xi, axis=1)[:, None]
    nj = jnp.sum(xj * xj, axis=1)[None, :]
    sq = jnp.maximum(ni + nj - 2.0 * jax.lax.dot(
        xi, xj.T, precision=jax.lax.Precision.HIGHEST), 0.0)
    tau = jnp.sqrt(sq + 1e-30)
    k_tile = profile.k(tau)  # (bn, bm), fused elementwise on the VPU
    o_ref[...] += jax.lax.dot(k_tile, vj,
                              precision=jax.lax.Precision.HIGHEST)


def exact_mvm_pallas(profile: KernelProfile, x: Array, v: Array, *,
                     block_n: int = DEFAULT_BLOCK_N,
                     block_m: int = DEFAULT_BLOCK_M,
                     interpret: bool = True) -> Array:
    """u = K(X,X) v with K produced tile-wise in VMEM.

    x: (n, d) lengthscale-normalized inputs; v: (n, c). n must be padded to
    a multiple of the block sizes by the caller (ops.py handles it).
    """
    n, d = x.shape
    c = v.shape[1]
    assert n % block_n == 0 and n % block_m == 0, (n, block_n, block_m)
    grid = (n // block_n, n // block_m)

    kernel = functools.partial(_mvm_kernel, profile=profile,
                               num_col_blocks=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),  # x rows
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),  # x cols
            pl.BlockSpec((block_m, c), lambda i, j: (j, 0)),  # v cols
        ],
        out_specs=pl.BlockSpec((block_n, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, x, v)
