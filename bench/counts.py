"""Operations and compulsory bytes of the work the algorithm needs.

Counted from the shapes of the problem, never from what an implementation
happens to run: n points in d dimensions, c value channels, stencil
radius r, ``m`` the OCCUPIED lattice vertices (not a padded capacity) and
the CG iterations the step reports. A FLOP is one multiply or one add; a
multiply-add is 2. Values and weights are 4-byte floats, indices 4-byte
integers.

Bytes are compulsory traffic: each input of the operation read once and
each output written once. No table re-read per blur sweep is charged, so
an implementation that keeps the table on chip across sweeps reads 100%
of its roofline at best, never more.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def mvm(n: int, d: int, m: int, c: int, r: int = 1,
        symmetrize: bool = True) -> tuple[float, float]:
    """One lattice MVM ``W B W^T v`` on an (n, c) block.

    FLOPs:
      * splat ``W^T v``: n (d+1) c multiply-adds;
      * blur: (2r+1) taps per vertex per channel per sweep, over d+1 sweeps,
        twice when symmetrised (forward and reverse order) plus the m c
        averaging of the two;
      * slice ``W u``: n (d+1) c multiply-adds.
    Bytes: the per-point vertex indices and weights (n (d+1) each), v and
    the result (n c each), and the neighbour indices ((d+1) m 2r).
    """
    passes = 2 if symmetrize else 1
    flops = (2.0 * n * (d + 1) * c
             + passes * (d + 1) * 2.0 * (2 * r + 1) * m * c
             + (m * c if symmetrize else 0)
             + 2.0 * n * (d + 1) * c)
    nbytes = (n * (d + 1) * (I32 + F32) + 2.0 * n * c * F32
              + (d + 1) * m * 2 * r * I32)
    return flops, nbytes


def build(n: int, d: int, m: int, r: int = 1) -> tuple[float, float]:
    """One lattice build from (n, d) inputs.

    FLOPs per point: the elevation (a suffix sum and a scale, 3d), the
    rounding and its differential (3 (d+1)), the descending rank ((d+1)^2
    comparisons), the barycentric weights (2 (d+1)) and the d+1 vertex
    keys ((d+1)^2 adds). Per vertex: the 2r (d+1) neighbour keys of
    (d+1) coordinates each.
    Bytes: x read; the per-point vertex indices and weights, the vertex
    coordinates and the neighbour indices written.
    """
    k = d + 1
    flops = n * (3.0 * d + 5.0 * k + 2.0 * k * k) + 2.0 * r * k * k * m
    nbytes = (n * d * F32 + n * k * (I32 + F32) + m * k * I32
              + k * m * 2 * r * I32)
    return flops, nbytes


def train_step(n: int, d: int, m: int, c: int, cg_iters: int,
               r: int = 1) -> tuple[float, float]:
    """One training step of the BBMM MLL (paper Eq. 4) with its gradient.

    One build; ``cg_iters`` MVMs on the [y | probes] block of c channels,
    each with CG's vector work (two dot products and three axpys, 10 n c
    FLOPs; x, r, p and the MVM's result read and written, 7 n c values);
    the surrogate's forward MVM (c channels) and its section 4.2 gradient
    as one MVM with the derivative stencil on 2 c (d+1) channels. The SLQ
    eigendecompositions (num_probes of at most max_cg_iters^2) are left
    out: under 0.1% of the rest at any of the benchmark's sizes.
    """
    f_b, b_b = build(n, d, m, r)
    f_m, b_m = mvm(n, d, m, c, r)
    f_g, b_g = mvm(n, d, m, 2 * c * (d + 1), r)
    flops = f_b + cg_iters * (f_m + 10.0 * n * c) + f_m + f_g
    nbytes = b_b + cg_iters * (b_m + 7.0 * n * c * F32) + b_m + b_g
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The time the chip needs at least: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
