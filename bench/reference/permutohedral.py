"""Plain permutohedral-lattice filter: the reference the benchmark checks
the program against.

Written from the paper (Kapoor et al. 2021, sections 3-4; Adams et al.
2010) in float64 NumPy and SciPy sparse matrices. It imports nothing of
the program under test and takes nothing it made: the stencil spacing is
solved again from Eq. 9, the lattice is built from the inputs, and the
operator ``F = W (B_fwd + B_rev) / 2 W^T`` is held as sparse matrices:

  * ``W`` (n x m): barycentric interpolation of each input onto the d+1
    vertices of its enclosing simplex;
  * ``B_a`` (m x m): the (2r+1)-tap blur along lattice direction a,
    ``B_fwd = B_d ... B_0`` and ``B_rev = B_0 ... B_d``.

``store`` rounds every stored lattice table (the splat, each sweep, the
slice) to a lower precision: the control of the correctness check runs
the same reference with bfloat16 storage and f32 accumulation.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np
import scipy.sparse as sp

SQRT3 = math.sqrt(3.0)

# Matern-3/2 profile: k(tau) and dk/d(tau^2) (paper Eq. 11's k')
PROFILES = {
    "matern32": (lambda t: (1.0 + SQRT3 * np.abs(t)) * np.exp(-SQRT3 * np.abs(t)),
                 lambda t: -1.5 * np.exp(-SQRT3 * np.abs(t))),
}


def _check_profile(name: str):
    if name not in PROFILES:
        raise ValueError(f"reference has no kernel profile {name!r}")
    return PROFILES[name]


def eq9_spacing(kernel: str, r: int) -> float:
    """Tap spacing s at which the kernel's spatial mass inside the stencil
    equals its spectral mass inside the Nyquist band (paper Eq. 9)."""
    k_fn, _ = _check_profile(kernel)
    big_t, npts = 64.0, 1 << 17
    tau = np.linspace(0.0, big_t, npts)
    k = k_fn(tau)
    dt = tau[1] - tau[0]
    ck = np.concatenate([[0.0], np.cumsum((k[1:] + k[:-1]) * 0.5 * dt)])
    full = np.concatenate([k, k[-2:0:-1]])
    spec = np.maximum(np.fft.rfft(full).real * dt, 0.0)
    omega = 2.0 * math.pi * np.fft.rfftfreq(full.size, d=dt)
    dw = omega[1] - omega[0]
    cs = np.concatenate([[0.0], np.cumsum((spec[1:] + spec[:-1]) * 0.5 * dw)])

    def gap(s):
        lhs = np.interp(min(s * (2 * r + 1) / 2.0, big_t), tau, ck) / ck[-1]
        rhs = np.interp(min(math.pi / s, omega[-1]), omega, cs) / cs[-1]
        return lhs - rhs

    lo, hi = 1e-4, big_t / max(r, 1)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if gap(mid) < 0:
            lo = mid
        else:
            hi = mid
        if hi - lo < 1e-9:
            break
    return 0.5 * (lo + hi)


def stencil(kernel: str, r: int):
    """(spacing, taps, dtaps, dscale): the kernel and its derivative k'
    sampled at the lattice steps, each normalised to a centre tap of 1."""
    k_fn, dk_fn = _check_profile(kernel)
    s = eq9_spacing(kernel, r)
    tau = np.abs(np.arange(-r, r + 1, dtype=np.float64)) * s
    dscale = float(dk_fn(np.zeros(())))
    return s, k_fn(tau), dk_fn(tau) / dscale, dscale


def embed(z: np.ndarray, spacing: float):
    """Enclosing-simplex vertex keys (n, d+1, d+1) and barycentric weights
    (n, d+1) of each input (Adams et al. 2010, section 3)."""
    z = np.asarray(z, np.float64)
    n, d = z.shape
    scale = math.sqrt(d * (d + 1.0)) / spacing
    j = np.arange(d, dtype=np.float64)
    c = z * (scale / np.sqrt((j + 1.0) * (j + 2.0)))[None, :]
    suffix = np.concatenate([np.cumsum(c[:, ::-1], axis=1)[:, ::-1],
                             np.zeros((n, 1))], axis=1)
    el = np.concatenate(
        [suffix[:, :1], suffix[:, 1:] - np.arange(1, d + 1)[None, :] * c],
        axis=1)
    rem0 = np.round(el / (d + 1.0)) * (d + 1.0)
    diff = el - rem0
    # descending rank; ties go to the lower coordinate index
    bigger = diff[:, None, :] > diff[:, :, None]
    ties = (diff[:, None, :] == diff[:, :, None]) & np.tri(d + 1, k=-1,
                                                            dtype=bool)[None]
    rank = np.sum(bigger | ties, axis=2)
    rank = rank + np.round(rem0.sum(axis=1) / (d + 1.0)).astype(np.int64)[:, None]
    under, over = rank < 0, rank > d
    rank = np.where(under, rank + d + 1, np.where(over, rank - d - 1, rank))
    rem0 = np.where(under, rem0 + d + 1, np.where(over, rem0 - d - 1, rem0))
    delta = (el - rem0) / (d + 1.0)
    bary = np.zeros((n, d + 2))
    rows = np.arange(n)[:, None]
    np.add.at(bary, (rows, d - rank), delta)
    np.add.at(bary, (rows, d + 1 - rank), -delta)
    bary[:, 0] += 1.0 + bary[:, d + 1]
    kk = np.arange(d + 1)[None, :, None]
    canon = kk - (d + 1) * ((rank[:, None, :] + kk) > d)
    keys = np.round(rem0).astype(np.int64)[:, None, :] + canon
    return keys.astype(np.int32), bary[:, : d + 1]


def _rows(keys: np.ndarray) -> np.ndarray:
    """Each int32 key row as one opaque value, for sort and search."""
    k = np.ascontiguousarray(keys, np.int32)
    return k.view(np.dtype((np.void, 4 * k.shape[-1]))).reshape(k.shape[:-1])


def _lookup(table: np.ndarray, queries: np.ndarray):
    """Row of each query key in the sorted unique ``table``, -1 if absent."""
    q = _rows(queries)
    pos = np.minimum(np.searchsorted(table, q), table.shape[0] - 1)
    return np.where(table[pos] == q, pos, -1)


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back: the control's table storage."""
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class Lattice:
    """The lattice of inputs ``z`` (already divided by the lengthscales)."""

    def __init__(self, z: np.ndarray, spacing: float, r: int = 1):
        n, d = z.shape
        self.n, self.d, self.r, self.spacing = n, d, r, spacing
        keys, bary = embed(z, spacing)
        self.keys_sorted, inv = np.unique(_rows(keys.reshape(-1, d + 1)),
                                          return_inverse=True)
        self.m = int(self.keys_sorted.shape[0])
        self.coords = self.keys_sorted.view(np.int32).reshape(self.m, d + 1)
        seg = inv.reshape(n, d + 1)
        self.seg, self.bary = seg, bary
        self.W = sp.csr_matrix(
            (bary.ravel(), (np.repeat(np.arange(n), d + 1), seg.ravel())),
            shape=(n, self.m))
        self.Wt = self.W.T.tocsr()
        # neighbour slots along each direction a at steps -r..-1, 1..r
        dirs = (d + 1) * np.eye(d + 1, dtype=np.int64) - 1
        steps = [s for s in range(-r, r + 1) if s != 0]
        self.nbr = np.stack([
            np.stack([_lookup(self.keys_sorted, self.coords + s * dirs[a])
                      for s in steps], axis=1) for a in range(d + 1)])

    def blur_mats(self, taps: np.ndarray):
        """Sparse ``B_a`` for each direction a, for one (2r+1) stencil."""
        r, m = self.r, self.m
        side = np.concatenate([taps[:r], taps[r + 1:]])
        mats = []
        for a in range(self.d + 1):
            nb = self.nbr[a]
            hit = nb >= 0
            rows = np.concatenate([np.arange(m),
                                   np.nonzero(hit)[0]])
            cols = np.concatenate([np.arange(m), nb[hit]])
            vals = np.concatenate([np.full(m, taps[r]),
                                   np.broadcast_to(side, nb.shape)[hit]])
            mats.append(sp.csr_matrix((vals, (rows, cols)), shape=(m, m)))
        return mats

    def blur(self, table: np.ndarray, mats, store=None) -> np.ndarray:
        """Symmetrised blur ``(B_fwd + B_rev) / 2`` of an (m, c) table."""
        keep = store or (lambda a: a)
        fwd, rev = table, table
        for a in range(self.d + 1):
            fwd = keep(mats[a] @ fwd)
            rev = keep(mats[self.d - a] @ rev)
        return keep(0.5 * (fwd + rev))

    def filter(self, v: np.ndarray, mats, store=None) -> np.ndarray:
        """``F v`` for an (n, c) block: splat, blur, slice."""
        keep = store or (lambda a: a)
        table = keep(self.Wt @ np.asarray(v, np.float64))
        return self.W @ self.blur(table, mats, store)
