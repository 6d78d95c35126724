"""Plain Simplex-GP training step and validation posterior: the reference
the benchmark's correctness check compares with.

Follows the paper (Eq. 4 with the BBMM estimator of Gardner et al. 2018,
section 4.2 for the lengthscale gradient, Appendix A for the solver
settings) on the lattice of ``permutohedral.py``, in float64 NumPy. The
solvers follow their published definitions with the stopping rules the
configuration states: CG stops a column once its relative residual is
under the tolerance, after at least ``min_iters`` iterations; the log-det
is stochastic Lanczos quadrature on the tridiagonals CG collects. The
probes come from ``jax.random`` with the keys the benchmark hands to the
program, so both see the same estimator.

``store`` (see ``permutohedral.bf16``) lowers the precision of the
lattice tables for the control run.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np

from bench.reference import permutohedral as lat

MIN_ITERS = 10  # CG's refinement floor (GPyTorch's, which the paper ran)


def softplus(x):
    return np.logaddexp(0.0, x)


def sigmoid(x):
    return 0.5 * (1.0 + np.tanh(0.5 * np.asarray(x, np.float64)))


def inv_softplus(y):
    return y + np.log(-np.expm1(-y))


@dataclasses.dataclass
class Params:
    """Raw (softplus-space) hyperparameters."""
    raw_ls: np.ndarray
    raw_os: float
    raw_noise: float

    @staticmethod
    def init(d: int, ls=1.0, os_=1.0, noise=0.1) -> "Params":
        return Params(np.full(d, inv_softplus(ls)), float(inv_softplus(os_)),
                      float(inv_softplus(noise)))

    def leaves(self) -> dict:
        return {"raw_lengthscale": np.atleast_1d(self.raw_ls),
                "raw_outputscale": np.atleast_1d(self.raw_os),
                "raw_noise": np.atleast_1d(self.raw_noise)}


@dataclasses.dataclass
class CGOut:
    x: np.ndarray
    alphas: np.ndarray  # (max_iters, k)
    betas: np.ndarray
    valid: np.ndarray


def cg(mvm, b, *, tol, max_iters) -> CGOut:
    """CG over a block of columns from zero."""
    n, k = b.shape
    x, r = np.zeros_like(b), b.copy()
    active = np.ones(k, bool)
    bnorm = np.maximum(np.linalg.norm(b, axis=0), 1e-30)
    p, rz = r.copy(), np.sum(r * r, axis=0)
    alphas = np.zeros((max_iters, k))
    betas = np.zeros((max_iters, k))
    valid = np.zeros((max_iters, k), bool)
    for j in range(max_iters):
        if not active.any():
            break
        ap = mvm(p)
        pap = np.sum(p * ap, axis=0)
        alpha = np.where(active & (pap > 0), rz / np.where(pap > 0, pap, 1.0),
                         0.0)
        x = x + alpha * p
        r = r - alpha * ap
        rz_new = np.sum(r * r, axis=0)
        beta = np.where(active, rz_new / np.where(rz != 0, rz, 1.0), 0.0)
        p = r + beta * p
        alphas[j], betas[j], valid[j] = alpha, beta, active
        rz = rz_new
        res = np.linalg.norm(r, axis=0) / bnorm
        active = active & ((res > tol) | (j + 1 < MIN_ITERS))
    return CGOut(x, alphas, betas, valid)


def logdet_from_cg(out: CGOut, cols: slice, n: int) -> float:
    """SLQ log-det from CG's Lanczos coefficients (the probe columns),
    by the CG-Lanczos identity (Golub & Van Loan 10.2)."""
    a, b, v = out.alphas[:, cols], out.betas[:, cols], out.valid[:, cols]
    safe = np.where(v & (a != 0), a, 1.0)
    inv = 1.0 / safe
    diag = np.concatenate([inv[:1], inv[1:] + np.where(v[:-1], b[:-1] / safe[:-1],
                                                       0.0)])
    diag = np.where(v, diag, 1.0)
    off = np.where(v[:-1] & (b[:-1] >= 0),
                   np.sqrt(np.maximum(b[:-1], 0.0)) / safe[:-1], 0.0)
    quads = []
    for j in range(diag.shape[1]):
        t = np.diag(diag[:, j]) + np.diag(off[:, j], 1) + np.diag(off[:, j], -1)
        ev, vec = np.linalg.eigh(t)
        quads.append(np.sum(vec[0] ** 2 * np.log(np.maximum(ev, 1e-30))))
    return float(np.mean(n * np.asarray(quads)))


class SimplexGP:
    """The model of one configuration, in the reference's own terms."""

    def __init__(self, model: dict, store=None):
        self.cfg = model
        self.r = model["order"]
        self.spacing, self.taps, self.dtaps, self.dscale = lat.stencil(
            model["kernel"], self.r)
        self.store = store

    def scales(self, p: Params):
        return (softplus(p.raw_ls), softplus(p.raw_os),
                softplus(p.raw_noise) + self.cfg["min_noise"])

    def lattice(self, x, ls):
        return lat.Lattice(np.asarray(x, np.float64) / ls[None, :],
                           self.spacing, self.r)

    # -- training step (paper Eq. 4, BBMM estimator) --------------------------

    def mll_step(self, p: Params, x, y, key):
        """MLL value, the magnitude of its terms, and d(-MLL)/d(raw params)
        at ``p`` for the step key."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        n, _ = x.shape
        npr = self.cfg["num_probes"]
        pk, _, _ = jax.random.split(key, 3)
        probes = np.asarray(jax.random.rademacher(pk, (n, npr), dtype=np.float32),
                            np.float64)
        ls, os_, noise = self.scales(p)
        lt = self.lattice(x, ls)
        fwd, drv = lt.blur_mats(self.taps), lt.blur_mats(self.dtaps)
        mvm = lambda v: os_ * lt.filter(v, fwd, self.store) + noise * v
        out = cg(mvm, np.concatenate([y[:, None], probes], axis=1),
                 tol=self.cfg["cg_tol_train"], max_iters=self.cfg["max_cg_iters"])
        u, w = out.x[:, 0], out.x[:, 1:]
        logdet = logdet_from_cg(out, slice(1, None), n)
        mll = -0.5 * y @ u - 0.5 * logdet - 0.5 * n * math.log(2.0 * math.pi)
        # the MLL is a difference of terms far larger than itself; the
        # magnitudes of the two that are computed (not the exact constant)
        # are the scale its rounding is read against
        scale = 0.5 * abs(y @ u) + 0.5 * abs(logdet)

        # surrogate S = os sum(a * F b) + noise sum(a * b) with u, W, Z fixed
        a = np.concatenate([0.5 * u[:, None], (-0.5 / npr) * w], axis=1)
        b = np.concatenate([u[:, None], probes], axis=1)
        fb = lt.filter(b, fwd, self.store)
        z = x / ls[None, :]
        g = os_ * a  # cotangent of F b in S
        c = b.shape[1]
        zg = (z[:, :, None] * g[:, None, :]).reshape(n, -1)
        zb = (z[:, :, None] * b[:, None, :]).reshape(n, -1)
        big = lt.filter(np.concatenate([zg, g, zb, b], axis=1), drv, self.store)
        dc = zg.shape[1]
        fa = big[:, :dc].reshape(n, -1, c)  # F'(z * g)
        fg = big[:, dc:dc + c]  # F' g
        fc = big[:, dc + c:2 * dc + c].reshape(n, -1, c)  # F'(z * b)
        fd = big[:, 2 * dc + c:]  # F' b
        dz = 2.0 * self.dscale * (
            z * np.sum(b * fg, axis=1, keepdims=True)
            - np.einsum("nc,ndc->nd", b, fa)
            + z * np.sum(g * fd, axis=1, keepdims=True)
            - np.einsum("nc,ndc->nd", g, fc))
        ds_dls = -np.sum(dz * z, axis=0) / ls
        grads = Params(-sigmoid(p.raw_ls) * ds_dls,
                       float(-sigmoid(p.raw_os) * np.sum(a * fb)),
                       float(-sigmoid(p.raw_noise) * np.sum(a * b)))
        return float(mll), float(scale), grads

    # -- validation posterior mean -------------------------------------------

    def posterior_mean(self, p: Params, x, y, xs):
        """Predictive mean at ``xs``: the solve and the cross-covariance on
        one lattice over the joint point set, as the paper's posterior."""
        x = np.asarray(x, np.float64)
        xs = np.asarray(xs, np.float64)
        n, ns = x.shape[0], xs.shape[0]
        ls, os_, noise = self.scales(p)
        lt = self.lattice(np.concatenate([x, xs]), ls)
        fwd = lt.blur_mats(self.taps)
        pad = lambda v: np.concatenate([v, np.zeros((ns, v.shape[1]))])
        mvm = lambda v: os_ * lt.filter(pad(v), fwd, self.store)[:n] + noise * v
        out = cg(mvm, np.asarray(y, np.float64)[:, None],
                 tol=self.cfg["cg_tol_eval"], max_iters=self.cfg["max_cg_iters"])
        return os_ * lt.filter(pad(out.x), fwd, self.store)[n:, 0]


def adam(p: Params, g: Params, state: dict, lr: float, b1=0.9, b2=0.999,
         eps=1e-8):
    """One Adam step (Kingma & Ba 2015) over the three raw leaves."""
    t = state["t"] + 1
    out, new = {}, {"t": t}
    for name in ("raw_ls", "raw_os", "raw_noise"):
        gv = np.asarray(getattr(g, name), np.float64)
        m = b1 * state.get("m_" + name, 0.0) + (1 - b1) * gv
        v = b2 * state.get("v_" + name, 0.0) + (1 - b2) * gv * gv
        new["m_" + name], new["v_" + name] = m, v
        upd = (m / (1 - b1 ** t)) / (np.sqrt(v / (1 - b2 ** t)) + eps)
        out[name] = getattr(p, name) - lr * upd
    return Params(out["raw_ls"], float(out["raw_os"]),
                  float(out["raw_noise"])), new
