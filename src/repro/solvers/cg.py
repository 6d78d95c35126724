"""Batched (preconditioned) conjugate gradients — the BBMM workhorse (§2, §5.4).

The paper's inference loop (GPyTorch-style BBMM, Gardner et al. 2018a) needs
only MVMs ``v -> K_hat v``. We implement *mBCG*: CG over a block of
right-hand-sides ``B = [y | z_1 .. z_p]`` that simultaneously

  * solves ``K_hat X = B``,
  * collects the Lanczos tridiagonal coefficients (alpha, beta) per column,
    which SLQ (solvers/lanczos.py) turns into a log-det estimate "for free".

The loop is a ``lax.while_loop`` that exits on the residuals: a column
stops once ``||r|| <= tol * ||b||`` (the paper's "CG error tolerance",
Appendix A: tol 1.0 train / 0.01 eval) after the ``min_iters`` floor, and
the loop stops once every column has, or at ``max_iters``. Each trip
costs one MVM on the whole block, so a solve pays for the iterations its
stopping rule uses, not for ``max_iters``. The coefficient buffers keep the
static ``(max_iters, k)`` shape; rows past a column's exit hold alpha =
beta = 0 and valid = False, what a frozen trip would have written.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

Array = jax.Array
MatVec = Callable[[Array], Array]  # (n, k) -> (n, k)


class CGInfo(NamedTuple):
    iterations: Array  # () int32: iterations actually used (max over columns)
    residual_norms: Array  # (k,) final ||r_j|| / ||b_j||
    converged: Array  # (k,) bool
    alphas: Array  # (max_iters, k) Lanczos-from-CG coefficients
    betas: Array  # (max_iters, k)
    valid: Array  # (max_iters, k) bool: True where the iterate was active


def _identity_precond(v: Array) -> Array:
    return v


def _seed_state(matvec: MatVec, b: Array,
                x0: Array | None) -> tuple[Array, Array]:
    """Initial (x, r) with warm-start hygiene (DESIGN.md §14).

    Warm-start seeds come from durable state — a previous Predictor's
    alpha, possibly restored from a checkpoint written under DIFFERENT
    hyperparameters or data — so they are sanitized, never trusted:
    non-finite entries are zeroed (one NaN would poison the whole Krylov
    basis), and any column whose seed residual is WORSE than the zero
    start (``||b - A x0|| > ||b||``) is reset to the cold start for that
    column. A stale seed can therefore only help or be ignored; it can
    never make the solve slower to converge than a cold one.
    """
    if x0 is None:
        return jnp.zeros_like(b), b
    x = jnp.where(jnp.isfinite(x0), x0, 0.0).astype(b.dtype)
    r = b - matvec(x)
    worse = (jnp.linalg.norm(r, axis=0)
             > jnp.linalg.norm(b, axis=0))  # (k,) regressive seeds
    x = jnp.where(worse[None, :], 0.0, x)
    r = jnp.where(worse[None, :], b, r)
    return x, r


@obs.scope("cg")
def cg(
    matvec: MatVec,
    b: Array,
    *,
    precond: MatVec | None = None,
    tol: float | Array = 1e-2,
    max_iters: int = 500,
    min_iters: int = 10,
    x0: Array | None = None,
) -> tuple[Array, CGInfo]:
    """Preconditioned CG on SPD ``A`` for a block of RHS columns.

    Multi-RHS contract: the whole block advances together — each
    iteration issues exactly ONE ``matvec`` on the full (n, k) block
    (never one per column), so with a lattice operator every iteration
    costs one batched lattice MVM regardless of how many probes ride
    along. The ``mvm.calls``/``mvm.cols`` counters of ``repro.obs``
    instrument this (tests/test_solvers.py pins it); sharded operators
    (DESIGN.md §10) then also pay one psum per iteration, not k.

    Args:
      matvec: ``v -> A v`` over (n, k) blocks.
      b: (n, k) right-hand sides.
      precond: ``v -> P^{-1} v`` (SPD); None = identity.
      tol: relative residual tolerance (paper Appendix A: 1.0 train / 0.01 eval).
      max_iters: the most iterations (paper Appendix A: 500); also the
        static row count of the coefficient buffers.
      min_iters: iterations always run before the tolerance may stop a
        column (GPyTorch semantics — at the paper's train tolerance 1.0 the
        *initial* relative residual is exactly 1, so without a floor CG
        would do nothing; GPyTorch's 10-iteration floor is what actually
        does the work at tol=1).
      x0: optional warm start (gp/serve.refreeze seeds from the previous
        Predictor's alpha). Seeds pass ``_seed_state`` hygiene, and a
        column whose seed is already within ``tol`` starts inactive, so a
        perfect seed costs one matvec and no iteration.

    Returns:
      x: (n, k) approximate solves.
      info: CGInfo, including the (alpha, beta) tridiagonal coefficients of
        the *preconditioned* operator, for SLQ.
    """
    if b.ndim == 1:
        raise ValueError("cg expects (n, k) column-blocked RHS; got 1-D")
    minv = precond or _identity_precond
    n, k = b.shape
    dt = b.dtype

    x, r = _seed_state(matvec, b, x0)
    z = minv(r)
    p = z
    rz = jnp.sum(r * z, axis=0)  # (k,)
    bnorm = jnp.maximum(jnp.linalg.norm(b, axis=0), 1e-30)
    tol_arr = jnp.asarray(tol, dt)
    min_iters = min(min_iters, max_iters)
    # a cold start must enter the loop even at tol >= 1 (the min_iters
    # contract); a warm start may skip columns its seed already solved
    if x0 is None:
        active0 = jnp.ones((k,), bool)
    else:
        active0 = jnp.linalg.norm(r, axis=0) / bnorm > tol_arr

    def cond(state):
        j, *_rest, active, _coefs = state
        return (j < max_iters) & jnp.any(active)

    def body(state):
        j, x, r, z, p, rz, active, (alphas, betas, valid) = state
        ap = matvec(p)
        pap = jnp.sum(p * ap, axis=0)
        # guard: inactive / degenerate columns get alpha = 0 (no update)
        safe_pap = jnp.where(pap > 0, pap, 1.0)
        alpha = jnp.where(active & (pap > 0), rz / safe_pap, 0.0)
        x = x + alpha * p
        r = r - alpha * ap
        z = minv(r)
        rz_new = jnp.sum(r * z, axis=0)
        safe_rz = jnp.where(rz != 0, rz, 1.0)
        beta = jnp.where(active, rz_new / safe_rz, 0.0)
        p = z + beta * p
        res = jnp.linalg.norm(r, axis=0) / bnorm
        still = active & ((res > tol_arr) | (j + 1 < min_iters))
        coefs = tuple(jax.lax.dynamic_update_index_in_dim(buf, row, j, 0)
                      for buf, row in ((alphas, alpha), (betas, beta),
                                       (valid, active)))
        return (j + 1, x, r, z, p, rz_new, still, coefs)

    coefs0 = (jnp.zeros((max_iters, k), dt), jnp.zeros((max_iters, k), dt),
              jnp.zeros((max_iters, k), bool))
    state = (jnp.zeros((), jnp.int32), x, r, z, p, rz, active0, coefs0)
    j, x, r, *_rest, (alphas, betas, valid) = jax.lax.while_loop(
        cond, body, state)

    res = jnp.linalg.norm(r, axis=0) / bnorm
    info = CGInfo(
        iterations=j,
        residual_norms=res,
        converged=res <= tol_arr,
        alphas=alphas,
        betas=betas,
        valid=valid,
    )
    return x, info


def lanczos_tridiag_from_cg(info: CGInfo) -> tuple[Array, Array]:
    """Recover symmetric-tridiagonal (diag, offdiag) per column from CG.

    Standard CG<->Lanczos identity (Golub & Van Loan §10):
      T[0,0]   = 1/alpha_0
      T[j,j]   = 1/alpha_j + beta_{j-1}/alpha_{j-1}
      T[j,j-1] = sqrt(beta_{j-1}) / alpha_{j-1}

    Returns (diag, offdiag) with shapes (max_iters, k), (max_iters-1, k);
    entries past a column's convergence are padded so that eigenvalues appear
    as exact 1.0 (harmless for log-dets of unit-free operators we use this
    with — SLQ masks them out via ``valid`` anyway).
    """
    a, b, valid = info.alphas, info.betas, info.valid
    safe_a = jnp.where(valid & (a != 0), a, 1.0)
    inv_a = 1.0 / safe_a
    diag0 = inv_a[:1]
    diag_rest = inv_a[1:] + jnp.where(valid[:-1], b[:-1] / safe_a[:-1], 0.0)
    diag = jnp.concatenate([diag0, diag_rest], axis=0)
    off = jnp.where(valid[:-1] & (b[:-1] >= 0),
                    jnp.sqrt(jnp.maximum(b[:-1], 0.0)) / safe_a[:-1], 0.0)
    # freeze rows after convergence to identity
    diag = jnp.where(valid, diag, 1.0)
    return diag, off
