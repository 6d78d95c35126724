"""Lanczos tridiagonalization + stochastic Lanczos quadrature (SLQ).

Used for the log-determinant term of the MLL (paper Eq. 4) exactly as in
BBMM (Gardner et al. 2018a): with Rademacher probes ``z_i``,

    log|A| ~= (1/p) sum_i ||z_i||^2 * e_1^T log(T_i) e_1 ,

where ``T_i`` is the Lanczos tridiagonalization of ``A`` started at
``z_i/||z_i||``. Appendix A caps Lanczos at 100 iterations.

Everything is static-shape (``lax.scan``) and batched over probes with
``vmap`` so it lowers to a single While op — dry-run friendly.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

Array = jax.Array
MatVec = Callable[[Array], Array]  # (n, k) -> (n, k)


class LanczosResult(NamedTuple):
    alphas: Array  # (iters, k) tridiagonal diagonal
    betas: Array  # (iters, k) sub/super-diagonal; betas[j] couples j, j+1
    q: Array  # (iters, n, k) Lanczos basis (needed by LOVE-style variance)
    valid: Array  # (iters, k) True while the recurrence was healthy


@obs.scope("lanczos")
def lanczos(matvec: MatVec, q0: Array, num_iters: int,
            *, reorthogonalize: bool = True) -> LanczosResult:
    """Block-of-columns Lanczos with optional full reorthogonalization.

    q0: (n, k) start vectors (normalized internally). ``num_iters`` is small
    (<= 100 per Appendix A) so full reorthogonalization is O(iters^2 n k) but
    cheap, and necessary in float32.
    """
    n, k = q0.shape
    dt = q0.dtype
    q = q0 / jnp.maximum(jnp.linalg.norm(q0, axis=0), 1e-30)

    def body(carry, i):
        q_prev, q_cur, beta_prev, basis, alive = carry
        w = matvec(q_cur)
        alpha = jnp.sum(q_cur * w, axis=0)
        w = w - alpha * q_cur - beta_prev * q_prev
        if reorthogonalize:
            # w -= Q (Q^T w), Q = collected basis (rows masked by step < i)
            proj = jnp.einsum("jnk,nk->jk", basis, w)
            step_mask = (jnp.arange(num_iters) <= i)[:, None]
            proj = proj * step_mask
            w = w - jnp.einsum("jnk,jk->nk", basis, proj)
        beta = jnp.linalg.norm(w, axis=0)
        healthy = alive & (beta > 1e-10)
        q_next = jnp.where(healthy, w / jnp.maximum(beta, 1e-30), q_cur)
        basis = basis.at[i].set(q_cur)
        out = (alpha, jnp.where(healthy, beta, 0.0), alive)
        return (q_cur, q_next, jnp.where(healthy, beta, 0.0), basis, healthy), out

    basis0 = jnp.zeros((num_iters, n, k), dt)
    init = (jnp.zeros_like(q), q, jnp.zeros((k,), dt), basis0,
            jnp.ones((k,), bool))
    (_, _, _, basis, _), (alphas, betas, valid) = jax.lax.scan(
        body, init, jnp.arange(num_iters))
    return LanczosResult(alphas=alphas, betas=betas, q=basis, valid=valid)


def _tridiag_to_dense(alpha: Array, beta: Array, valid: Array) -> Array:
    """(iters,) coeffs -> (iters, iters) dense symmetric tridiagonal.

    Rows past breakdown are replaced by identity so eigenvalues contribute
    log(1) = 0.
    """
    t = jnp.diag(jnp.where(valid, alpha, 1.0))
    off = beta[:-1] * valid[:-1] * valid[1:]
    t = t + jnp.diag(off, 1) + jnp.diag(off, -1)
    return t


def slq_quadrature(alphas: Array, betas: Array, valid: Array,
                   f: Callable[[Array], Array]) -> Array:
    """e_1^T f(T) e_1 for each column's tridiagonal. -> (k,)"""

    def one(alpha, beta, v):
        t = _tridiag_to_dense(alpha, beta, v)
        evals, evecs = jnp.linalg.eigh(t)
        w = evecs[0, :] ** 2
        return jnp.sum(w * f(evals))

    return jax.vmap(one, in_axes=(1, 1, 1))(alphas, betas, valid)


@obs.scope("slq")
def slq_logdet(matvec: MatVec, n: int, *, key: Array, num_probes: int = 10,
               num_iters: int = 100, dtype=jnp.float32) -> Array:
    """SLQ estimate of log|A| for SPD A of size n."""
    z = jax.random.rademacher(key, (n, num_probes), dtype=dtype)
    res = lanczos(matvec, z, num_iters)
    safe_log = lambda lam: jnp.log(jnp.maximum(lam, 1e-30))
    quad = slq_quadrature(res.alphas, res.betas, res.valid, safe_log)
    znorm2 = jnp.sum(z * z, axis=0)
    return jnp.mean(znorm2 * quad)


@obs.scope("slq")
def slq_logdet_from_cg(alphas: Array, betas: Array, valid: Array,
                       probe_norms2: Array) -> Array:
    """Log-det estimate reusing mBCG's tridiagonal coefficients.

    alphas/betas/valid: as returned by solvers.cg (per probe column);
    probe_norms2: (p,) squared norms of the Rademacher probes (= n).
    """
    from repro.solvers.cg import CGInfo, lanczos_tridiag_from_cg

    info = CGInfo(iterations=None, residual_norms=None, converged=None,
                  alphas=alphas, betas=betas, valid=valid)
    diag, off = lanczos_tridiag_from_cg(info)
    pad_off = jnp.concatenate([off, jnp.zeros((1, off.shape[1]), off.dtype)])
    safe_log = lambda lam: jnp.log(jnp.maximum(lam, 1e-30))
    quad = slq_quadrature(diag, pad_off, jnp.ones_like(diag, bool), safe_log)
    return jnp.mean(probe_norms2 * quad)
