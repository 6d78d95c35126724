"""Rank-k pivoted Cholesky preconditioner (Appendix A: rank 100).

Greedy partial Cholesky of the *exact* kernel matrix: at each step pick the
pivot with the largest residual diagonal, append the corresponding scaled
residual column. The preconditioner for CG on ``K + sigma^2 I`` is then

    P = L L^T + sigma^2 I ,     P^{-1} via Woodbury:
    P^{-1} v = (v - L (sigma^2 I_k + L^T L)^{-1} L^T v) / sigma^2 .

Only ``rank`` exact kernel *rows* are ever formed (O(rank * n * d) total),
so the preconditioner never materializes K — the same trick GPyTorch uses.
The whole build is a ``lax.scan`` with static rank: jittable, TPU-safe.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

Array = jax.Array


class PivotedCholesky(NamedTuple):
    l: Array  # (n, rank)
    pivots: Array  # (rank,) int32
    error: Array  # () trace of the residual diagonal


@obs.scope("precond")
def pivoted_cholesky(row_fn: Callable[[Array], Array], diag: Array,
                     rank: int) -> PivotedCholesky:
    """Greedy rank-`rank` Cholesky. row_fn(i) -> K[i, :] (length n)."""
    n = diag.shape[0]
    dt = diag.dtype

    def body(carry, j):
        d, l = carry
        piv = jnp.argmax(d).astype(jnp.int32)
        dp = jnp.maximum(d[piv], 1e-30)
        row = row_fn(piv)  # (n,)
        # residual column: row - L[:, :j] @ L[piv, :j], mask cols >= j
        mask = (jnp.arange(rank) < j).astype(dt)
        corr = l @ (l[piv] * mask)
        col = (row - corr) / jnp.sqrt(dp)
        d_new = jnp.maximum(d - col * col, 0.0)
        d_new = d_new.at[piv].set(0.0)
        l = l.at[:, j].set(col)
        return (d_new, l), piv

    init = (diag, jnp.zeros((n, rank), dt))
    (d_final, l), pivots = jax.lax.scan(body, init, jnp.arange(rank))
    return PivotedCholesky(l=l, pivots=pivots, error=jnp.sum(d_final))


def woodbury_precond(l: Array, sigma2: Array) -> Callable[[Array], Array]:
    """Return ``v -> (L L^T + sigma^2 I)^{-1} v`` via the Woodbury identity."""
    rank = l.shape[1]
    inner = sigma2 * jnp.eye(rank, dtype=l.dtype) + l.T @ l
    chol = jnp.linalg.cholesky(inner)

    def apply(v: Array) -> Array:
        lt_v = l.T @ v  # (rank, k)
        sol = jax.scipy.linalg.cho_solve((chol, True), lt_v)
        return (v - l @ sol) / sigma2

    return apply


def precond_logdet(l: Array, sigma2: Array, n: int) -> Array:
    """log|L L^T + sigma^2 I| (matrix determinant lemma)."""
    rank = l.shape[1]
    inner = jnp.eye(rank, dtype=l.dtype) + (l.T @ l) / sigma2
    sign, ld = jnp.linalg.slogdet(inner)
    return ld + n * jnp.log(sigma2)
