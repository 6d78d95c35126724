"""BBMM marginal log-likelihood: value + unbiased stochastic gradients.

Paper Eq. 4 with the Gardner et al. (2018a) estimator:

  value:  -1/2 y^T u - 1/2 logdet(K_hat) - n/2 log 2pi,
          u = K_hat^{-1} y via CG (Appendix A tolerances),
          logdet via SLQ on the Lanczos tridiagonals mBCG already collected
          during the probe solves (BBMM's "log-det for free"; the separate
          Lanczos pass survives as ``logdet_estimator="slq"`` and for
          preconditioned runs, where the CG tridiagonals describe the
          preconditioned operator rather than K_hat).

  grads:  dMLL/dtheta = 1/2 u^T (dK/dtheta) u - 1/2 E_z[w^T (dK/dtheta) z],
          w = K_hat^{-1} z, z Rademacher probes — realized by differentiating
          the *surrogate* S = 1/2 u^T K(theta) u - 1/(2p) sum_i w_i^T K(theta) z_i
          with u, w, z treated as constants. K(theta) applications go through
          the §4.2 custom VJP, so every gradient is itself a lattice
          filtering call — the paper's headline trick.

One lattice build per step (DESIGN.md §9): the operator built for the
solves is threaded into the surrogate ``quad_form`` via
``lattice_filter_with``, so the whole step — solves, log-det, and all
gradients — runs on a single build (down from 3+ in the seed). The
data-fit and trace surrogate terms are batched into ONE (1+p)-column
quad form (quad_form is bilinear), so the step's gradient costs a single
batched filtering + its single batched §4.2 backward filtering. Set
``SimplexGPConfig.shared_lattice=False`` for the seed's rebuild-per-call
behavior (the benchmark baseline). Optional RR-CG (Table 4) replaces the
y-solve with the unbiased randomized-truncation estimator.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.filtering import LatticeCache
from repro.gp.models import GPParams, SimplexGP
from repro.solvers.cg import cg as cg_solve
from repro.solvers.lanczos import slq_logdet, slq_logdet_from_cg
from repro.solvers.pivoted_cholesky import pivoted_cholesky, woodbury_precond
from repro.solvers.rrcg import rrcg as rrcg_solve

Array = jax.Array


class MLLResult(NamedTuple):
    mll: Array  # () the MLL value (per Eq. 4, up to reported constant)
    grads: GPParams  # d(-MLL)/d(raw params) — ready for a minimizer
    cg_iters: Array  # () iterations the solve used
    cg_residual: Array  # () final relative residual of the y-solve
    overflow: Array  # () bool: lattice table overflowed (grow cap, retry)
    pack_overflow: Array  # () bool: coord range overflow — growth can't fix


def _solve_block(model: SimplexGP, params: GPParams, x: Array, y: Array,
                 probes: Array, *, tol: float, rr_key: Array | None,
                 cap: int | None, cache: LatticeCache | None, mesh=None):
    """u = K^{-1} y and W = K^{-1} Z with one operator build.

    The whole ``[y | Z]`` block goes through ONE mBCG run whose matvec is
    a single (n, 1+p)-channel lattice MVM per iteration — the multi-RHS
    operator contract (never one MVM per probe).
    """
    cfg = model.config
    op = model.operator(params, x, cap=cap, cache=cache, mesh=mesh)

    precond = None
    if cfg.precond_rank > 0:
        diag = op.outputscale + op.noise
        row_fn = lambda i: model.exact_row(params, x, i)
        pc = pivoted_cholesky(row_fn, jnp.full(x.shape[0], diag,
                                                      x.dtype),
                                     cfg.precond_rank)
        precond = woodbury_precond(pc.l, op.noise)

    b = jnp.concatenate([y[:, None], probes], axis=1)
    solves, info = cg_solve(op.mvm, b, precond=precond, tol=tol,
                             max_iters=cfg.max_cg_iters)
    if rr_key is not None:
        rr = rrcg_solve(op.mvm, y[:, None], key=rr_key,
                           precond=precond,
                           min_iters=max(cfg.max_cg_iters // 4, 10),
                           max_iters=cfg.max_cg_iters)
        solves = solves.at[:, 0].set(rr.x[:, 0])
    return op, solves, info, precond


def mll_value_and_grad(model: SimplexGP, params: GPParams, x: Array,
                       y: Array, key: Array, *, tol: float | None = None,
                       use_rrcg: bool = False, cap: int | None = None,
                       cache: LatticeCache | None = None,
                       mesh=None) -> MLLResult:
    """One training-step MLL evaluation (value + surrogate gradients).

    ``cap`` overrides the worst-case lattice capacity (thread a right-sized
    cap chosen outside jit — see gp/train.py); ``cache`` memoizes
    eager-mode lattice builds across calls with unchanged hyperparameters.
    ``mesh`` shards every solve-phase MVM over its "data" axis (DESIGN.md
    §10; n must divide the axis size).
    """
    cfg = model.config
    n = x.shape[0]
    tol = cfg.cg_tol_train if tol is None else tol

    pk, lk, rk = jax.random.split(key, 3)
    probes = jax.random.rademacher(pk, (n, cfg.num_probes),
                                   dtype=x.dtype)

    sg_params = jax.tree.map(jax.lax.stop_gradient, params)
    op, solves, info, precond = _solve_block(
        model, sg_params, x, y, probes, tol=tol,
        rr_key=rk if use_rrcg else None, cap=cap, cache=cache, mesh=mesh)
    u = jax.lax.stop_gradient(solves[:, 0])
    w = jax.lax.stop_gradient(solves[:, 1:])

    # ---- value ------------------------------------------------------------
    # The probe columns of the mBCG run ARE Lanczos processes on K_hat
    # started at z_i/||z_i||, so their tridiagonals give the SLQ log-det with
    # zero extra MVMs. (With a preconditioner they tridiagonalize P^{-1}K
    # instead — fall back to the separate pass.)
    if cfg.logdet_estimator == "cg" and precond is None:
        probe_norms2 = jnp.full((cfg.num_probes,), float(n), x.dtype)
        logdet = slq_logdet_from_cg(info.alphas[:, 1:], info.betas[:, 1:],
                                    info.valid[:, 1:], probe_norms2)
    else:
        logdet = slq_logdet(op.mvm, n, key=lk,
                            num_probes=cfg.num_probes,
                            num_iters=cfg.max_lanczos_iters,
                            dtype=x.dtype)
    mll = (-0.5 * jnp.dot(y, u) - 0.5 * logdet
           - 0.5 * n * math.log(2.0 * math.pi))

    # ---- gradients via the surrogate --------------------------------------
    # Shared-lattice path: the surrogate quad form filters on the operator's
    # lattice (numerically identical params — sg_params is a stop_gradient
    # of the same values), so the step performs exactly one build.
    #
    # Multi-RHS: quad_form is bilinear, so the data-fit and trace terms
    # batch into ONE (1+p)-column call — the §4.2 backward then also runs
    # as a single batched filtering instead of one per term:
    #   S = 1/2 u^T K u - 1/(2p) sum_i w_i^T K z_i = sum(A * K_hat B),
    #   A = [1/2 u | -1/(2p) W],  B = [u | Z].
    shared = (op.lattice if cfg.shared_lattice and cfg.grad_mode == "paper"
              else None)
    a_blk = jnp.concatenate([0.5 * u[:, None],
                             (-0.5 / cfg.num_probes) * w], axis=1)
    b_blk = jnp.concatenate([u[:, None], probes], axis=1)

    def neg_surrogate(p: GPParams) -> Array:
        return -model.quad_form(p, x, a_blk, b_blk, lat=shared)

    with obs.scope("grad"):
        grads = jax.grad(neg_surrogate)(params)
    return MLLResult(mll=mll, grads=grads, cg_iters=info.iterations,
                     cg_residual=info.residual_norms[0],
                     overflow=op.lattice.overflow,
                     pack_overflow=op.lattice.pack_overflow)
