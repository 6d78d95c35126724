"""Posterior prediction for Simplex-GP (paper Eqs. 2-3, MVM-based).

Mean: mu_* = K_{*,X} u with u = K_hat^{-1} y (CG at eval tolerance 1e-2).
K_{*,X} u is ONE lattice filtering over the joint point set [X; X_*] with
the training rows carrying u and test rows carrying 0 — cross-covariance
times a vector is just another bilateral filter (paper §3.1).

Variance: LOVE-style low-rank approximation. Run k Lanczos iterations on
K_hat from a y-seeded start to get K_hat^{-1} ~= Q T^{-1} Q^T on the Krylov
subspace; then var_* ~= k_*(0) - (K_{*,X} Q) T^{-1} (K_{*,X} Q)^T. This
mirrors GPyTorch's fast predictive variances the paper evaluates NLL with;
accuracy grows with k.

One lattice build per posterior (DESIGN.md §9): the joint lattice over
[X; X_*] serves BOTH the K_hat MVMs of the solve/Lanczos phases (restrict
the joint filtering to the training rows) and the cross-covariance rows,
and ``u`` and the LOVE basis ``Q`` are batched into a single (1+k)-channel
cross filtering. The seed built three lattices per posterior (train
operator + one per cross_mvm call); ``shared_lattice=False`` restores that
as the benchmark baseline. Restricting the joint filtering to train rows is
a slightly *denser* K_XX approximation than the train-only lattice (extra
lattice points from X_* refine the blur graph) and keeps the solve
consistent with the cross-covariance — both use the same W K_UU W^T.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import filtering
from repro.core.filtering import LatticeCache
from repro.core.lattice import Lattice, build_lattice
from repro.gp.models import GPParams, SimplexGP
from repro.solvers.cg import cg as cg_solve
from repro.solvers.lanczos import lanczos as lanczos_run

Array = jax.Array


class Posterior(NamedTuple):
    mean: Array  # (n*,)
    var: Array  # (n*,) latent-f variance (add noise for predictive y)
    overflow: Array | bool = False  # lattice table overflow flag
    pack_overflow: Array | bool = False  # coord range overflow (can't grow)
    cg_iters: Array | int = 0  # iterations the mean solve used


def _joint_lattice(model: SimplexGP, params: GPParams, x: Array, xs: Array,
                   *, cap: int | None,
                   cache: LatticeCache | None, mesh=None) -> Lattice:
    """Build (or fetch) the one lattice over the joint point set [x; xs]."""
    st = model.stencil
    ls, _, _ = model.constrained(params)
    zj = jnp.concatenate([x, xs], axis=0) / ls[None, :]
    n, ns = x.shape[0], xs.shape[0]
    cap = model.capacity(n + ns, x.shape[1]) if cap is None else cap
    if cache is not None:
        return cache.get(cache.point_set_tag(x, xs), zj,
                         spacing=st.spacing, r=st.r, cap=cap, ls=ls,
                         build_backend=model.config.build_backend, mesh=mesh)
    return build_lattice(zj, spacing=st.spacing, r=st.r, cap=cap,
                         backend=model.config.build_backend)


def _joint_filter(model: SimplexGP, lat: Lattice, v: Array,
                  dtype, mesh=None) -> Array:
    """One filtering of (n+ns, c) values on the joint lattice (no scales)."""
    cfg = model.config
    st = model.stencil
    w = jnp.asarray(st.weights, dtype)
    return filtering.filter_mvm(lat, v, w, symmetrize=cfg.symmetrize,
                                backend=cfg.backend, taps=tuple(st.weights),
                                mesh=mesh)


def cross_mvm(model: SimplexGP, params: GPParams, x: Array, xs: Array,
              v: Array, *, lat: Lattice | None = None,
              cache: LatticeCache | None = None, mesh=None) -> Array:
    """K_{*,X} v via one joint-lattice filtering. v: (n, c) -> (n*, c).

    Multi-RHS by construction: a (n, c) block of cross-covariance RHS
    costs the same single filtering as one column. ``lat`` reuses a
    prebuilt joint lattice over [x; xs] (e.g. the one ``posterior``
    shares across its solve and cross-MVMs); ``mesh`` shards the joint
    filtering data-parallel (n + n* must divide the "data" axis).
    """
    _, os_, _ = model.constrained(params)
    n, ns = x.shape[0], xs.shape[0]
    if lat is None:
        lat = _joint_lattice(model, params, x, xs, cap=None, cache=cache,
                             mesh=mesh)
    with obs.scope("posterior.cross"):
        vj = jnp.concatenate([v, jnp.zeros((ns, v.shape[1]), v.dtype)],
                             axis=0)
        out = _joint_filter(model, lat, vj, x.dtype, mesh=mesh)
        return os_ * out[n:]


def _love_tridiag(lres) -> Array:
    """The dense (k, k) Lanczos tridiagonal T of LOVE's first column."""
    valid = lres.valid[:, 0]
    off = lres.betas[:-1, 0] * valid[:-1] * valid[1:]
    return (jnp.diag(jnp.where(valid, lres.alphas[:, 0], 1.0))
            + jnp.diag(off, 1) + jnp.diag(off, -1))


@obs.scope("posterior.var")
def _love_var(tdense: Array, ksq: Array, prior_var: Array) -> Array:
    """var_* = k(0) - (K_*X Q) T^{-1} (K_*X Q)^T, clipped to [1e-6, k(0)]
    (k(0) = outputscale for unit profiles); ``ksq`` is (n*, k)."""
    sol = jnp.linalg.solve(
        tdense + 1e-6 * jnp.eye(tdense.shape[0], dtype=tdense.dtype),
        ksq.T)  # (k, n*)
    var = prior_var - jnp.sum(ksq * sol.T, axis=1)
    return jnp.clip(var, 1e-6, prior_var)


def posterior(model: SimplexGP, params: GPParams, x: Array, y: Array,
              xs: Array, *, key: Array, variance_rank: int = 30,
              cap: int | None = None,
              cache: LatticeCache | None = None, mesh=None) -> Posterior:
    """Predictive mean and LOVE variance at ``xs``.

    ``cap`` overrides the joint lattice's worst-case capacity (thread a
    right-sized one chosen outside jit); ``cache`` memoizes eager builds.
    ``mesh`` shards every joint-lattice filtering — the solve MVMs, the
    LOVE Lanczos MVMs, and the batched [u | Q] cross filtering — over its
    "data" axis, one psum each (DESIGN.md §10).
    """
    cfg = model.config
    n, ns = x.shape[0], xs.shape[0]
    if not cfg.shared_lattice:
        return _posterior_rebuild(model, params, x, y, xs, key=key,
                                  variance_rank=variance_rank)

    ls, os_, noise = model.constrained(params)
    lat = _joint_lattice(model, params, x, xs, cap=cap, cache=cache,
                         mesh=mesh)

    # K_hat MVM on the training block, through the shared joint lattice.
    def mvm(v: Array) -> Array:
        vj = jnp.concatenate([v, jnp.zeros((ns, v.shape[1]), v.dtype)],
                             axis=0)
        return (os_ * _joint_filter(model, lat, vj, x.dtype, mesh=mesh)[:n]
                + noise * v)

    # mean solve
    u, info = cg_solve(mvm, y[:, None], tol=cfg.cg_tol_eval,
                       max_iters=cfg.max_cg_iters)

    # variance via Lanczos on K_hat (LOVE-style)
    q0 = y[:, None] + 1e-3 * jax.random.normal(key, (n, 1), x.dtype)
    lres = lanczos_run(mvm, q0, variance_rank)
    q = lres.q[:, :, 0].T  # (n, k)
    tdense = _love_tridiag(lres)

    # ONE batched cross filtering for [u | Q]: (1 + k) channels at once.
    ksall = cross_mvm(model, params, x, xs, jnp.concatenate([u, q], axis=1),
                      lat=lat, mesh=mesh)
    var = _love_var(tdense, ksall[:, 1:], os_)
    return Posterior(mean=ksall[:, 0], var=var, overflow=lat.overflow,
                     pack_overflow=lat.pack_overflow,
                     cg_iters=info.iterations)


def _posterior_rebuild(model: SimplexGP, params: GPParams, x: Array,
                       y: Array, xs: Array, *, key: Array,
                       variance_rank: int) -> Posterior:
    """Seed-compatible path: train-lattice operator + per-call joint builds
    (3 lattice constructions per posterior). Kept as the benchmark baseline
    and for A/B parity checks against the shared-lattice path."""
    cfg = model.config
    op = model.operator(params, x)

    u, info = cg_solve(op.mvm, y[:, None], tol=cfg.cg_tol_eval,
                       max_iters=cfg.max_cg_iters)
    mean = cross_mvm(model, params, x, xs, u)[:, 0]

    q0 = y[:, None] + 1e-3 * jax.random.normal(key, (x.shape[0], 1), x.dtype)
    lres = lanczos_run(op.mvm, q0, variance_rank)
    ksq = cross_mvm(model, params, x, xs, lres.q[:, :, 0].T)  # (n*, k)
    return Posterior(mean=mean,
                     var=_love_var(_love_tridiag(lres), ksq, op.outputscale),
                     overflow=op.lattice.overflow,
                     pack_overflow=op.lattice.pack_overflow,
                     cg_iters=info.iterations)


def exact_mean_grad(profile, x: Array, y: Array, xs: Array, *,
                    lengthscale, outputscale, noise) -> Array:
    """Analytic d(mean)/dx* of the DENSE exact GP — the gradient oracle.

    The closed form the frozen serving gradients (gp/serve.predict_grad,
    DESIGN.md §15) are validated against on in-model draws:

      d mu(x*)/dx* = os * sum_i alpha_i k'(tau_i) * 2 (x* - x_i) / ls^2

    with ``k' = profile.dk_dsq`` (dk/d tau^2, the same derivative profile
    the paper's Eq. 11 hyperparameter gradients use — core/kernels_math)
    and ``alpha = (K + noise I)^{-1} y`` from the same jittered system
    ``core/exact.ExactGP`` solves. O(n* n d): test/benchmark-scale only.
    """
    from repro.core import kernels_math as km
    d = x.shape[1]
    ls = jnp.broadcast_to(jnp.asarray(lengthscale, x.dtype), (d,))
    khat = km.gram(profile, x, x, ls, outputscale) \
        + (noise + 1e-6) * jnp.eye(x.shape[0], dtype=x.dtype)
    alpha = jnp.linalg.solve(khat, y)
    zs, z = xs / ls[None, :], x / ls[None, :]
    tau = jnp.sqrt(km.pairwise_sqdist(zs, z) + 1e-30)  # (n*, n)
    kp = outputscale * profile.dk_dsq(tau)  # dk/d(tau^2) per pair
    dsq = 2.0 * (zs[:, None, :] - z[None, :, :]) / ls[None, None, :]
    return jnp.einsum("sn,n,snd->sd", kp, alpha, dsq)


def nll(post: Posterior, noise: Array, y_true: Array) -> Array:
    """Mean predictive negative log-likelihood (Table 2's NLL column)."""
    s2 = post.var + noise
    return jnp.mean(0.5 * jnp.log(2.0 * jnp.pi * s2)
                    + 0.5 * (y_true - post.mean) ** 2 / s2)


def rmse(post: Posterior, y_true: Array) -> Array:
    return jnp.sqrt(jnp.mean((post.mean - y_true) ** 2))
