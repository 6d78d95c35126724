"""GP model definitions: hyperparameters + the Simplex-GP operator factory.

``GPParams`` holds raw (unconstrained) hyperparameters; softplus transforms
keep lengthscale/outputscale/noise positive, with the paper's minimum-noise
floor (Appendix A: {1e-4, 1e-1}). ``SimplexGP.operator`` builds the lattice
ONCE per hyperparameter setting and returns the K_hat MVM closure used by
all CG/Lanczos iterations of that step — the paper's amortization — and
that same ``Lattice`` is shared with the surrogate ``quad_form`` calls via
``lat=`` (DESIGN.md §9), so a whole training step costs ONE build. Both
``operator`` and ``quad_form`` also take prebuilt/right-sized lattices from
outside jit (``lat=``/``cap=``) and an eager-mode ``LatticeCache``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import filtering, kernels_math as km
from repro.core.lattice import Lattice, build_lattice, default_capacity
from repro.core.stencil import Stencil, make_stencil

Array = jax.Array


def softplus(x: Array) -> Array:
    return jax.nn.softplus(x)


def inv_softplus(y) -> Array:
    y = jnp.asarray(y, jnp.float32)
    return y + jnp.log(-jnp.expm1(-y))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GPParams:
    raw_lengthscale: Array  # (d,) ARD
    raw_outputscale: Array  # ()
    raw_noise: Array  # ()

    @staticmethod
    def init(d: int, *, lengthscale: float = 1.0, outputscale: float = 1.0,
             noise: float = 0.1) -> "GPParams":
        return GPParams(
            raw_lengthscale=jnp.full((d,), inv_softplus(lengthscale)),
            raw_outputscale=inv_softplus(outputscale),
            raw_noise=inv_softplus(noise),
        )


@dataclasses.dataclass(frozen=True)
class SimplexGPConfig:
    """Static configuration (Appendix A defaults)."""

    kernel: str = "matern32"  # {rbf, matern12, matern32, matern52}
    order: int = 1  # blur stencil order r
    min_noise: float = 1e-4
    symmetrize: bool = True
    cap_factor: float = 1.0  # capacity = cap_factor * n * (d+1)
    cg_tol_train: float = 1.0
    cg_tol_eval: float = 1e-2
    max_cg_iters: int = 100
    # lattice-MVM backend tier (kernels/blur/ops.py policy; DESIGN.md §8):
    # "auto" picks the fused single-jit XLA path on every host (the Pallas
    # tiers do not compile for the TPU yet).
    backend: str = "auto"
    # lattice BUILD path (kernels/hash/ops.py policy; DESIGN.md §11):
    # "auto" resolves to the open-addressing hash build (hash_xla on every
    # host); "sort" keeps the original lexicographic-sort build as the
    # bit-exact oracle.
    build_backend: str = "auto"
    precond_rank: int = 0  # 0 = no preconditioner (lattice MVMs are cheap)
    num_probes: int = 8
    max_lanczos_iters: int = 50
    # "paper": §4.2 derivative-stencil custom VJP (faithful reproduction).
    # "autodiff": differentiate through the barycentric weights of the
    #   actual lattice operator (beyond-paper; self-consistent with the
    #   approximate model the solves come from — see DESIGN.md §7).
    grad_mode: str = "paper"
    # One lattice build per training step / posterior (DESIGN.md §9): the
    # solve operator, the surrogate quad forms, and the prediction cross-
    # MVMs all share a single Lattice. False restores the seed's
    # rebuild-per-call behavior (the benchmark baseline). Note "autodiff"
    # grad mode must rebuild inside the differentiated quad form regardless
    # (its gradient flows through the barycentric construction itself).
    shared_lattice: bool = True
    # log-det estimator for the MLL value: "cg" reuses the Lanczos
    # tridiagonals mBCG already collected during the probe solves (BBMM's
    # free log-det; zero extra MVMs), "slq" runs the separate Lanczos pass.
    # Preconditioned runs fall back to "slq" (the CG tridiagonals then
    # describe the preconditioned operator, not K_hat).
    logdet_estimator: str = "cg"
    # frozen-lattice serving (gp/serve.py; DESIGN.md §12): the query-path
    # backend (kernels/slice/ops.py policy — "auto" resolves to the XLA
    # lookup + slice on every host) and the fixed padding-bucket sizes jit
    # compiles per (not per batch shape).
    serve_backend: str = "auto"
    serve_buckets: tuple[int, ...] = (64, 256, 1024, 4096)


class Operator(NamedTuple):
    """K_hat = outputscale * F(z) + noise * I as closures over one lattice."""

    mvm: Callable[[Array], Array]  # (n, k) -> (n, k), full K_hat
    kxx_mvm: Callable[[Array], Array]  # kernel part only (no noise)
    lattice: Lattice
    noise: Array
    outputscale: Array
    lengthscale: Array


@dataclasses.dataclass(frozen=True)
class SimplexGP:
    config: SimplexGPConfig

    @property
    def stencil(self) -> Stencil:
        return make_stencil(self.config.kernel, self.config.order)

    @property
    def profile(self) -> km.KernelProfile:
        return km.get_profile(self.config.kernel)

    def constrained(self, params: GPParams):
        ls = softplus(params.raw_lengthscale)
        os_ = softplus(params.raw_outputscale)
        noise = softplus(params.raw_noise) + self.config.min_noise
        return ls, os_, noise

    def capacity(self, n: int, d: int) -> int:
        return int(self.config.cap_factor * default_capacity(n, d))

    def operator(self, params: GPParams, x: Array, *,
                 lat: Lattice | None = None, cap: int | None = None,
                 cache: "filtering.LatticeCache | None" = None,
                 mesh=None, axis_name: str = "data") -> Operator:
        """Build lattice once; return the K_hat MVM for CG loops.

        The MVM obeys the multi-RHS block contract: (n, k) in, (n, k)
        out, one lattice filtering per call — mBCG's ``[y | Z]`` block
        and LOVE's Krylov starts all ride a single MVM per iteration.

        NOT differentiable (stop-gradient semantics by construction —
        params enter only through concrete values). Use ``quad_form``
        for gradient paths.

        ``lat`` skips the build entirely (a prebuilt lattice for these
        ``x`` under these params — e.g. an auto-sized one constructed
        outside jit, or a shared joint lattice). ``cap`` overrides the
        worst-case ``default_capacity`` table size, so jit-side code can
        inherit a right-sized cap chosen outside jit (build_lattice_auto).
        ``cache`` memoizes eager-mode builds across calls. ``mesh`` runs
        every MVM data-parallel over its ``axis_name`` axis (DESIGN.md
        §10: sharded splat/slice, replicated blur, one psum per MVM).
        """
        cfg = self.config
        st = self.stencil
        ls, os_, noise = self.constrained(params)
        z = x / ls[None, :]
        if lat is None:
            cap = self.capacity(*x.shape) if cap is None else cap
            if cache is not None:
                lat = cache.get(cache.point_set_tag(x), z,
                                spacing=st.spacing, r=st.r, cap=cap, ls=ls,
                                build_backend=cfg.build_backend, mesh=mesh)
            else:
                lat = build_lattice(z, spacing=st.spacing, r=st.r, cap=cap,
                                    backend=cfg.build_backend)
        w = jnp.asarray(st.weights, x.dtype)
        taps = tuple(st.weights)

        def kxx(v: Array) -> Array:
            return os_ * filtering.filter_mvm(lat, v, w,
                                              symmetrize=cfg.symmetrize,
                                              backend=cfg.backend,
                                              taps=taps, mesh=mesh,
                                              axis_name=axis_name)

        def mvm(v: Array) -> Array:
            return kxx(v) + noise * v

        return Operator(mvm=mvm, kxx_mvm=kxx, lattice=lat, noise=noise,
                        outputscale=os_, lengthscale=ls)

    def quad_form(self, params: GPParams, x: Array, a: Array,
                  b: Array, *, lat: Lattice | None = None) -> Array:
        """Differentiable ``sum(a * (K_hat(theta) b))`` (for MLL surrogates).

        Uses ``lattice_filter``'s §4.2 custom VJP, so gradients w.r.t.
        lengthscale flow through z = x / ls without differentiating the
        integer lattice construction. Passing ``lat`` (a lattice already
        built for these x under numerically identical params — e.g.
        ``operator(...).lattice``) skips the per-call rebuild via
        ``lattice_filter_with``; values and §4.2 gradients are identical.
        Only honored in "paper" grad mode — "autodiff" differentiates
        through the barycentric weights of the build itself, so it must
        construct the lattice inside the traced computation.
        """
        cfg = self.config
        st = self.stencil
        ls, os_, noise = self.constrained(params)
        z = x / ls[None, :]
        w = jnp.asarray(st.weights, x.dtype)
        if cfg.grad_mode == "paper":
            dw = jnp.asarray(st.dweights, x.dtype)
            cap = lat.cap if lat is not None else self.capacity(*x.shape)
            spec = filtering.spec_for(st, cap=cap,
                                      symmetrize=cfg.symmetrize,
                                      backend=cfg.backend,
                                      build_backend=cfg.build_backend)
            if lat is not None:
                kb = os_ * filtering.lattice_filter_with(lat, z, b, w, dw,
                                                         spec)
            else:
                kb = os_ * filtering.lattice_filter(z, b, w, dw, spec)
        else:  # autodiff through the barycentric interpolation (a.e. exact)
            lat = build_lattice(z, spacing=st.spacing, r=st.r,
                                cap=self.capacity(*x.shape),
                                backend=cfg.build_backend)
            # Pallas kernels have no VJP; keep autodiff on the fused XLA
            # tier even when the config would pick a Pallas backend.
            bk = cfg.backend if cfg.backend in ("fused_xla", "xla") \
                else "fused_xla"
            kb = os_ * filtering.filter_mvm(lat, b, w,
                                            symmetrize=cfg.symmetrize,
                                            backend=bk,
                                            taps=tuple(st.weights))
        return jnp.sum(a * kb) + noise * jnp.sum(a * b)

    def exact_row(self, params: GPParams, x: Array, i: Array) -> Array:
        """Exact kernel row K_hat[i, :] (for the pivoted-Cholesky precond)."""
        ls, os_, noise = self.constrained(params)
        row = km.gram(self.profile, x[i][None, :], x, ls, os_)[0]
        return row.at[i].add(noise)
