"""Training-step / posterior cost: shared-lattice pipeline vs the seed path.

PR 1 fused the per-iteration MVM, which left the lattice *build* as the
dominant per-step cost: the seed pipeline builds the SAME lattice 3x per
training step (operator + two surrogate quad forms) and 3x per posterior
(operator + two cross-MVM joint builds). The shared-lattice pipeline
(DESIGN.md §9) performs exactly ONE build each and reuses the mBCG
tridiagonals for the log-det instead of a separate Lanczos pass.

This benchmark races both pipelines on the same data — the "legacy" config
(``shared_lattice=False, logdet_estimator="slq"``) IS the pre-change
measurement, recorded in the same artifact — and reports:

  * builds/step and builds/posterior (counted at trace level by the
    ``lattice.builds`` counter of ``repro.obs``: each traced build is one
    construction in the compiled program);
  * median step / posterior wall seconds;
  * MLL value under the CG-reused log-det vs the separate-SLQ one, as
    multi-seed means/stds at a converged CG tolerance — both are stochastic
    trace estimators over different probe draws, so the check is that the
    means agree within the probe-sampling noise (|z| modest), not that any
    single seed matches. (At the paper's train tolerance 1.0 the CG
    tridiagonals stop at the 10-iteration floor, which adds truncation bias
    — the standard GPyTorch/BBMM trade-off; the grads are unaffected, and
    model selection runs on validation RMSE per §5.4.);
  * n / d / m / cap so table growth is visible across PRs.

Results land in BENCH_train.json.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SCALE, emit, timeit, write_json
from repro import obs
from repro.core.lattice import build_lattice_auto
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig,
                      mll_value_and_grad, posterior)

SIZES = [1000, 4000]
D = 8
NS_FRACTION = 0.2  # test set size relative to n
NUM_PROBES = 8
MAX_CG = 40
MAX_LANCZOS = 40
VARIANCE_RANK = 15


def _configs():
    kw = dict(kernel="matern32", max_cg_iters=MAX_CG, num_probes=NUM_PROBES,
              max_lanczos_iters=MAX_LANCZOS, backend="auto")
    return {
        "legacy": SimplexGP(SimplexGPConfig(shared_lattice=False,
                                            logdet_estimator="slq", **kw)),
        "shared": SimplexGP(SimplexGPConfig(shared_lattice=True,
                                            logdet_estimator="cg", **kw)),
    }


def _measure(model, params, x, y, xs, *, step_cap, post_cap, key):
    """(builds/step, step_s, mll, builds/posterior, posterior_s)."""
    step = jax.jit(lambda p, k: mll_value_and_grad(model, p, x, y, k,
                                                   cap=step_cap))
    c0 = obs.counter("lattice.builds")
    res = jax.block_until_ready(step(params, key))  # trace + compile
    builds_step = obs.counter("lattice.builds") - c0
    step_s = timeit(step, params, key)
    mll = float(res.mll)

    post = jax.jit(lambda p, k: posterior(model, p, x, y, xs, key=k,
                                          variance_rank=VARIANCE_RANK,
                                          cap=post_cap))
    c0 = obs.counter("lattice.builds")
    jax.block_until_ready(post(params, key).mean)
    builds_post = obs.counter("lattice.builds") - c0
    post_s = timeit(post, params, key)
    return builds_step, step_s, mll, builds_post, post_s


def _mll_agreement(models, params, x, y, *, seeds: int = 6,
                   tol: float = 1e-4, depth: int = 100) -> dict:
    """Multi-seed means of both MLL estimators at matched converged depth.

    The timed configs truncate Krylov depth differently (CG stops at the
    training tolerance, SLQ at max_lanczos_iters), which would mix
    truncation bias into the comparison — so the agreement check re-runs
    both with ``depth`` iterations available and a tight tolerance, leaving
    probe sampling as the only difference. ``z_score`` = |mean_cg -
    mean_slq| / pooled std-error; both estimators are unbiased trace
    estimates over independent probe draws, so modest |z| means agreement
    within stochastic-estimator noise.
    """
    deep = {name: SimplexGP(dataclasses.replace(
        model.config, max_cg_iters=depth, max_lanczos_iters=depth))
        for name, model in models.items()}
    vals = {name: [] for name in deep}
    for name, model in deep.items():
        for s in range(seeds):
            res = mll_value_and_grad(model, params, x, y,
                                     jax.random.PRNGKey(s), tol=tol)
            vals[name].append(float(res.mll))
    mean = {k: float(np.mean(v)) for k, v in vals.items()}
    std = {k: float(np.std(v)) for k, v in vals.items()}
    pooled_se = max(np.sqrt(sum(s ** 2 for s in std.values()) / seeds),
                    1e-9)
    # A residual |z| ~ 2 at larger n is the known f32 effect: CG runs
    # without reorthogonalization, so its recovered tridiagonals develop
    # ghost eigenvalues at depth, slightly biasing the quadrature relative
    # to the fully reorthogonalized Lanczos — the standard BBMM trade-off.
    # rel_diff is the honest scale of that effect on the MLL itself.
    return {"mll_mean": {k: round(v, 3) for k, v in mean.items()},
            "mll_std": {k: round(v, 3) for k, v in std.items()},
            "seeds": seeds, "cg_tol": tol,
            "rel_diff": round(abs(mean["shared"] - mean["legacy"])
                              / max(abs(mean["legacy"]), 1.0), 4),
            "z_score": round(abs(mean["shared"] - mean["legacy"])
                             / pooled_se, 3)}


def main():
    rng = np.random.default_rng(0)
    models = _configs()
    rows = []
    for n in [int(s * SCALE) for s in SIZES]:
        ns = max(int(n * NS_FRACTION), 10)
        x = jnp.asarray(rng.normal(size=(n, D)) * 0.3, jnp.float32)
        y = jnp.asarray(np.sin(2 * np.asarray(x[:, 0]))
                        + 0.1 * rng.normal(size=n), jnp.float32)
        xs = jnp.asarray(rng.normal(size=(ns, D)) * 0.3, jnp.float32)
        params = GPParams.init(D)
        key = jax.random.PRNGKey(0)

        # right-size static caps outside jit (the fast-build entry): the
        # legacy config keeps the seed's worst-case default (cap=None)
        st = models["shared"].stencil
        ls = models["shared"].constrained(params)[0]
        lat0 = build_lattice_auto(x / ls[None, :], spacing=st.spacing,
                                  r=st.r)
        latj = build_lattice_auto(jnp.concatenate([x, xs]) / ls[None, :],
                                  spacing=st.spacing, r=st.r)
        m = int(lat0.m)
        caps = {"legacy": (None, None),
                "shared": (lat0.cap, latj.cap)}

        row = {"n": n, "ns": ns, "d": D, "m": m,
               "cap_shared": lat0.cap,
               "cap_worst": n * (D + 1)}
        for name, model in models.items():
            step_cap, post_cap = caps[name]
            bs, ss, mll, bp, ps = _measure(model, params, x, y, xs,
                                           step_cap=step_cap,
                                           post_cap=post_cap, key=key)
            row[name] = {"builds_per_step": bs, "step_s": round(ss, 4),
                         "mll": mll, "builds_per_posterior": bp,
                         "posterior_s": round(ps, 4)}
        row["step_speedup"] = round(row["legacy"]["step_s"]
                                    / row["shared"]["step_s"], 2)
        row["posterior_speedup"] = round(row["legacy"]["posterior_s"]
                                         / row["shared"]["posterior_s"], 2)
        row["mll_agreement"] = _mll_agreement(models, params, x, y)
        emit(f"fig_train/n{n}", row["shared"]["step_s"],
             f"legacy_step_s={row['legacy']['step_s']:.3f} "
             f"shared_step_s={row['shared']['step_s']:.3f} "
             f"step_speedup={row['step_speedup']}x "
             f"builds {row['legacy']['builds_per_step']}->"
             f"{row['shared']['builds_per_step']}/step "
             f"{row['legacy']['builds_per_posterior']}->"
             f"{row['shared']['builds_per_posterior']}/posterior "
             f"posterior_speedup={row['posterior_speedup']}x "
             f"mll_rel_diff={row['mll_agreement']['rel_diff']} "
             f"mll_z={row['mll_agreement']['z_score']}")
        rows.append(row)
    write_json("BENCH_train.json", {"figure": "fig_train_step",
                                    "kernel": "matern32", "sizes": rows})


if __name__ == "__main__":
    main()
