"""PILCO-style MC rollout throughput + serving gradcheck (DESIGN.md §15).

The control-workload benchmark behind differentiable frozen serving:
freeze a k=2-output pendulum dynamics model once (``freeze_multi`` — one
lattice, stacked tables), then push a P-particle, H-step Monte-Carlo
rollout through it as one jitted ``lax.scan``. Measured columns:

  rollout_s         one full (P, H) forward rollout (all channels)
  evals_per_s       particle state evaluations per second, P*H/rollout_s
                    (>= 1e4 on one CPU is the acceptance floor; in
                    practice ~1e6)
  grad_rollout_s    value_and_grad of the expected rollout cost w.r.t.
                    policy params — the end-to-end policy gradient
                    through the ``slice_only`` custom JVP
  worst_miss        max per-step miss_mass over the rollout (validity)

plus two correctness columns the trend check ENFORCES:

  gradcheck         worst central-difference relative error of
                    ``predict_grad``'s d(mean, var)/dx* over d in
                    {2, 3, 5} at same-cell interior probe pairs (the
                    served surface is piecewise linear/quadratic, so the
                    in-cell secant is the derivative up to f32 roundoff;
                    <= 1e-4 is the acceptance band)
  grad_collectives  collective-primitive counts on the jaxpr of the
                    query-space gradient under the replicated-table mesh
                    — all zero by the DESIGN.md §15 contract, asserted
                    here so a committed artifact can never claim
                    otherwise.

Results land in BENCH_rollout.json; tier-1 runs ``measure_rollout`` and
``measure_gradcheck`` at tiny size via the ``bench_smoke`` marker.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SCALE, emit, timeit, write_json
from repro.core import lattice as L
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, freeze,
                      freeze_multi)
from repro.gp.serve import _predict_core, predict, predict_grad, predict_multi
from repro.sharding.simplex import collective_counts, data_mesh

TIGHT = SimplexGPConfig(kernel="matern32", cg_tol_eval=3e-7,
                        max_cg_iters=400)
DT = 0.1
FD_EPS = 2.5e-2


def _pendulum_data(n):
    """(state, action) -> next-state-delta pairs of a damped pendulum."""
    rng = np.random.default_rng(0)
    th = rng.uniform(-np.pi, np.pi, n)
    om = rng.uniform(-7, 7, n)
    a = rng.uniform(-2, 2, n)
    om2 = om + DT * (-9.8 * np.sin(th) - 0.2 * om + a)
    th2 = th + DT * om2
    x = jnp.asarray(np.stack([th, om, a], 1), jnp.float32)
    y = jnp.asarray(np.stack([th2 - th, om2 - om], 1), jnp.float32)
    return x, y


def measure_rollout(n: int, particles: int, horizon: int, *,
                    variance_rank: int = 16, iters: int = 3) -> dict:
    """Freeze the k=2 dynamics model and race the MC rollout through it."""
    x, y = _pendulum_data(n)
    model = SimplexGP(SimplexGPConfig(kernel="matern32"))
    # anisotropic lengthscales sized to the state box (examples/
    # rollout_pilco.py): dense-per-cell coverage, near-zero rollout miss
    params = GPParams.init(3, lengthscale=jnp.asarray([1.0, 2.0, 1.2]),
                           noise=1e-2)

    t0 = time.perf_counter()
    mp = freeze_multi(model, params, x, y, key=jax.random.PRNGKey(0),
                      variance_rank=variance_rank)
    jax.block_until_ready(mp.tables)
    freeze_s = time.perf_counter() - t0

    def rollout(w, key):
        s0 = jnp.zeros((particles, 2), jnp.float32).at[:, 0].set(2.5)
        eps = jax.random.normal(key, (horizon, particles, 2))

        def step(s, e):
            a = 2.0 * jnp.tanh(s @ w[:2] + w[2])
            # wrap the angle into the trained chart (round has zero
            # tangent, so d wrap/d th == 1 — examples/rollout_pilco.py)
            th = s[:, 0] - 2 * jnp.pi * jnp.round(s[:, 0] / (2 * jnp.pi))
            q = jnp.stack([th, s[:, 1], a], axis=1)
            res = predict_multi(mp, q)
            s2 = s + res.mean + 0.1 * jnp.sqrt(res.var) * e
            cost = jnp.mean(jnp.sum(s2 ** 2, axis=1))
            return s2, (cost, jnp.max(res.miss_mass))

        _, (costs, miss) = jax.lax.scan(step, s0, eps)
        return jnp.mean(costs), jnp.max(miss)

    w0 = jnp.zeros(3)
    key = jax.random.PRNGKey(1)
    fwd = jax.jit(rollout)
    rollout_s = timeit(fwd, w0, key, iters=iters)
    _, worst_miss = fwd(w0, key)

    grad_fn = jax.jit(jax.value_and_grad(rollout, has_aux=True))
    grad_rollout_s = timeit(grad_fn, w0, key, iters=iters)

    evals = particles * horizon
    return {
        "n": n, "d_in": 3, "k": int(mp.n_outputs),
        "particles": particles, "horizon": horizon,
        "variance_rank": variance_rank,
        "m": int(mp.index.m),
        "freeze_s": round(freeze_s, 3),
        "rollout_s": round(rollout_s, 5),
        "evals_per_s": round(evals / rollout_s, 0),
        "grad_rollout_s": round(grad_rollout_s, 5),
        "grad_evals_per_s": round(evals / grad_rollout_s, 0),
        "worst_miss": round(float(worst_miss), 4),
    }


def measure_gradcheck(dims=(2, 3, 5), n: int = 400, *,
                      variance_rank: int = 8) -> dict:
    """Worst FD relative error of predict_grad per dimension (the number
    the trend check enforces at 1e-4). Probe pairs that cross a simplex
    cell boundary are excluded — there the surface is kinked by design
    and the secant measures the kink, not the gradient."""
    out = {"eps": FD_EPS, "dims": {}}
    worst_all = 0.0
    for d in dims:
        rng = np.random.default_rng(d)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        y = (jnp.sin(2 * x[:, 0]) + 0.4 * x[:, 1] * x[:, d - 1])
        model = SimplexGP(TIGHT)
        params = GPParams.init(d, noise=0.3)
        pred = freeze(model, params, x, y, key=jax.random.PRNGKey(0),
                      variance_rank=variance_rank)
        xs = x[:64]
        g = predict_grad(pred, xs)
        sp = model.stencil.spacing
        worst = 0.0
        used = 0
        for j in range(d):
            e = jnp.zeros(d, xs.dtype).at[j].set(FD_EPS)
            xp, xm = xs + e, xs - e
            kp, _ = L.simplex_embed(xp / pred.lengthscale[None, :], sp)
            km = L.simplex_embed(xm / pred.lengthscale[None, :], sp)[0]
            keep = (np.asarray(jnp.all(kp == km, axis=(1, 2)))
                    & np.asarray(g.grad_ok))
            rp, rm = predict(pred, xp), predict(pred, xm)
            fdm = np.asarray((rp.mean - rm.mean) / (2 * FD_EPS))[keep]
            fdv = np.asarray((rp.var - rm.var) / (2 * FD_EPS))[keep]
            am = np.asarray(g.dmean[:, j])[keep]
            av = np.asarray(g.dvar[:, j])[keep]
            rel_m = np.abs(fdm - am) / np.maximum(np.abs(am), 1.0)
            rel_v = np.abs(fdv - av) / np.maximum(np.abs(av), 1.0)
            if keep.sum():
                worst = max(worst, float(rel_m.max()), float(rel_v.max()))
            used += int(keep.sum())
        out["dims"][str(d)] = {"worst_rel_err": worst, "pairs": used}
        worst_all = max(worst_all, worst)
    out["max_rel_err"] = worst_all
    return out


def measure_grad_collectives(n: int = 300, *, variance_rank: int = 6) -> dict:
    """Collective counts on the query-gradient jaxpr under the
    replicated-table mesh — asserted all-zero before the artifact is
    written (DESIGN.md §15 zero-collective gradient contract)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    x, y = _pendulum_data(n)
    model = SimplexGP(SimplexGPConfig(kernel="matern32"))
    params = GPParams.init(3, lengthscale=jnp.asarray([1.0, 2.0, 1.2]),
                           noise=1e-2)
    pred = freeze(model, params, x, y[:, 0], key=jax.random.PRNGKey(0),
                  variance_rank=variance_rank)
    mesh = data_mesh(1)

    def grad_core(p, q):
        f = lambda qq: jnp.sum(_predict_core(p, qq, backend="slice_xla")[0])
        return jax.grad(f)(q)

    fn = shard_map(grad_core, mesh=mesh, in_specs=(P(), P("data")),
                   out_specs=P("data"), check_vma=False)
    counts = collective_counts(fn, pred, jnp.zeros((64, 3), jnp.float32))
    assert all(v == 0 for v in counts.values()), (
        f"query-space gradient is not collective-free: {counts}")
    return dict(counts)


def main() -> dict:
    n = int(2000 * SCALE)
    particles = int(256 * SCALE)
    row = measure_rollout(n, particles, 100)
    emit(f"rollout_n{n}_p{particles}_h100", row["rollout_s"],
         f"evals_per_s={row['evals_per_s']:.0f}")
    emit(f"rollout_grad_n{n}_p{particles}_h100", row["grad_rollout_s"],
         f"grad_evals_per_s={row['grad_evals_per_s']:.0f}")

    gc = measure_gradcheck()
    emit("gradcheck_d235", None, f"max_rel_err={gc['max_rel_err']:.2e}")
    counts = measure_grad_collectives()
    emit("grad_collectives", None,
         f"total={sum(counts.values())}")

    payload = {"rollout": row, "gradcheck": gc,
               "grad_collectives": counts}
    write_json("BENCH_rollout.json", payload)
    return payload


if __name__ == "__main__":
    main()
