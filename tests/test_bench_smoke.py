"""Tier-1 smoke of the training/prediction pipeline the benchmarks measure.

One jitted training step and one posterior at tiny size, under the
policy-chosen ("auto") fused backend, asserting the DESIGN.md §9 contract:
exactly one lattice build each, finite outputs, no table overflow. A
pipeline regression (extra rebuilds, broken fused dispatch, NaNs from the
CG-reused log-det) fails here instead of only showing up in
``benchmarks/fig_train_step.py``.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig,
                      mll_value_and_grad, posterior)

# the benchmarks package lives at the repo root (not under src/)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.bench_smoke
def test_training_step_smoke(rng):
    n, d = 96, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=15,
                                      num_probes=3, backend="auto"))
    params = GPParams.init(d)

    step = jax.jit(lambda p, k: mll_value_and_grad(model, p, x, y, k))
    c0 = obs.counter("lattice.builds")
    res = jax.block_until_ready(step(params, jax.random.PRNGKey(0)))
    # one lattice build per training step
    assert obs.counter("lattice.builds") - c0 == 1
    assert np.isfinite(float(res.mll))
    assert not bool(res.overflow)
    for leaf in jax.tree.leaves(res.grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.bench_smoke
def test_posterior_smoke(rng):
    n, ns, d = 96, 24, 2
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(ns, d)), jnp.float32)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=15,
                                      backend="auto"))
    params = GPParams.init(d)

    c0 = obs.counter("lattice.builds")
    post = posterior(model, params, x, y, xs, key=jax.random.PRNGKey(1),
                     variance_rank=6)
    jax.block_until_ready(post.mean)
    # one lattice build per posterior
    assert obs.counter("lattice.builds") - c0 == 1
    assert post.mean.shape == (ns,)
    assert bool(jnp.all(jnp.isfinite(post.mean)))
    assert bool(jnp.all(post.var > 0))
    assert not bool(post.overflow)


@pytest.mark.bench_smoke
def test_build_bench_smoke(rng):
    """benchmarks/fig_build.py's measurement path at tiny size: both build
    backends run, the row carries every field BENCH_build.json reports,
    and the structural invariants (m, occupancy, finite timings) hold. A
    broken backend fails here instead of only in the benchmark run."""
    from benchmarks.fig_build import measure_build

    x = jnp.asarray(rng.normal(size=(160, 3)) * 0.5, jnp.float32)
    row = measure_build(x, with_phases=True)
    assert row["n"] == 160 and row["d"] == 3
    assert 0 < row["m"] <= row["cap"]
    assert 0 < row["occupancy"] <= 0.5
    for backend in ("sort", "hash_xla"):
        assert row[backend]["cold_s"] > 0
        assert row[backend]["compile_s"] > 0
        assert row[backend]["compile_s"] >= row[backend]["cold_s"]
    assert row["cold_speedup"] > 0 and row["compile_speedup"] > 0
    ph = row["phases"]
    assert ph["embed_s"] > 0
    assert set(ph["sort"]) == {"dedup_s", "neighbor_s"}
    assert set(ph["hash"]) == {"dedup_s", "neighbor_s", "plan_s"}


@pytest.mark.bench_smoke
def test_serve_bench_smoke(rng):
    """benchmarks/fig_serve.py's measurement path at tiny size: freeze +
    predict + the posterior baseline all run, the row carries every field
    BENCH_serve.json reports, and the fidelity invariants hold (tight-tol
    parity, zero in-lattice miss, off-lattice miss in [0, 1])."""
    from benchmarks.fig_serve import measure_serve

    n, d, bq = 200, 3, 32
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    xs_out = jnp.asarray(rng.normal(size=(bq, d)) * 2.0, jnp.float32)
    row = measure_serve(x, y, x[:bq], xs_out, variance_rank=6)
    assert row["n"] == n and row["bq"] == bq
    assert row["freeze_s"] > 0 and row["serve_s"] > 0
    assert row["posterior_s"] > 0 and row["speedup"] > 1
    assert row["mean_parity"] <= 1e-4  # tiny-size band; 1e-5 at bench size
    assert row["miss_in_lattice"] == 0.0
    off = row["offlattice"]
    assert 0.0 <= off["mean_miss"] <= 1.0 and 0.0 <= off["max_miss"] <= 1.0


@pytest.mark.bench_smoke
def test_rollout_bench_smoke():
    """benchmarks/fig_rollout.py's measurement paths at tiny size: the
    k=2 freeze_multi + jitted MC rollout runs and reports positive
    throughput with a valid miss bound, the FD gradcheck meets the same
    1e-4 band the trend check enforces, and the query-gradient jaxpr is
    collective-free (the measure asserts it)."""
    from benchmarks.fig_rollout import (measure_grad_collectives,
                                        measure_gradcheck, measure_rollout)

    row = measure_rollout(200, 32, 10, variance_rank=4, iters=1)
    assert row["k"] == 2 and row["m"] > 0
    assert row["evals_per_s"] > 0 and row["grad_evals_per_s"] > 0
    assert 0.0 <= row["worst_miss"] <= 1.0
    gc = measure_gradcheck(dims=(2,), n=200, variance_rank=4)
    assert gc["max_rel_err"] <= 1e-4
    assert gc["dims"]["2"]["pairs"] > 0
    counts = measure_grad_collectives(n=150, variance_rank=4)
    assert all(v == 0 for v in counts.values())


@pytest.mark.bench_smoke
def test_trend_check_runs_clean():
    """The CI trend gate parses every committed artifact and exits 0 (its
    fail-soft contract); a malformed BENCH_*.json fails here in tier-1
    instead of only annotating a CI run."""
    from benchmarks.trend_check import main

    assert main([]) == 0


def test_run_exits_nonzero_when_a_module_raises(monkeypatch):
    """benchmarks.run keeps going past a failed module but must not
    report success: a run built on it would read "ok" over a path that
    never ran."""
    from benchmarks import run

    class Broken:
        @staticmethod
        def main():
            raise RuntimeError("module failed")

    monkeypatch.setattr(run, "MODULES", {"broken": Broken})
    monkeypatch.setattr(sys, "argv", ["run", "broken"])
    assert run.main() == 1
