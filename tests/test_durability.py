"""Durable state (DESIGN.md §14): checkpoints, persistence, crash recovery.

Pins the durability contract layer by layer: the checkpoint integrity
gate detects every scripted on-disk corruption kind (truncation, bit
flip, missing blob, stale manifest) and never restores past it;
Predictor save/load round-trips bit-exactly (property-tested over
d/n/rank and over raw blob dtype/shape edge cases) and every corrupted
save is refused at load; ``fit`` resumes bit-compatibly from its newest
valid checkpoint after an injected crash and survives injected
divergence (NaN params, loss spikes) by rolling back instead of
aborting; the serving engine warm-boots from its ``PredictorStore``,
falls back generation by generation past damage, and persists every
published Predictor off the query path. The ``recovery`` marker lane
replays the benchmarks/fig_recovery.py kill/restart schedule with real
subprocesses (an injected kill is ``os._exit`` — it needs a victim).
"""
import os
import pathlib
import sys
import tempfile

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, fit, freeze,
                      load_predictor, save_predictor, self_probe,
                      PredictorLoadError)
from repro.gp.serve import predict
from repro.launch.serve_gp import (EngineConfig, GPServeEngine,
                                   PredictorStore)
from repro.optim import Adam
from repro.runtime.checkpoint import (CheckpointCorruptError,
                                      CheckpointManager, load_blobs,
                                      save_blobs)
from repro.runtime.faults import (CORRUPTION_KINDS, FaultInjector,
                                  InjectedFault, corrupt_checkpoint)
from repro.solvers import cg

# the benchmarks package lives at the repo root (not under src/)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CFG = SimplexGPConfig(kernel="matern32", max_cg_iters=40, num_probes=4,
                      max_lanczos_iters=10)


def _data(rng, n=300, d=2):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = (jnp.sin(2 * x[:, 0])
         + 0.1 * jnp.asarray(rng.normal(size=n), jnp.float32))
    return x, y


def _val(rng, d=2, n=60):
    xv = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    return xv, jnp.sin(2 * xv[:, 0])


# -- checkpoint integrity gate (satellite 1) ---------------------------------

@pytest.mark.parametrize("kind", CORRUPTION_KINDS)
def test_checkpoint_corruption_detected(tmp_path, rng, kind):
    mgr = CheckpointManager(tmp_path, async_write=False)
    tree = {"w": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    mgr.save(1, tree)
    corrupt_checkpoint(tmp_path / "step_00000001", kind)
    with pytest.raises(CheckpointCorruptError):
        mgr.verify(1)
    tmpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(1, tmpl)


def test_latest_valid_step_skips_corrupt_newest(tmp_path, rng):
    mgr = CheckpointManager(tmp_path, keep_last=5, async_write=False)
    tree = {"w": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    for step in (1, 2, 3):
        mgr.save(step, tree)
    corrupt_checkpoint(tmp_path / "step_00000003", "bitflip")
    assert mgr.latest_valid_step() == 2
    corrupt_checkpoint(tmp_path / "step_00000002", "missing_blob")
    assert mgr.latest_valid_step() == 1
    corrupt_checkpoint(tmp_path / "step_00000001", "truncate")
    assert mgr.latest_valid_step() is None


def test_checkpoint_corruption_error_names_the_blob(tmp_path, rng):
    mgr = CheckpointManager(tmp_path, async_write=False)
    mgr.save(1, {"alpha": jnp.zeros((64,), jnp.float32)})
    corrupt_checkpoint(tmp_path / "step_00000001", "truncate")
    with pytest.raises(CheckpointCorruptError, match="alpha"):
        mgr.verify(1)


def test_checkpoint_async_wait_then_verify(tmp_path, rng):
    mgr = CheckpointManager(tmp_path, keep_last=2, async_write=True)
    tree = {"w": jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)}
    for step in range(4):
        mgr.save(step, tree, metric=float(step))
    mgr.wait()
    steps = mgr.steps()
    assert len(steps) <= 3  # keep_last=2 (+ keep_best default)
    for step in steps:
        mgr.verify(step)  # every retained generation is fully intact


# -- serialization round-trips (satellite 3) ---------------------------------

@settings(max_examples=8)
@given(dtype=st.sampled_from(["float32", "int32", "uint32", "bool"]),
       rank=st.integers(0, 3), seed=st.integers(0, 1000))
def test_blob_roundtrip_property(dtype, rank, seed):
    """Raw blob layer: any shape/dtype leaf survives save+load exactly.

    NOTE: no pytest fixtures here — a function-scoped fixture is not
    reset between @given examples, so temp dirs come from tempfile."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(1, 5)) for _ in range(rank))
    arr = (rng.normal(size=shape) * 100).astype(dtype)
    with tempfile.TemporaryDirectory() as td:
        directory = pathlib.Path(td)
        leaves = save_blobs(directory, {"leaf/with/path": arr})
        got = load_blobs(directory, leaves)["leaf/with/path"]
    assert got.dtype == arr.dtype and got.shape == arr.shape
    np.testing.assert_array_equal(got, arr)


@settings(max_examples=4, deadline=None)
@given(d=st.integers(1, 3), n=st.integers(48, 96),
       rank=st.integers(1, 4), seed=st.integers(0, 100))
def test_predictor_roundtrip_property(d, n, rank, seed):
    """Predictor save/load is bit-exact and the load passes the full gate
    across d / n / variance-rank — shapes, static fields, index."""
    rng = np.random.default_rng(seed)
    x, y = _data(rng, n=n, d=d)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=60))
    pred = freeze(model, GPParams.init(d, noise=0.2), x, y,
                  key=jax.random.PRNGKey(seed), variance_rank=rank)
    with tempfile.TemporaryDirectory() as td:
        path = pathlib.Path(td) / "p"
        save_predictor(pred, path)
        # full gate: integrity + validate + self-probe
        got = load_predictor(path)
    assert got.n_train == pred.n_train
    assert got.buckets == pred.buckets
    assert got.spacing == pred.spacing and got.backend == pred.backend
    np.testing.assert_array_equal(np.asarray(got.tables),
                                  np.asarray(pred.tables))
    np.testing.assert_array_equal(np.asarray(got.alpha),
                                  np.asarray(pred.alpha))
    np.testing.assert_array_equal(np.asarray(got.index.tkeys),
                                  np.asarray(pred.index.tkeys))
    xs = jnp.asarray(rng.normal(size=(16, d)), jnp.float32)
    a, b = predict(pred, xs), predict(got, xs)
    np.testing.assert_array_equal(np.asarray(a.mean), np.asarray(b.mean))
    np.testing.assert_array_equal(np.asarray(a.var), np.asarray(b.var))


@settings(max_examples=6)
@given(d=st.integers(1, 5), seed=st.integers(0, 1000))
def test_training_state_roundtrip_property(d, seed):
    """The exact tree ``fit`` checkpoints (params+opt_state+key) survives
    a save/restore round-trip bit-exactly for any input dimension."""
    params = GPParams.init(d, noise=0.1 + 0.01 * (seed % 7))
    opt = Adam(learning_rate=0.1)
    tree = {"params": params, "opt_state": opt.init(params),
            "best_params": params, "key": jax.random.PRNGKey(seed)}
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, async_write=False)
        mgr.save(7, tree, extra={"epoch": 7, "d": d})
        got = mgr.restore(7, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree))
        assert mgr.manifest(7)["extra"]["epoch"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", CORRUPTION_KINDS)
def test_predictor_corruption_detected_at_load(tmp_path, rng, kind):
    """Every scripted corruption kind is refused by the load gate —
    a damaged Predictor is never eligible to serve."""
    x, y = _data(rng, n=200, d=2)
    model = SimplexGP(CFG)
    pred = freeze(model, GPParams.init(2, noise=0.2), x, y,
                  key=jax.random.PRNGKey(0), variance_rank=4)
    path = tmp_path / "pred"
    save_predictor(pred, path)
    corrupt_checkpoint(path, kind)
    with pytest.raises(PredictorLoadError):
        load_predictor(path)


def test_self_probe_catches_torn_index(tmp_path, rng):
    """A key table torn against its hash layout passes every value check
    (finite, in-range, row map still a bijection) but must fail the
    behavioral self-probe: keys swapped across probe neighborhoods are
    no longer reachable from their own home buckets, which is exactly
    what a tkeys blob mixed in from another generation looks like."""
    import dataclasses as dc
    x, y = _data(rng, n=200, d=2)
    pred = freeze(SimplexGP(CFG), GPParams.init(2, noise=0.2), x, y,
                  key=jax.random.PRNGKey(0), variance_rank=4)
    self_probe(pred)  # healthy predictor passes
    ros = np.asarray(pred.index.row_of_slot)
    occ = np.nonzero(ros < pred.index.m)[0]
    tk = np.asarray(pred.index.tkeys).copy()
    a, b = occ[0], occ[-1]  # far apart -> different probe chains
    tk[[a, b]] = tk[[b, a]]
    torn = dc.replace(pred, index=dc.replace(
        pred.index, tkeys=jnp.asarray(tk)))
    with pytest.raises(PredictorLoadError, match="own rows"):
        self_probe(torn)

    # a duplicated dense row (restore-gone-wrong) trips the bijection check
    ros2 = ros.copy()
    ros2[occ[0]] = ros2[occ[1]]
    dup = dc.replace(pred, index=dc.replace(
        pred.index, row_of_slot=jnp.asarray(ros2)))
    with pytest.raises(PredictorLoadError, match="bijection"):
        self_probe(dup)


# -- resumable training (tentpole a) -----------------------------------------

def test_fit_resume_bitcompat_after_crash(tmp_path, rng):
    """The acceptance criterion: crash mid-run, resume from the newest
    checkpoint, and the combined trajectory matches an uninterrupted run
    epoch for epoch (same rng stream — the key is checkpointed)."""
    x, y = _data(rng)
    xv, yv = _val(np.random.default_rng(7))
    model = SimplexGP(CFG)
    ref = fit(model, x, y, x_val=xv, y_val=yv, epochs=8, patience=20)

    fi = FaultInjector()
    fi.arm(site="fit", kind="exception", at=5)  # crash in epoch 4
    with pytest.raises(InjectedFault):
        fit(model, x, y, x_val=xv, y_val=yv, epochs=8, patience=20,
            ckpt_dir=tmp_path, ckpt_every=2, faults=fi)
    res = fit(model, x, y, x_val=xv, y_val=yv, epochs=8, patience=20,
              ckpt_dir=tmp_path, ckpt_every=2)
    assert res.report.resumed_from_epoch == 3  # ckpt at epochs 1, 3
    ref_by_epoch = {h["epoch"]: h for h in ref.history}
    assert [h["epoch"] for h in res.history] == [4, 5, 6, 7]
    for h in res.history:
        want = ref_by_epoch[h["epoch"]]
        assert abs(h["mll"] - want["mll"]) <= 1e-3 * max(
            1.0, abs(want["mll"]))
        assert abs(h["val_rmse"] - want["val_rmse"]) <= 1e-4


def test_fit_resume_skips_corrupt_checkpoint(tmp_path, rng):
    x, y = _data(rng)
    xv, yv = _val(np.random.default_rng(7))
    model = SimplexGP(CFG)
    fit(model, x, y, x_val=xv, y_val=yv, epochs=6, patience=20,
        ckpt_dir=tmp_path, ckpt_every=2)
    steps = sorted(int(p.name[5:]) for p in tmp_path.glob("step_*")
                   if not p.name.endswith(".tmp"))
    corrupt_checkpoint(tmp_path / f"step_{steps[-1]:08d}", "bitflip")
    res = fit(model, x, y, x_val=xv, y_val=yv, epochs=8, patience=20,
              ckpt_dir=tmp_path, ckpt_every=2)
    # resumed from the newest VALID step, not the corrupted newest
    assert res.report.resumed_from_epoch == steps[-2]


def test_fit_rollback_on_injected_nan(rng):
    x, y = _data(rng)
    xv, yv = _val(np.random.default_rng(7))
    fi = FaultInjector()
    fi.arm(site="fit", kind="nan_params", at=4)
    res = fit(SimplexGP(CFG), x, y, x_val=xv, y_val=yv, epochs=8,
              patience=30, faults=fi)
    reasons = [e["reason"] for e in res.report.rollbacks]
    assert any("non-finite" in r for r in reasons)
    assert all(np.isfinite(h["mll"]) for h in res.history)
    # escalation recorded: reduced lr, raised jitter
    assert res.report.rollbacks[0]["lr_scale"] == 0.5
    assert res.report.rollbacks[0]["jitter_raw"] > 0


def test_fit_rollback_on_loss_spike(rng):
    """An injected loss spike is survived by rollback, not an abort, and
    training continues to a healthy final state."""
    x, y = _data(rng)
    xv, yv = _val(np.random.default_rng(7))
    fi = FaultInjector()
    fi.arm(site="fit", kind="spike_params", at=10)
    res = fit(SimplexGP(CFG), x, y, x_val=xv, y_val=yv, epochs=14,
              patience=30, spike_window=4, spike_sigma=6.0, faults=fi)
    assert len(res.report.rollbacks) >= 1
    assert "spike" in res.report.rollbacks[0]["reason"]
    assert res.history[-1]["val_rmse"] < 0.5  # recovered, kept training


def test_fit_rollback_budget_exhaustion_raises(rng):
    x, y = _data(rng)
    xv, yv = _val(np.random.default_rng(7))
    fi = FaultInjector()
    fi.arm(site="fit", kind="nan_params", at=2, count=10)  # persistent
    with pytest.raises(RuntimeError, match="divergence guard exhausted"):
        fit(SimplexGP(CFG), x, y, x_val=xv, y_val=yv, epochs=8,
            patience=30, max_rollbacks=2, faults=fi)


# -- warm-boot serving (tentpole c) ------------------------------------------

def _store_engine(rng, store, **kw):
    x, y = _data(rng, n=240, d=3)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=60))
    params = GPParams.init(3, noise=0.2)
    cfg = EngineConfig(variance_rank=4, refresh_min_deadline_s=30.0)
    eng = GPServeEngine(model, params, x, y, key=jax.random.PRNGKey(0),
                        config=cfg, store=store, model_name="m", **kw)
    return eng, x, y


def test_engine_persists_and_warm_boots(tmp_path, rng):
    store = PredictorStore(tmp_path, keep_last=2)
    eng, x, y = _store_engine(rng, store)
    assert eng.health().boot_mode == "cold"
    assert eng.wait_persisted(timeout_s=60)  # boot predictor durable
    eng.submit_refresh(y=y + 0.01)
    assert eng.refresh_now()
    assert eng.wait_persisted(timeout_s=60)
    gens = store.generations("m")
    assert len(gens) == 2
    eng.close()

    eng2, x2, _ = _store_engine(np.random.default_rng(0), store)
    h = eng2.health()
    assert h.boot_mode == "warm" and h.boot_generation == gens[-1]
    assert h.boot_skipped == 0
    res = eng2.query(x2[:16])
    assert np.isfinite(np.asarray(res.mean)).all()
    eng2.close()


def test_engine_generation_fallback_past_corruption(tmp_path, rng):
    store = PredictorStore(tmp_path, keep_last=3)
    eng, x, y = _store_engine(rng, store)
    eng.wait_persisted(timeout_s=60)
    eng.submit_refresh(y=y + 0.01)
    assert eng.refresh_now() and eng.wait_persisted(timeout_s=60)
    eng.close()
    gens = store.generations("m")
    corrupt_checkpoint(store.path("m", gens[-1]), "truncate")

    eng2, x2, _ = _store_engine(np.random.default_rng(0), store)
    h = eng2.health()
    assert h.boot_mode == "warm"
    assert h.boot_generation == gens[-2]  # fell back exactly one
    assert h.boot_skipped == 1
    res = eng2.query(x2[:16])
    assert np.isfinite(np.asarray(res.mean)).all()
    eng2.close()


def test_engine_cold_boot_when_store_all_corrupt(tmp_path, rng):
    store = PredictorStore(tmp_path, keep_last=3)
    eng, _, _ = _store_engine(rng, store)
    eng.wait_persisted(timeout_s=60)
    eng.close()
    for g in store.generations("m"):
        corrupt_checkpoint(store.path("m", g), "missing_blob")
    eng2, x2, _ = _store_engine(np.random.default_rng(0), store)
    h = eng2.health()
    assert h.boot_mode == "cold"
    assert h.boot_skipped >= 1  # the rejected generations are on record
    res = eng2.query(x2[:16])
    assert np.isfinite(np.asarray(res.mean)).all()
    eng2.close()


def test_store_retention_keeps_last_k_plus_best(tmp_path, rng):
    store = PredictorStore(tmp_path, keep_last=2, keep_best=1)
    x, y = _data(rng, n=200, d=2)
    pred = freeze(SimplexGP(CFG), GPParams.init(2, noise=0.2), x, y,
                  key=jax.random.PRNGKey(0), variance_rank=4)
    metrics = [5.0, 1.0, 4.0, 3.0, 2.0]
    for i, m in enumerate(metrics):
        store.save("m", pred, gen=i + 1, metric=m)
    gens = store.generations("m")
    assert 2 in gens  # best metric (1.0) survives retention
    assert gens[-2:] == [4, 5]  # newest two kept
    assert len(gens) <= 3


# -- CG warm-start hygiene (powers warm boot + refreeze) ---------------------

def _spd_problem(rng, n=48, k=3):
    a = rng.normal(size=(n, n)).astype(np.float32)
    A = jnp.asarray(a @ a.T + n * np.eye(n, dtype=np.float32))
    b = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    return (lambda v: A @ v), b


def test_cg_nonfinite_seed_sanitized(rng):
    matvec, b = _spd_problem(rng)
    x_ref, _ = cg(matvec, b, tol=1e-6, max_iters=200)
    bad = jnp.full_like(b, jnp.nan).at[:, 0].set(b[:, 0])
    x, info = cg(matvec, b, tol=1e-6, max_iters=200, x0=bad)
    assert bool(jnp.all(jnp.isfinite(x)))
    assert bool(jnp.all(info.converged))
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               rtol=1e-3, atol=1e-4)


def test_cg_regressive_seed_reset_to_cold(rng):
    """A seed WORSE than zero (stale checkpoint under new hyperparams)
    must not slow convergence below the cold start."""
    matvec, b = _spd_problem(rng)
    _, cold = cg(matvec, b, tol=1e-6, max_iters=200)
    awful = 1e6 * jnp.ones_like(b)
    x, info = cg(matvec, b, tol=1e-6, max_iters=200, x0=awful)
    assert bool(jnp.all(info.converged))
    assert int(info.iterations) <= int(cold.iterations)


def test_cg_perfect_seed_costs_zero_iterations(rng):
    matvec, b = _spd_problem(rng)
    x_ref, _ = cg(matvec, b, tol=1e-6, max_iters=200)
    _, info = cg(matvec, b, tol=1e-4, max_iters=200, x0=x_ref)
    assert int(info.iterations) == 0


# -- crash-recovery smoke (tentpole d; CI lane) ------------------------------

@pytest.mark.recovery
def test_kill_restart_recovery_smoke(tmp_path):
    """Real-subprocess kill/restart cycles through one shared store:
    the scaled-down benchmarks/fig_recovery.py schedule (one corruption
    kind). Asserts the §14 acceptance invariants end to end."""
    from benchmarks.fig_recovery import run_recovery
    payload = run_recovery(tmp_path, corruption_kinds=("bitflip",),
                           queries=2, timeout_s=280.0)
    s = payload["summary"]
    assert not s["errors"], s["errors"]
    assert s["kills"] == 2
    assert s["max_generations_lost"] <= 1
    assert s["invalid_responses"] == 0
    assert s["all_corruptions_detected"]
    assert s["warm_boots"] >= 1
