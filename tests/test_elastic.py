"""Elastic sharded GP training (DESIGN.md §16).

Three layers of defense, mirroring test_multidevice.py:
  * in-process (always runs, 1 real device): cache mesh-keying, the
    degenerate size-1-data-axis one-psum pin, fit's transient-retry and
    watchdog-breach semantics, the in-process ElasticGPTrainer loop,
    and a hypothesis property for the replicated checkpoint round-trip;
  * subprocess snippets (marker ``elastic``): checkpoint round-trip
    across REAL mesh sizes (8 -> 4 -> 1 -> 8, params bit-identical) and
    the cross-mesh LatticeCache staleness regression (8 -> 4 resume must
    miss and rebuild);
  * subprocess worker lives (marker ``elastic``): a scripted kill on the
    full mesh resumed on half the devices — true device loss, losing at
    most ``ckpt_every`` epochs.

The ``elastic`` CI lane runs the subprocess tests under varying base
device counts (``ELASTIC_BASE_DEVICES``).
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import lattice as lat_mod
from repro.core.filtering import LatticeCache
from repro.core.stencil import make_stencil
from repro.gp import SimplexGP, SimplexGPConfig
from repro.gp import train as train_mod
from repro.gp.models import GPParams
from repro.launch.elastic_gp import (ElasticGPTrainer, make_problem,
                                     params_digest)
from repro.runtime import elastic
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.faults import FaultEvent, FaultInjector, is_injected
from repro.runtime.straggler import StepWatchdog
from repro.sharding import simplex as sx

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BASE_DEVICES = int(os.environ.get("ELASTIC_BASE_DEVICES", "8"))

CFG = SimplexGPConfig(kernel="matern32", max_cg_iters=40, num_probes=2)


# -- mesh fingerprints and cache keys (in-process) ---------------------------

def test_mesh_fingerprint_distinguishes_layouts():
    assert sx.mesh_fingerprint(None) == ""
    m1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    fp = sx.mesh_fingerprint(m1)
    assert fp and fp != sx.mesh_fingerprint(None)
    # same devices, same axis -> same fingerprint (stable key)
    assert fp == sx.mesh_fingerprint(Mesh(np.array(jax.devices()[:1]),
                                          ("data",)))


def test_cache_misses_on_mesh_change(rng):
    """A lattice built for one consumer mesh must never serve another
    (DESIGN.md §16): mesh=None and a 1-device mesh are distinct keys."""
    st_ = make_stencil("rbf", 1)
    x = jnp.asarray(rng.normal(size=(64, 2)), jnp.float32)
    ls = jnp.ones((2,), jnp.float32)
    cache = LatticeCache()
    tag = cache.point_set_tag(x)
    m1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    l_none = cache.get(tag, x, spacing=st_.spacing, r=st_.r, cap=None,
                       ls=ls)
    l_mesh = cache.get(tag, x, spacing=st_.spacing, r=st_.r, cap=None,
                       ls=ls, mesh=m1)
    assert l_mesh is not l_none
    assert cache.misses == 2 and cache.hits == 0
    assert cache.get(tag, x, spacing=st_.spacing, r=st_.r, cap=None,
                     ls=ls, mesh=m1) is l_mesh
    assert cache.hits == 1


def test_one_psum_on_size1_data_axis(rng):
    """Degenerate mesh: the one-psum contract holds when the data axis
    has shrunk all the way to a single device (elastic floor)."""
    st_ = make_stencil("matern32", 1)
    z = jnp.asarray(rng.normal(size=(37, 3)), jnp.float32)  # uneven too
    v = jnp.asarray(rng.normal(size=(37, 2)), jnp.float32)
    lat = lat_mod.build_lattice(z, spacing=st_.spacing, r=st_.r)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    w = jnp.asarray(st_.weights, jnp.float32)
    counts = sx.collective_counts(
        lambda vv: sx.sharded_lattice_mvm(lat, vv, w, mesh=mesh), v)
    assert counts["psum"] == 1
    assert all(c == 0 for p, c in counts.items() if p != "psum")


# -- checkpoint round-trip property (in-process) -----------------------------

@settings(max_examples=8, deadline=None)
@given(d=st.integers(1, 6), seed=st.integers(0, 1000))
def test_ckpt_roundtrip_replicated_property(d, seed):
    """GP loop state is replicated: restore via resume_gp onto any mesh
    must be bit-identical to what was saved, for any param shape/seed.

    NOTE: no pytest fixtures here — @given properties run many examples
    per test call, so state is built inside the example.
    """
    key = jax.random.PRNGKey(seed)
    params = GPParams.init(d)
    params = jax.tree.map(
        lambda a, k=key: a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    tree = {"params": params, "key": key}
    with tempfile.TemporaryDirectory() as td:
        m = CheckpointManager(td, keep_last=1)
        m.save(0, tree, metric=0.0, extra={"epoch": 0})
        m.wait()
        tmpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        out, step, extra, mesh = elastic.resume_gp(m, tmpl)
    assert step == 0 and extra["epoch"] == 0
    assert mesh.shape["data"] == jax.device_count()
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# -- fit step-failure semantics (in-process) ---------------------------------

def _tiny_problem():
    return make_problem(0, 96, 2, 24)


def test_fit_retries_transient_step_fault(tmp_path):
    """A transient in-step exception surfaces as a retried event in
    FitReport — and the retried trajectory is bit-identical to an
    un-faulted run (the step is pure; the retry replays it)."""
    x, y, xv, yv = _tiny_problem()
    model = SimplexGP(CFG)
    inj = FaultInjector([FaultEvent(site="fit_step", kind="exception",
                                    at=2, note="transient")])
    faulted = train_mod.fit(model, x, y, x_val=xv, y_val=yv, epochs=4,
                            faults=inj)
    assert len(faulted.report.retries) == 1
    assert faulted.report.retries[0]["epoch"] == 1
    assert faulted.report.completed_epochs == 4
    assert faulted.report.interrupted is None
    # bit-compat vs a clean run: the injector must be armed (same guarded
    # step program) but with nothing scheduled
    clean = train_mod.fit(model, x, y, x_val=xv, y_val=yv, epochs=4,
                          faults=FaultInjector())
    assert params_digest(faulted.params) == params_digest(clean.params)


def test_fit_exhausted_retries_raise(tmp_path):
    """A PERSISTENT in-step failure (count > step_retries) aborts: retry
    absorbs transients, not hard faults."""
    x, y, xv, yv = _tiny_problem()
    model = SimplexGP(CFG)
    inj = FaultInjector([FaultEvent(site="fit_step", kind="exception",
                                    at=1, count=5, note="persistent")])
    with pytest.raises(Exception) as ei:
        train_mod.fit(model, x, y, x_val=xv, y_val=yv, epochs=3,
                      faults=inj, step_retries=2)
    assert is_injected(ei.value)


def test_fit_watchdog_breach_checkpoints_and_aborts(tmp_path):
    """A wedged step trips the watchdog: fit records the breach, writes
    an immediate checkpoint of the slow-but-valid epoch, and (with
    watchdog_abort) returns early so a supervisor can re-shard."""
    x, y, xv, yv = _tiny_problem()
    model = SimplexGP(CFG)
    # warm the 2-step window first so compile time doesn't set the median
    inj = FaultInjector([FaultEvent(site="fit_step", kind="slow", at=5,
                                    seconds=1.0, note="wedge")])
    wd = StepWatchdog(window=2, multiplier=2.0, min_deadline=0.3)
    res = train_mod.fit(model, x, y, x_val=xv, y_val=yv, epochs=8,
                        ckpt_dir=str(tmp_path), ckpt_every=100,
                        faults=inj, watchdog=wd, watchdog_abort=True)
    assert res.report.interrupted == "watchdog_breach"
    assert len(res.report.watchdog_breaches) == 1
    breach_epoch = res.report.watchdog_breaches[0]["epoch"]
    assert res.history[-1]["epoch"] == breach_epoch
    # the breach epoch is durable DESPITE ckpt_every=100
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest_valid_step() == breach_epoch
    # and a resumed fit continues from it to completion
    cont = train_mod.fit(model, x, y, x_val=xv, y_val=yv, epochs=8,
                         ckpt_dir=str(tmp_path), ckpt_every=100,
                         resume=True)
    assert cont.report.resumed_from_epoch == breach_epoch
    assert cont.history[-1]["epoch"] == 7


def test_elastic_trainer_crash_resume(tmp_path):
    """The in-process supervisor: an injected crash falls back to the
    last durable checkpoint and the run still completes."""
    x, y, xv, yv = _tiny_problem()
    model = SimplexGP(CFG)
    inj = FaultInjector([FaultEvent(site="fit", kind="exception", at=4,
                                    note="crash")])
    t = ElasticGPTrainer(model, x, y, x_val=xv, y_val=yv,
                         ckpt_dir=str(tmp_path), epochs=6, ckpt_every=2,
                         faults=inj)
    rep = t.run()
    assert rep.restarts == 1
    assert rep.events[0]["kind"] == "crash"
    assert rep.result.history[-1]["epoch"] == 5
    # the crash cost at most ckpt_every epochs of progress
    assert rep.result.report.resumed_from_epoch >= 3 - 2


# -- subprocess: REAL mesh sizes ---------------------------------------------

ROUNDTRIP = textwrap.dedent("""
    import json, tempfile
    import jax, numpy as np
    from repro.gp.models import GPParams
    from repro.runtime.checkpoint import CheckpointManager
    from repro.runtime import elastic
    from repro.launch.elastic_gp import params_digest

    devs = jax.devices()
    tree = {"params": GPParams.init(3), "key": jax.random.PRNGKey(7)}
    d0 = params_digest(tree)
    tmpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)
    out = {"devices": jax.device_count(), "chain": []}
    with tempfile.TemporaryDirectory() as td:
        m = CheckpointManager(td, keep_last=8)
        m.save(0, tree, metric=0.0, extra={}); m.wait()
        step = 0
        for k in (len(devs) // 2, 1, len(devs)):
            # restore onto a k-device mesh, then re-save FROM that mesh:
            # the next restore exercises a save-on-k/restore-on-k' pair
            t2, s, _, mesh = elastic.resume_gp(m, tmpl, devices=devs[:k])
            out["chain"].append({"k": k, "from_step": s,
                                 "bit_identical": params_digest(t2) == d0,
                                 "axis": int(mesh.shape["data"])})
            step += 1
            m.save(step, t2, metric=0.0, extra={}); m.wait()
    print(json.dumps(out))
""")


CACHE_STALENESS = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core.filtering import LatticeCache
    from repro.gp import GPParams, SimplexGP, SimplexGPConfig

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(120, 2)), jnp.float32)
    model = SimplexGP(SimplexGPConfig(kernel="matern32"))
    params = GPParams.init(2)
    devs = jax.devices()
    m8 = Mesh(np.array(devs), ("data",))
    m4 = Mesh(np.array(devs[: len(devs) // 2]), ("data",))
    cache = LatticeCache()
    # "training on the full mesh": the operator builds through the cache
    model.operator(params, x, cache=cache, mesh=m8)
    after_full = (cache.misses, cache.hits)
    # "resume on half the mesh": MUST miss (a lattice keyed to the old
    # layout is stale) and rebuild
    model.operator(params, x, cache=cache, mesh=m4)
    after_shrink = (cache.misses, cache.hits)
    # steady state on the new mesh: hits
    model.operator(params, x, cache=cache, mesh=m4)
    print(json.dumps({"devices": jax.device_count(),
                      "after_full": after_full,
                      "after_shrink": after_shrink,
                      "final": (cache.misses, cache.hits)}))
""")


@pytest.mark.elastic
@pytest.mark.multidevice
def test_ckpt_roundtrip_8_4_1_8_subprocess(multidevice_run):
    """Checkpoint round-trip across real mesh sizes: 8 -> 4 -> 1 -> 8,
    params bit-identical after every re-shard."""
    data = multidevice_run(ROUNDTRIP)
    assert data["devices"] == 8
    assert [c["k"] for c in data["chain"]] == [4, 1, 8]
    for c in data["chain"]:
        assert c["bit_identical"], c
        assert c["axis"] == c["k"]


@pytest.mark.elastic
@pytest.mark.multidevice
def test_cache_staleness_8_to_4_subprocess(multidevice_run):
    """Resuming 8 -> 4 devices must never serve the 8-device lattice:
    the cache misses and rebuilds, then serves the new entry."""
    data = multidevice_run(CACHE_STALENESS)
    assert data["devices"] == 8
    assert tuple(data["after_full"]) == (1, 0)
    assert tuple(data["after_shrink"]) == (2, 0)  # miss: stale layout
    assert tuple(data["final"]) == (2, 1)  # steady state on new mesh


# -- subprocess: true device loss (worker lives) -----------------------------

def _run_life(spec: dict, devices: int, timeout: int = 600):
    """One elastic_gp worker life under ``devices`` virtual CPUs."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={devices}").strip()
    extra = env.get("PYTHONPATH")
    env["PYTHONPATH"] = SRC + (os.pathsep + extra if extra else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.elastic_gp", "--worker",
         json.dumps(spec)],
        env=env, capture_output=True, text=True, timeout=timeout)
    report = None
    if proc.returncode == 0:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
    else:
        assert proc.returncode == 17, proc.stderr[-3000:]
    return proc.returncode, report


@pytest.mark.elastic
def test_kill_on_full_mesh_resume_on_half(tmp_path):
    """True device loss: a life killed at a scripted epoch on the full
    mesh resumes on HALF the devices, losing <= ckpt_every epochs —
    across a data size the smaller mesh does not divide evenly."""
    full, half = BASE_DEVICES, max(1, BASE_DEVICES // 2)
    spec = {"ckpt_dir": str(tmp_path), "seed": 1, "n": 90, "d": 2,
            "n_val": 24, "epochs": 8, "ckpt_every": 2,
            "max_cg_iters": 30, "num_probes": 2}
    # dies at epoch 5: epochs 0..4 completed, checkpoints at 1/3 -> the
    # resume restores 3 and loses exactly 1 completed epoch (<= 2)
    code, _ = _run_life(
        dict(spec, faults=[{"site": "fit", "kind": "kill", "at": 6}]),
        devices=full)
    assert code == 17
    code, rep = _run_life(spec, devices=half)
    assert code == 0
    assert rep["devices"] == half and rep["visible_devices"] == half
    assert rep["resumed_from_epoch"] == 3
    lost = 4 - rep["resumed_from_epoch"]
    assert 0 <= lost <= spec["ckpt_every"]
    assert rep["last_epoch"] == 7 and rep["interrupted"] is None
    assert np.isfinite(rep["final_mll"])
