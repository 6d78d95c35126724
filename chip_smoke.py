"""Run the Simplex-GP main path once on a TPU and check what comes out.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the data-parallel path on four chips

One chip, at protein's published (n, d) with inputs made from ``--seed``
(``data/synthetic_uci.py``): ``fit`` for a few epochs with a finite MLL on
every step, ``freeze``, then a ``GPServeEngine`` answers query batches and
one y-only ``refresh_now``. Two comparisons against references run on the
chip: the ``auto`` lattice MVM against the ``xla`` reference tier at full
size, and at small n the Simplex-GP posterior mean against the dense GP of
``core/exact.py``. Both references run at full f32 matmul precision.

Four chips: ``fit`` on a 4-chip data mesh, its training steps against the
same steps on one chip (MLL and gradients), one psum per sharded MVM with
rows on every chip, and replicated-table ``predict`` against single-device
``predict`` with zero collectives.

Progress goes to stdout, one line per step, with compile seconds apart
from the rest of each phase's wall time. The last line is one JSON object,
``{"ok": true, "device": {...}}``. The script exits non-zero before that
line when JAX finds no TPU or when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import kernels_math as km  # noqa: E402
from repro.core import lattice as lat_mod  # noqa: E402
from repro.core.exact import ExactGP  # noqa: E402
from repro.data.synthetic_uci import load  # noqa: E402
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, fit,  # noqa: E402
                      freeze, mll_value_and_grad, posterior,
                      validate_predictor)
from repro.gp.serve import _predict_core, predict  # noqa: E402
from repro.kernels.blur.ops import lattice_mvm, resolve_backend  # noqa: E402
from repro.kernels.hash import ops as hash_ops  # noqa: E402
from repro.kernels.slice.ops import resolve_slice_backend  # noqa: E402
from repro.launch.serve_gp import EngineConfig, GPServeEngine  # noqa: E402
from repro.optim import Adam  # noqa: E402
from repro.sharding import simplex as sx  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

DATASET = "protein"
# tests/test_parity_exact.py: tiers agree to 1e-3; the sharded operator
# to 1e-5 (tests/test_multidevice.py)
TIER_REL_TOL = 1e-3
MESH_REL_TOL = 1e-5
STEP_CG_TOL = 1e-4  # converged solves for the step comparison
EPOCHS = 3
MVM_REPEATS = 5  # timed calls per tier after the compiling one
QUERY_BATCHES = (1000, 4096, 4096)  # two serving buckets: 1024 and 4096


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def tree_rel_err(a, b) -> float:
    """Relative error of two pytrees taken as one flat vector each."""
    flat = lambda t: np.concatenate([np.ravel(v) for v in jax.tree.leaves(t)])
    return rel_err(flat(a), flat(b))


def compile_seconds(since: float) -> float:
    """Seconds XLA spent compiling since ``since`` (``time.perf_counter``),
    from JAX's own compile events (``repro.obs``'s JIT record)."""
    return sum(e.t1 - e.t0 for e in obs.jit_events()
               if e.kind == "compile" and e.t0 >= since)


def run_phase(name: str, fn, failed: list[str] | None = None):
    """Run one phase and print its wall, compile and run seconds.

    With ``failed``, a failed check is recorded there and the run goes
    on, so one run reports every comparison; without it, it propagates.
    """
    t0 = time.perf_counter()
    out = None
    try:
        out = fn()
    except SmokeFailure as e:
        if failed is None:
            raise
        log(f"[{name}] FAILED: {e}")
        failed.append(name)
    finally:
        wall = time.perf_counter() - t0
        comp = compile_seconds(t0)
        log(f"[{name}] wall_s={wall:.3f} compile_s={comp:.3f} "
            f"run_s={wall - comp:.3f}")
    return out


# -- one chip ----------------------------------------------------------------


def phase_fit(model: SimplexGP, ds, *, epochs: int, seed: int):
    """``fit`` for ``epochs`` epochs; every step's MLL must be finite."""
    res = fit(model, jnp.asarray(ds.x_train), jnp.asarray(ds.y_train),
              x_val=jnp.asarray(ds.x_val), y_val=jnp.asarray(ds.y_val),
              epochs=epochs, lr=0.1, seed=seed, patience=epochs + 1)
    for h in res.history:
        log(f"  epoch {h['epoch']} mll={h['mll']!r} "
            f"val_rmse={h['val_rmse']!r} cg_iters={h['cg_iters']} "
            f"cap={h['cap']} step_s={h['seconds']:.3f}")
    check(len(res.history) == epochs,
          f"fit ran {len(res.history)} of {epochs} epochs")
    check(all(np.isfinite(h["mll"]) for h in res.history),
          "non-finite MLL in fit")
    check(not res.report.rollbacks and not res.report.retries,
          f"fit rolled back or retried: {res.report.rollbacks} "
          f"{res.report.retries}")
    return res


def phase_lattice(model: SimplexGP, params: GPParams, x):
    """The training lattice at the trained lengthscales."""
    st = model.stencil
    ls = model.constrained(params)[0]
    lat = lat_mod.build_lattice_auto(x / ls[None, :], spacing=st.spacing,
                                     r=st.r,
                                     backend=model.config.build_backend)
    check(not bool(lat.overflow), "lattice overflow at the trained params")
    log(f"  m={int(lat.m)} cap={lat.cap}")
    return lat


def phase_mvm_parity(model: SimplexGP, lat, *, c: int, seed: int):
    """The ``auto`` lattice MVM against the ``xla`` reference tier."""
    st = model.stencil
    n = lat.n
    tiers = {"blur": resolve_backend("auto"),
             "build": hash_ops.resolve_build_backend(
                 model.config.build_backend)}
    taps = tuple(st.weights)
    v = jax.random.normal(jax.random.PRNGKey(seed), (n, c), jnp.float32)

    def mvm(backend):
        return jax.jit(lambda lt, vv: lattice_mvm(lt, vv, taps=taps,
                                                  backend=backend))

    def timed(fn):
        """First call (compile included), then the median of timed runs."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(lat, v))
        first = time.perf_counter() - t0
        runs = []
        for _ in range(MVM_REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(lat, v))
            runs.append(time.perf_counter() - t0)
        return out, first, float(np.median(runs))

    got, first_a, run_a = timed(mvm("auto"))
    with jax.default_matmul_precision("highest"):
        ref, first_x, run_x = timed(mvm("xla"))
    rel = rel_err(got, ref)
    log(f"  tiers blur={tiers['blur']} build={tiers['build']} "
        f"auto_vs_xla_rel={rel!r} (tol {TIER_REL_TOL})")
    log(f"  mvm c={c} auto first_call_s={first_a!r} run_s={run_a!r}; "
        f"xla first_call_s={first_x!r} run_s={run_x!r} "
        f"(median of {MVM_REPEATS})")
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite MVM output")
    check(rel <= TIER_REL_TOL, f"auto MVM vs xla reference: {rel}")


def phase_freeze(model: SimplexGP, params: GPParams, x, y, *, cap: int,
                 rank: int, seed: int):
    pred = freeze(model, params, x, y, key=jax.random.PRNGKey(seed),
                  variance_rank=rank, cap=cap)
    rep = validate_predictor(pred)
    tier = resolve_slice_backend(pred.backend)
    log(f"  tables={tuple(pred.tables.shape)} hcap={pred.index.hcap} "
        f"cg_iters={int(pred.cg_iterations)} "
        f"cg_residual={float(pred.cg_residual)!r} slice_tier={tier}")
    check(rep.ok, f"frozen predictor failed validation: {rep.failures}")


def phase_serve(model: SimplexGP, params: GPParams, x, y, queries, *,
                cap: int, rank: int, seed: int):
    """Engine queries, one y-only refresh, more queries; counters clean."""
    y_new = y + 0.05 * jnp.sin(x[:, 0])
    with GPServeEngine(model, params, x, y, key=jax.random.PRNGKey(seed),
                       config=EngineConfig(variance_rank=rank),
                       cap=cap) as eng:
        for q in queries:
            t0 = time.perf_counter()
            res = eng.query(q)
            jax.block_until_ready(res.mean)
            dt = time.perf_counter() - t0
            log(f"  query b={q.shape[0]} version={res.version} "
                f"fallback={int(res.fallback.sum())} "
                f"mean_miss={float(jnp.mean(res.miss_mass))!r} "
                f"wall_s={dt:.4f}")
            check(res.mean.shape == (q.shape[0],), "bad mean shape")
            check(bool(jnp.all(jnp.isfinite(res.mean))
                       & jnp.all(jnp.isfinite(res.var))),
                  "non-finite query answer")
        eng.submit_refresh(y=y_new)
        check(eng.refresh_now(), "y-only refresh did not publish")
        res = eng.query(queries[-1])
        check(res.version == 2, f"refreshed version not served: "
                                f"{res.version}")
        check(bool(jnp.all(jnp.isfinite(res.mean))), "non-finite answer")
        h = eng.health()
    log(f"  health status={h.status} served={h.queries_served} "
        f"retried={h.queries_retried} refused={h.queries_refused} "
        f"refresh_ok={h.refreshes_ok} failed={h.refreshes_failed} "
        f"rejected={h.refreshes_rejected} wedged={h.refreshes_wedged} "
        f"last_refresh_s={h.last_refresh_s!r}")
    check(h.queries_retried == 0 and h.queries_refused == 0,
          "retried or refused queries")
    check(h.refreshes_failed == 0 and h.refreshes_wedged == 0
          and h.refreshes_rejected == 0 and h.refreshes_ok == 1,
          "refresh failed")


def phase_exact_parity(*, seed: int, d: int = 3, n: int = 192,
                       ns: int = 48):
    """Posterior mean against the dense GP at small n, in the bands of
    tests/test_parity_exact.py (an in-model draw, as there)."""
    rng = np.random.default_rng(1000 + seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(ns, d)), jnp.float32)
    params = GPParams.init(d, lengthscale=1.0, noise=0.5)
    model = SimplexGP(SimplexGPConfig(kernel="matern32", max_cg_iters=120,
                                      num_probes=8, cg_tol_eval=1e-4))
    ls, os_, nz = model.constrained(params)
    with jax.default_matmul_precision("highest"):
        xj = jnp.concatenate([x, xs])
        kj = km.gram(km.MATERN32, xj, xj, ls, os_) + 1e-5 * jnp.eye(n + ns)
        fj = jnp.linalg.cholesky(kj) @ jnp.asarray(rng.normal(size=n + ns),
                                                   jnp.float32)
        y = fj[:n] + jnp.sqrt(nz) * jnp.asarray(rng.normal(size=n),
                                                jnp.float32)
        ep = ExactGP(km.MATERN32).posterior(x, y, xs, lengthscale=ls,
                                            outputscale=os_, noise=nz)
    post = posterior(model, params, x, y, xs, key=jax.random.PRNGKey(seed),
                     variance_rank=30)
    truth = np.asarray(fj[n:])
    sm, em = np.asarray(post.mean), np.asarray(ep.mean)
    cos = float(sm @ em / (np.linalg.norm(sm) * np.linalg.norm(em)))
    rmse_s = float(np.sqrt(np.mean((sm - truth) ** 2)))
    rmse_e = float(np.sqrt(np.mean((em - truth) ** 2)))
    log(f"  cos={cos!r} (>0.90) rmse_simplex={rmse_s!r} "
        f"rmse_exact={rmse_e!r} (<= 1.8*exact+0.05)")
    check(cos > 0.90, f"posterior mean vs exact: cos {cos}")
    check(rmse_s <= 1.8 * rmse_e + 0.05,
          f"posterior mean RMSE {rmse_s} vs exact {rmse_e}")


def one_chip(*, seed: int, epochs: int = EPOCHS,
             scale: float = 1.0) -> list[str]:
    """The one-chip path; returns the names of the phases that failed a
    check (data, fit and the lattice must pass for the rest to run)."""
    failed: list[str] = []
    ds = run_phase("data", lambda: load(DATASET, scale=scale, seed=seed))
    log(f"  {DATASET}: n_train={ds.n} d={ds.d} n_val={ds.x_val.shape[0]} "
        f"n_test={ds.x_test.shape[0]}")
    model = SimplexGP(SimplexGPConfig())
    rank = EngineConfig().variance_rank
    res = run_phase("fit", lambda: phase_fit(model, ds, epochs=epochs,
                                             seed=seed))
    params = res.params
    x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
    lat = run_phase("lattice", lambda: phase_lattice(model, params, x))
    run_phase("mvm_parity", lambda: phase_mvm_parity(
        model, lat, c=1 + model.config.num_probes, seed=seed), failed)
    run_phase("freeze", lambda: phase_freeze(model, params, x, y,
                                             cap=lat.cap, rank=rank,
                                             seed=seed), failed)
    xt = np.asarray(ds.x_test)
    queries = [jnp.asarray(np.resize(xt, (b, ds.d))) for b in QUERY_BATCHES]
    run_phase("serve", lambda: phase_serve(model, params, x, y, queries,
                                           cap=lat.cap, rank=rank,
                                           seed=seed + 1), failed)
    run_phase("exact_parity", lambda: phase_exact_parity(seed=seed), failed)
    return failed


# -- four chips --------------------------------------------------------------


def phase_mesh_mvm(model: SimplexGP, params: GPParams, x, mesh, *, c: int,
                   seed: int):
    """Sharded MVM == one chip's, with exactly one psum; rows on every
    device of the mesh."""
    st = model.stencil
    ls = model.constrained(params)[0]
    lat = lat_mod.build_lattice_auto(x / ls[None, :], spacing=st.spacing,
                                     r=st.r)
    w = jnp.asarray(st.weights, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(seed), (x.shape[0], c))
    v_rows = jax.device_put(v, NamedSharding(mesh, P("data")))
    ref = jax.jit(lambda lt, vv: lattice_mvm(lt, vv, w,
                                             backend="fused_xla"))(lat, v)

    def sharded(vv):
        return sx.sharded_lattice_mvm(lat, vv, w, mesh=mesh)

    got = jax.block_until_ready(jax.jit(sharded)(v_rows))
    counts = sx.collective_counts(sharded, v_rows)
    rel = rel_err(got, ref)
    ndev = mesh.devices.size
    placed = {s.device.id: s.data.shape for s in v_rows.addressable_shards}
    log(f"  rows per device {placed} input devices="
        f"{len(v_rows.sharding.device_set)} output devices="
        f"{len(got.sharding.device_set)} rel={rel!r} (tol {MESH_REL_TOL}) "
        f"collectives={counts}")
    check(len(v_rows.sharding.device_set) == ndev
          and len(placed) == ndev, "rows are not on every device")
    check(rel <= MESH_REL_TOL, f"sharded MVM vs one chip: {rel}")
    check(counts["psum"] == 1 and sum(counts.values()) == 1,
          f"want exactly one psum per sharded MVM: {counts}")


def phase_mesh_fit(model: SimplexGP, ds, mesh, *, epochs: int, seed: int):
    """``fit`` on the mesh; every epoch's MLL must be finite."""
    four = fit(model, jnp.asarray(ds.x_train), jnp.asarray(ds.y_train),
               x_val=jnp.asarray(ds.x_val), y_val=jnp.asarray(ds.y_val),
               epochs=epochs, lr=0.1, seed=seed, patience=epochs + 1,
               mesh=mesh)
    for h in four.history:
        log(f"  mesh fit epoch {h['epoch']} mll={h['mll']!r} "
            f"val_rmse={h['val_rmse']!r} step_s={h['seconds']:.3f}")
    check(len(four.history) == epochs, "mesh fit did not run every epoch")
    check(all(np.isfinite(h["mll"]) for h in four.history),
          "non-finite MLL on the mesh")
    return four


def phase_mesh_step(model: SimplexGP, ds, mesh, *, cap: int, steps: int,
                    seed: int):
    """The same training steps on the mesh and on one chip: at equal
    params each step's MLL and gradients must agree.

    Steps are compared at equal params and with converged solves: at the
    training tolerance (cg_tol_train = 1) CG stops after a few iterations,
    and f32 summation order can move that stop, which tests/test_multidevice
    bounds only to 2e-2. A converged step is a property of the operator,
    which the sharded path must reproduce to f32 noise.
    """
    x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
    step = jax.jit(lambda p, k, m: mll_value_and_grad(
        model, p, x, y, k, tol=STEP_CG_TOL, cap=cap, mesh=m),
        static_argnums=2)
    opt = Adam(learning_rate=0.1)
    params = GPParams.init(ds.d)
    state = opt.init(params)
    key = jax.random.PRNGKey(seed)
    worst = (0.0, 0.0)
    for i in range(steps):
        key, sub = jax.random.split(key)
        r1, r4 = step(params, sub, None), step(params, sub, mesh)
        mrel = (abs(float(r4.mll) - float(r1.mll))
                / max(1.0, abs(float(r1.mll))))
        grel = tree_rel_err(r4.grads, r1.grads)
        log(f"  step {i} mll one={float(r1.mll)!r} four={float(r4.mll)!r} "
            f"mll_rel={mrel!r} grads_rel={grel!r} (tol {MESH_REL_TOL}) "
            f"cg_iters one={int(r1.cg_iters)} four={int(r4.cg_iters)}")
        worst = (max(worst[0], mrel), max(worst[1], grel))
        params, state = opt.update(r1.grads, state, params)
    check(worst[0] <= MESH_REL_TOL and worst[1] <= MESH_REL_TOL,
          f"sharded step vs one chip: mll {worst[0]} grads {worst[1]}")


def phase_mesh_serve(model: SimplexGP, params: GPParams, x, y, queries,
                     mesh, *, rank: int, seed: int):
    pred = freeze(model, params, x, y, key=jax.random.PRNGKey(seed),
                  variance_rank=rank)
    for q in queries:
        single = predict(pred, q)
        sharded = predict(pred, q, mesh=mesh)
        mrel = rel_err(sharded.mean, single.mean)
        vrel = rel_err(sharded.var, single.var)
        log(f"  b={q.shape[0]} mean_rel={mrel!r} var_rel={vrel!r}")
        check(mrel <= MESH_REL_TOL and vrel <= MESH_REL_TOL,
              "replicated-table predict vs single device")
    fn = jax.shard_map(lambda p, qq: _predict_core(p, qq,
                                                   backend="slice_xla"),
                       mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=P("data"), check_vma=False)
    counts = sx.collective_counts(fn, pred, queries[0])
    log(f"  serving collectives={counts}")
    check(sum(counts.values()) == 0, f"serving collectives: {counts}")


def four_chips(*, seed: int, epochs: int = EPOCHS,
               scale: float = 1.0) -> list[str]:
    """The 4-chip path; returns the names of the phases that failed a
    check (data and the mesh fit must pass for the rest to run)."""
    failed: list[str] = []
    mesh = sx.data_mesh(4)
    ds = run_phase("data", lambda: load(DATASET, scale=scale, seed=seed))
    log(f"  {DATASET}: n_train={ds.n} d={ds.d} mesh={dict(mesh.shape)}")
    model = SimplexGP(SimplexGPConfig())
    res = run_phase("mesh_fit", lambda: phase_mesh_fit(
        model, ds, mesh, epochs=epochs, seed=seed))
    run_phase("mesh_step", lambda: phase_mesh_step(
        model, ds, mesh, cap=res.history[0]["cap"], steps=epochs,
        seed=seed), failed)
    x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
    run_phase("mesh_mvm", lambda: phase_mesh_mvm(
        model, res.params, x, mesh, c=1 + model.config.num_probes,
        seed=seed), failed)
    xt = np.asarray(ds.x_test)
    queries = [jnp.asarray(np.resize(xt, (b, ds.d))) for b in (1000, 4096)]
    run_phase("mesh_serve", lambda: phase_mesh_serve(
        model, res.params, x, y, queries, mesh,
        rank=EngineConfig().variance_rank, seed=seed), failed)
    for dev in mesh.devices.flat:
        stats = dev.memory_stats() or {}
        log(f"  device {dev.id} bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return failed


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        run = one_chip if args.chips == 1 else four_chips
        failed = run(seed=args.seed)
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    if failed:
        print(f"chip_smoke: FAILED checks: {failed}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"total wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={compile_seconds(t0):.3f} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
