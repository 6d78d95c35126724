"""Minimal serving walkthrough: freeze once, then run the engine.

Train once, freeze once, then serve query batches at O(d^2) per query —
no lattice build, no CG solve, cost independent of n (DESIGN.md §12).
The second half runs the same Predictor through the fault-tolerant
serving engine (DESIGN.md §13): queries against a hot-swappable
registry, warm background refreshes when new data lands, health/
staleness reporting. The final act makes the state DURABLE (§14):
persist the Predictor to a generation store, kill the process
mid-persist, and warm-boot a fresh engine from disk — no training, no
freeze, no data loss beyond the generation being written.

    PYTHONPATH=src python examples/serve_minimal.py
"""
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, fit, freeze,
                      posterior)
from repro.gp.serve import predict
from repro.launch import EngineConfig, GPServeEngine, PredictorStore
from repro.runtime.faults import corrupt_checkpoint
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# --- data: a smooth function of 4 inputs + noise ---------------------------
rng = np.random.default_rng(0)
n, d = 2000, 4
x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
f = jnp.sin(2 * x[:, 0]) + 0.5 * jnp.cos(x[:, 1] * x[:, 2]) + 0.3 * x[:, 3]
y = f + 0.1 * jnp.asarray(rng.normal(size=n), jnp.float32)
x_tr, y_tr = x[:1400], y[:1400]
x_val, y_val = x[1400:1700], f[1400:1700]

model = SimplexGP(SimplexGPConfig(kernel="matern32"))

# --- train (once) ----------------------------------------------------------
result = fit(model, x_tr, y_tr, x_val=x_val, y_val=y_val, epochs=10, lr=0.1)
params = result.best_params

# --- freeze (once): solves + one blur sweep -> immutable Predictor ---------
t0 = time.perf_counter()
pred = freeze(model, params, x_tr, y_tr, key=jax.random.PRNGKey(0),
              variance_rank=20)
print(f"freeze: {time.perf_counter() - t0:.2f}s  "
      f"(tables {pred.tables.shape}, {pred.tables.nbytes / 1024:.0f} KB, "
      f"hash index {pred.index.hcap} slots, "
      f"CG converged={bool(pred.cg_converged)} "
      f"in {int(pred.cg_iterations)} iters)")

# --- serve: batches pad to fixed buckets; first call per bucket compiles ---
queries = jnp.asarray(rng.normal(size=(200, d)), jnp.float32)
out = predict(pred, queries)  # warm-up / compile for the 256 bucket
t0 = time.perf_counter()
out = jax.block_until_ready(predict(pred, queries))
dt = time.perf_counter() - t0
print(f"serve: {dt * 1e3:.2f} ms / {queries.shape[0]} queries "
      f"({dt / queries.shape[0] * 1e6:.1f} us each)")

# miss_mass is the fidelity diagnostic: barycentric weight on lattice
# vertices the frozen model never saw. 0 = fully in-lattice; near 1 =
# the prediction is mostly prior. The engine below tracks it for you.
frac_clean = float(jnp.mean((out.miss_mass == 0).astype(jnp.float32)))
print(f"miss_mass: {frac_clean:.0%} of queries fully in-lattice, "
      f"mean mass {float(jnp.mean(out.miss_mass)):.3f}")

# --- sanity: the frozen path tracks the full posterior ---------------------
# The gap at the DEFAULT eval tolerance is dominated by CG stopping noise
# (both paths solve to rel. residual cg_tol_eval=1e-2, on marginally
# different lattices); with a converged-CG config it drops to ~1e-6 —
# see BENCH_serve.json's mean_parity column and tests/test_serve.py.
post = posterior(model, params, x_tr, y_tr, queries,
                 key=jax.random.PRNGKey(0), variance_rank=20)
clean = np.asarray(out.miss_mass) == 0
gap = np.abs(np.asarray(out.mean) - np.asarray(post.mean))[clean]
print(f"frozen vs posterior mean gap on in-lattice queries: "
      f"max {gap.max():.2e}  (~cg_tol_eval; see BENCH_serve.json "
      "mean_parity for the converged-CG figure)")

# --- the serving engine: hot swaps, warm refreshes, health -----------------
# In production you run the engine, not bare predict(): it validates
# every candidate before publishing, retries transient query faults,
# serves full-miss queries from the prior, and keeps the last-good
# Predictor serving if a refresh fails or wedges (launch/serve_gp.py).
with GPServeEngine(model, params, x_tr, y_tr, key=jax.random.PRNGKey(1),
                   config=EngineConfig(variance_rank=20)) as eng:
    res = eng.query(queries)
    print(f"engine: version {res.version} served {queries.shape[0]} "
          f"queries, {int(res.fallback.sum())} from the prior-fallback "
          f"lane, stale={res.stale}")

    # new observations arrive: a y-only refresh rides the warm lane
    # (cached lattice, reused hash index, CG warm-started from the old
    # alpha) and hot-swaps atomically — in-flight queries are untouched
    y_new = y_tr + 0.05 * jnp.sin(x_tr[:, 0])
    eng.submit_refresh(y=y_new)
    eng.refresh_now()  # or background=True for a worker thread
    h = eng.health()
    print(f"refresh: version {eng.version} in {h.last_refresh_s * 1e3:.0f} "
          f"ms (warm; CG {int(eng.predictor().cg_iterations)} iters), "
          f"status={h.status}, staleness={h.staleness:.3f}")

# --- durable state: save -> kill -> warm boot (DESIGN.md §14) --------------
# In production the frozen Predictor outlives the process: the engine
# persists every published version to a generation store (atomic
# tmp+rename, per-blob checksums), and a restarted engine boots from
# the newest generation that passes the full load gate — checksums,
# validate_predictor, and an in-lattice self-probe — skipping anything
# damaged. Here we persist two generations, vandalize the newest on
# disk (a stand-in for a torn write or a kill mid-persist: both leave
# either an ignored *.tmp orphan or a detectably damaged directory),
# and watch the warm boot fall back one generation instead of serving
# garbage or re-training.
with tempfile.TemporaryDirectory() as root:
    store = PredictorStore(root, keep_last=3)
    with GPServeEngine(model, params, x_tr, y_tr,
                       key=jax.random.PRNGKey(1),
                       config=EngineConfig(variance_rank=20),
                       store=store, model_name="demo") as eng:
        eng.query(queries)                    # cold boot: store was empty
        eng.submit_refresh(y=y_new)
        eng.refresh_now()                     # publish + persist gen 2
        eng.wait_persisted()                  # persistence is async
        print(f"persisted generations on disk: {store.generations('demo')}")

    # "kill": the process is gone; only the store survives. Damage the
    # newest generation the way a real crash or disk fault would.
    corrupt_checkpoint(store.path("demo", store.generations("demo")[-1]),
                       "bitflip")

    t0 = time.perf_counter()
    with GPServeEngine(model, params, x_tr, y_tr,
                       key=jax.random.PRNGKey(2),
                       config=EngineConfig(variance_rank=20),
                       store=store, model_name="demo") as eng2:
        res = eng2.query(queries)             # no fit, no freeze, no CG
        h = eng2.health()
        print(f"warm boot: {time.perf_counter() - t0:.2f}s to first answer "
              f"(mode={h.boot_mode}, generation={h.boot_generation}, "
              f"skipped {h.boot_skipped} damaged), version {res.version}")
