"""Crash-recovery soak: scripted kills and disk corruption vs warm boot.

The durability acceptance test (DESIGN.md §14). Each cycle runs a REAL
serving process (a subprocess: an injected kill is ``os._exit``, so the
victim must not be the benchmark itself) against one shared on-disk
``PredictorStore``, then the driver inspects the store and restarts:

  clean cycle       cold boot, persist, refresh, persist, clean exit —
                    establishes durable generations;
  kill cycles       a ``FaultInjector`` kill armed at the persistence
                    sites: ``persist_before_publish`` (process dies with
                    the tmp dir written but never renamed — the store
                    must be byte-identical to before) and
                    ``persist_after_publish`` (dies right after the
                    atomic rename — the new generation must be durable);
  corruption cycles the driver damages the newest generation on disk
                    (``runtime/faults.corrupt_checkpoint``: truncate,
                    bitflip, missing blob, stale manifest) before the
                    restart — warm boot must DETECT it, fall back one
                    generation, keep serving, and persist a fresh good
                    generation over it.

Measured per restart: recovery time (engine construction + first valid
query, plus driver wall clock including interpreter/jax startup),
boot mode/generation, generations lost (published in memory but not
durable — the atomic-persist design bounds this at <= 1), and invalid
responses after restart (must be 0). Results land in
BENCH_recovery.json; ``trend_check`` ENFORCES the invariants and the
tier-1 ``recovery`` lane replays a scaled-down schedule.

    PYTHONPATH=src python -m benchmarks.fig_recovery
    PYTHONPATH=src python -m benchmarks.fig_recovery --worker <store> <spec>
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

N, D = 400, 3
MODEL_NAME = "m"
KILL_EXIT = 17  # runtime/faults.kill_if_armed's scripted exit code


# -- worker (the process that gets killed) -----------------------------------

def _dataset(seed: int, n: int, d: int):
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = (jnp.sin(2 * x[:, 0]) + 0.4 * x[:, 1]
         + 0.05 * jnp.asarray(rng.normal(size=n), jnp.float32))
    return rng, x, y


def worker(store_dir: str, spec: dict) -> dict:
    """One serving-process life; returns the (JSON-able) cycle report.

    ``spec``: seed, n, d, queries (batches to serve after boot),
    kill (None | "persist_before_publish" | "persist_after_publish"),
    refresh (bool: submit one y-drift refresh and wait for its persist —
    the wait never returns when a kill is armed).
    """
    t_entry = time.perf_counter()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.gp import GPParams, SimplexGP, SimplexGPConfig
    from repro.launch.serve_gp import (EngineConfig, GPServeEngine,
                                       PredictorStore)
    from repro.runtime.faults import FaultInjector

    seed = int(spec.get("seed", 0))
    n = int(spec.get("n", N))
    d = int(spec.get("d", D))
    rng, x, y = _dataset(seed, n, d)
    model = SimplexGP(SimplexGPConfig(kernel="matern32"))
    params = GPParams.init(d, noise=0.1)

    fi = FaultInjector()
    if spec.get("kill"):
        fi.arm(site=spec["kill"], kind="kill", note="scripted crash")
    store = PredictorStore(store_dir, keep_last=3, keep_best=1)
    cfg = EngineConfig(variance_rank=4, refresh_min_deadline_s=30.0)
    eng = GPServeEngine(model, params, x, y,
                        key=jax.random.PRNGKey(seed + 1), config=cfg,
                        store=store, model_name=MODEL_NAME, faults=fi)
    boot_s = time.perf_counter() - t_entry
    h0 = eng.health()

    # first valid query = the moment the restarted process is SERVING
    xs = jnp.asarray(np.asarray(x)[rng.integers(0, n, 32)])
    res = eng.query(xs)
    first_query_s = time.perf_counter() - t_entry
    invalid = 0
    for _ in range(int(spec.get("queries", 5))):
        xs = jnp.asarray(np.asarray(x)[rng.integers(0, n, 32)])
        res = eng.query(xs)
        m, v = np.asarray(res.mean), np.asarray(res.var)
        if not (np.isfinite(m).all() and np.isfinite(v).all()
                and (v >= 0).all()):
            invalid += 1

    versions_published = eng.version
    if spec.get("refresh", True):
        eng.submit_refresh(y=y + 0.02 * jnp.sin(x[:, 0]))
        eng.refresh_now()
        versions_published = eng.version
        # with a kill armed at a persistence site the process dies INSIDE
        # this wait (the persist thread hits the site) — nothing below runs
        eng.wait_persisted(timeout_s=120.0)

    h = eng.health()
    eng.close()
    return {
        "boot_mode": h0.boot_mode,
        "boot_generation": h0.boot_generation,
        "boot_skipped": h0.boot_skipped,
        "boot_s": round(boot_s, 3),
        "first_query_s": round(first_query_s, 3),
        "invalid_responses": invalid,
        "versions_published": versions_published,
        "persists_ok": h.persists_ok,
        "persists_failed": h.persists_failed,
        "durable_gens": store.generations(MODEL_NAME),
    }


# -- driver ------------------------------------------------------------------

def _run_worker(store_dir: pathlib.Path, spec: dict, *,
                timeout_s: float = 300.0) -> tuple[int, dict | None, float]:
    """Launch one worker life; returns (exit_code, report|None, wall_s)."""
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root), env.get("PYTHONPATH", "")])
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig_recovery", "--worker",
         str(store_dir), json.dumps(spec)],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=str(root))
    wall = time.perf_counter() - t0
    report = None
    if proc.returncode == 0:
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
        if not lines:
            raise RuntimeError(f"worker exited 0 with no report:\n"
                               f"{proc.stderr[-2000:]}")
        report = json.loads(lines[-1])
    elif proc.returncode != KILL_EXIT:
        raise RuntimeError(
            f"worker died with unexpected exit {proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    return proc.returncode, report, wall


def run_recovery(store_root: str | pathlib.Path, *,
                 corruption_kinds: tuple[str, ...] | None = None,
                 queries: int = 5, seed: int = 0,
                 timeout_s: float = 300.0) -> dict:
    """The full scripted kill/restart/corruption schedule; returns the
    BENCH_recovery payload (also usable at reduced scale by the tier-1
    ``recovery`` test lane)."""
    from repro.launch.serve_gp import PredictorStore
    from repro.runtime.faults import CORRUPTION_KINDS, corrupt_checkpoint

    if corruption_kinds is None:
        corruption_kinds = CORRUPTION_KINDS
    store_dir = pathlib.Path(store_root)
    store = PredictorStore(store_dir)
    base = {"seed": seed, "n": N, "d": D, "queries": queries}

    cycles = []

    def cycle(name: str, spec: dict, *, corrupt: str | None = None,
              expect_kill: bool = False) -> dict:
        gens_before = store.generations(MODEL_NAME)
        corrupted_gen = None
        if corrupt is not None:
            corrupted_gen = gens_before[-1]
            corrupt_checkpoint(store.path(MODEL_NAME, corrupted_gen),
                               corrupt)
        code, report, wall = _run_worker(store_dir, dict(base, **spec),
                                         timeout_s=timeout_s)
        gens_after = store.generations(MODEL_NAME)
        row = {"name": name, "spec": spec, "exit_code": code,
               "killed": code == KILL_EXIT,
               "corruption": corrupt, "corrupted_gen": corrupted_gen,
               "gens_before": gens_before, "gens_after": gens_after,
               "wall_s": round(wall, 3), "report": report}
        if expect_kill != (code == KILL_EXIT):
            row["error"] = (f"expected killed={expect_kill}, "
                            f"got exit {code}")
        cycles.append(row)
        return row

    # 1. clean cold start: establishes durable generations
    cycle("cold_clean", {"kill": None, "refresh": True})
    # 2. kill BEFORE the atomic rename: store must be unchanged
    cycle("kill_before_publish", {"kill": "persist_before_publish",
                                  "refresh": True}, expect_kill=True)
    # 3. restart: warm boot; at most ONE generation (the unpersisted
    #    refresh of cycle 2) may be lost
    cycle("recover_after_kill_before", {"kill": None, "refresh": True})
    # 4. kill AFTER the atomic rename: the new generation must be durable
    cycle("kill_after_publish", {"kill": "persist_after_publish",
                                 "refresh": True}, expect_kill=True)
    # 5. restart: warm boot serves the generation persisted mid-kill
    cycle("recover_after_kill_after", {"kill": None, "refresh": True})
    # 6+. corruption cycles: damage the newest generation, restart —
    #     detection + one-generation fallback + re-persist a good one
    for kind in corruption_kinds:
        cycle(f"corrupt_{kind}", {"kill": None, "refresh": True},
              corrupt=kind)

    # -- summary invariants (trend_check ENFORCES these) --------------------
    # a killed life published exactly ONE in-memory refresh beyond its
    # boot generation; it is lost iff no new generation reached disk
    # before the kill (kill-before-publish: lost=1; after: lost=0)
    lost_max = 0
    for c in cycles:
        if c["killed"]:
            new_gens = set(c["gens_after"]) - set(c["gens_before"])
            lost_max = max(lost_max, 0 if new_gens else 1)
    restarts = [c for c in cycles[1:] if c["report"] is not None]
    # "detected" = the damaged generation was rejected at boot (skipped
    # >= 1 counts cold boots too — the store may run dry of valid gens)
    # and was never the one served
    corruption_rows = [c for c in cycles if c["corruption"]]
    all_detected = all(
        c["report"] is not None and c["report"]["boot_skipped"] >= 1
        and c["report"]["boot_generation"] != c["corrupted_gen"]
        for c in corruption_rows)
    recovery_s = [c["report"]["first_query_s"] for c in restarts]
    payload = {
        "figure": "fig_recovery",
        "n": N, "d": D, "model": MODEL_NAME,
        "cycles": cycles,
        "summary": {
            "cycles": len(cycles),
            "kills": sum(c["killed"] for c in cycles),
            "corruptions": len(corruption_rows),
            "corruptions_detected": sum(
                1 for c in corruption_rows
                if c["report"] and c["report"]["boot_skipped"] >= 1),
            "all_corruptions_detected": bool(all_detected),
            "warm_boots": sum(1 for c in restarts
                              if c["report"]["boot_mode"] == "warm"),
            "max_generations_lost": lost_max,
            "invalid_responses": sum(c["report"]["invalid_responses"]
                                     for c in restarts),
            "mean_recovery_s": round(sum(recovery_s)
                                     / max(len(recovery_s), 1), 3),
            "max_recovery_s": round(max(recovery_s, default=0.0), 3),
            "errors": [c["error"] for c in cycles if "error" in c],
        },
    }
    return payload


def main():
    from benchmarks.common import emit, require_cpu_rehearsal, write_json
    require_cpu_rehearsal("fig_recovery")
    with tempfile.TemporaryDirectory(prefix="recovery_store_") as td:
        payload = run_recovery(td)
    s = payload["summary"]
    emit(f"fig_recovery/n{N}_d{D}", None,
         f"cycles={s['cycles']} kills={s['kills']} "
         f"corruptions={s['corruptions']}/{s['corruptions_detected']}det "
         f"lost<={s['max_generations_lost']} "
         f"invalid={s['invalid_responses']} "
         f"warm_boots={s['warm_boots']} "
         f"recovery mean={s['mean_recovery_s']}s "
         f"max={s['max_recovery_s']}s errors={len(s['errors'])}")
    write_json("BENCH_recovery.json", payload)
    if s["errors"] or s["invalid_responses"] or not \
            s["all_corruptions_detected"] or s["max_generations_lost"] > 1:
        raise SystemExit("fig_recovery: durability invariant violated: "
                         + json.dumps(s))


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--worker":
        out = worker(sys.argv[2], json.loads(sys.argv[3]))
        print(json.dumps(out))  # last line: the report the driver parses
    else:
        main()
