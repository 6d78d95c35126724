"""Elastic training soak: scripted kill/shrink/regrow vs an uninterrupted run.

The elastic-training acceptance test (DESIGN.md §16). Every life is a REAL
training process (``python -m repro.launch.elastic_gp --worker``) whose
device count the driver sets via ``--xla_force_host_platform_device_count``
— killing a life and restarting it on fewer devices is exactly what losing
half the mesh looks like from the checkpoint layer's point of view. All
lives of a scenario share one checkpoint directory; ``fit(resume=True)``
picks up the newest valid generation.

Three scenarios:

  baseline    one uninterrupted life on 8 devices — the reference
              trajectory (final MLL, final-params digest);
  bitcompat   kill at a scripted epoch on 8 devices, restart on the SAME
              8 devices: the finished run must be bit-identical to the
              baseline (PR 7's resume guarantee, now under a mesh) and
              lose <= ckpt_every epochs to the kill;
  elastic     kill on 8 -> resume on 4 (shrink, uneven 300/4-per-device
              rows exercised on the 8-dev lives via ghost padding) ->
              kill on 4 -> regrow to 8 with a transient in-step exception
              (absorbed as a retry) and a wedged step (StepWatchdog
              breach: checkpoint + early return) -> final life completes.
              Each event loses <= ckpt_every epochs; the final MLL lands
              within a tolerance fence of the baseline (f32 reduction
              order differs across mesh sizes, so bitwise equality is
              only promised for same-mesh resume).

``trend_check`` ENFORCES the summary invariants: zero scripted faults
unfired, max steps lost <= ckpt_every, same-mesh bit-compat, regrow
success, MLL within the fence.

    PYTHONPATH=src python -m benchmarks.fig_elastic
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

N, D, N_VAL = 300, 2, 64  # 300 % 8 != 0: every 8-device life pads rows
EPOCHS = 24
CKPT_EVERY = 4
KILL_EXIT = 17  # runtime/faults.kill_if_armed's scripted exit code
MLL_FENCE_REL = 0.05


def _run_life(spec: dict, *, devices: int,
              timeout_s: float = 900.0) -> tuple[int, dict | None, float]:
    """One worker life under ``devices`` virtual CPUs; returns
    (exit_code, report|None, wall_s)."""
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root), env.get("PYTHONPATH", "")])
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={devices}").strip()
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.elastic_gp", "--worker",
         json.dumps(spec)],
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


def _resume_point(ckpt_dir: pathlib.Path) -> int | None:
    """The epoch the NEXT life of this scenario will resume from (its
    newest valid checkpoint). Needed for the steps-lost arithmetic of a
    KILLED life: ``os._exit`` means the victim never prints a report, so
    the driver reads the same ``latest_valid_step`` the successor's
    ``fit(resume=True)`` will."""
    from repro.runtime.checkpoint import CheckpointManager
    return CheckpointManager(str(ckpt_dir)).latest_valid_step()


def run_elastic(root: str | pathlib.Path, *, epochs: int = EPOCHS,
                ckpt_every: int = CKPT_EVERY, seed: int = 0,
                timeout_s: float = 900.0) -> dict:
    """The full scripted kill/shrink/regrow schedule; returns the
    BENCH_elastic payload (also usable at reduced ``epochs`` by the
    tier-1 ``elastic`` test lane). Requires ``epochs >= 20`` so every
    scripted event lands inside the run."""
    assert epochs >= 20, "schedule needs >= 20 epochs"
    root = pathlib.Path(root)
    base = {"seed": seed, "n": N, "d": D, "n_val": N_VAL,
            "epochs": epochs, "ckpt_every": ckpt_every}
    lives = []
    errors = []

    def life(name: str, scenario_dir: str, spec: dict, *, devices: int,
             expect_kill: bool = False) -> dict:
        ckpt_dir = root / scenario_dir
        ckpt_dir.mkdir(parents=True, exist_ok=True)
        code, report, wall = _run_life(
            dict(base, ckpt_dir=str(ckpt_dir), **spec), devices=devices,
            timeout_s=timeout_s)
        row = {"name": name, "devices": devices, "exit_code": code,
               "killed": code == KILL_EXIT, "wall_s": round(wall, 3),
               "report": report}
        if expect_kill != (code == KILL_EXIT):
            errors.append(f"{name}: expected killed={expect_kill}, "
                          f"got exit {code}")
        lives.append(row)
        return row

    # -- scenario A: uninterrupted reference on 8 devices -------------------
    a = life("baseline", "a", {}, devices=8)

    # -- scenario B: same-mesh kill + resume must be bit-compatible ---------
    # kill fires on the 15th epoch iteration (epoch 14): epochs 0..13
    # completed, cadence checkpoints at 3/7/11 -> resume loses 13-11 = 2
    b_kill_epoch = 14
    life("b_kill", "b",
         {"faults": [{"site": "fit", "kind": "kill",
                      "at": b_kill_epoch + 1, "note": "device loss"}]},
         devices=8, expect_kill=True)
    b2 = life("b_resume_same_mesh", "b", {}, devices=8)

    # -- scenario C: shrink 8 -> 4, then regrow 4 -> 8 ----------------------
    # C1 dies at epoch 10 (epochs 0..9 done, checkpoints 3/7 -> lose 2)
    c1_kill_epoch = 10
    life("c_kill_on_8", "c",
         {"faults": [{"site": "fit", "kind": "kill",
                      "at": c1_kill_epoch + 1, "note": "device loss"}]},
         devices=8, expect_kill=True)
    # C2 resumes on 4 devices from epoch 7, dies at its 7th epoch
    # iteration (epoch 14): 8..13 done, cadence checkpoint 11 -> lose 2.
    # Probe the resume point BEFORE each killed life: the victim cannot
    # report it (os._exit), the checkpoint dir can.
    c2_resume = _resume_point(root / "c")
    life("c_shrink_to_4", "c",
         {"faults": [{"site": "fit", "kind": "kill", "at": 7,
                      "note": "device loss"}]},
         devices=4, expect_kill=True)
    c3_resume = _resume_point(root / "c")
    # C3 regrows to 8. In-step executions of this life: #1/#2 warm the
    # watchdog window (#1 carries jit compile, which fattens the median
    # — deliberate, it keeps the retry epoch under the deadline), #3
    # raises (transient -> retried as #4, same epoch), #5 sleeps 12s ->
    # breach -> checkpoint + early return. 12s because the deadline is
    # 2x the window median (compile-heavy, a few seconds here): the
    # wedge must clear it on any plausible host.
    c3 = life("c_regrow_to_8_faulty", "c",
              {"faults": [
                  {"site": "fit_step", "kind": "exception", "at": 3,
                   "note": "transient step failure"},
                  {"site": "fit_step", "kind": "slow", "at": 5,
                   "seconds": 12.0, "note": "wedged collective"}],
               "watchdog": {"window": 4, "multiplier": 2.0,
                            "min_deadline": 1.0}},
              devices=8)
    c4 = life("c_finish_on_8", "c", {}, devices=8)

    # -- summary invariants (trend_check ENFORCES these) --------------------
    def _resumed(row):
        return (row["report"] or {}).get("resumed_from_epoch")

    # steps lost per event = last epoch completed before the event minus
    # the epoch the next life resumed from (kill positions are scripted,
    # so the completed count is known; breach epochs come from the report)
    losses = {
        "b_kill": (b_kill_epoch - 1) - _resumed(b2),
        "c_kill_on_8": (c1_kill_epoch - 1) - c2_resume,
        "c_kill_on_4": (c2_resume + 7 - 1) - c3_resume,
        "c_watchdog_breach": (c3["report"]["last_epoch"] or 0)
        - _resumed(c4),
    }
    scripted = 5  # 3 kills + 1 transient exception + 1 wedge
    fired = (sum(1 for lf in lives if lf["killed"])
             + len(c3["report"]["fired"]))
    bitcompat = (
        a["report"]["params_digest"] == b2["report"]["params_digest"]
        and a["report"]["final_mll"] == b2["report"]["final_mll"])
    regrow_ok = (c4["report"] is not None and c4["report"]["devices"] == 8
                 and c4["report"]["last_epoch"] == epochs - 1
                 and c4["report"]["interrupted"] is None)
    mll_rel = (abs(c4["report"]["final_mll"] - a["report"]["final_mll"])
               / max(1.0, abs(a["report"]["final_mll"])))
    if len(c3["report"]["retries"]) != 1:
        errors.append(f"expected 1 transient retry in c3, got "
                      f"{c3['report']['retries']}")
    if c3["report"]["interrupted"] != "watchdog_breach":
        errors.append(f"c3 should end on a watchdog breach, got "
                      f"{c3['report']['interrupted']!r}")

    payload = {
        "figure": "fig_elastic",
        "n": N, "d": D, "epochs": epochs, "ckpt_every": ckpt_every,
        "lives": lives,
        "steps_lost": losses,
        "summary": {
            "lives": len(lives),
            "kills": sum(1 for lf in lives if lf["killed"]),
            "scripted_faults": scripted,
            "fired_faults": fired,
            "all_faults_fired": fired >= scripted,
            "max_steps_lost": max(losses.values()),
            "ckpt_every": ckpt_every,
            "same_mesh_bitcompat": bool(bitcompat),
            "regrow_ok": bool(regrow_ok),
            "mesh_sizes": sorted({lf["devices"] for lf in lives}),
            "final_mll_baseline": a["report"]["final_mll"],
            "final_mll_elastic": c4["report"]["final_mll"],
            "mll_rel_err": round(mll_rel, 6),
            "mll_fence": MLL_FENCE_REL,
            "errors": errors,
        },
    }
    return payload


def main():
    from benchmarks.common import emit, require_cpu_rehearsal, write_json
    require_cpu_rehearsal("fig_elastic")
    with tempfile.TemporaryDirectory(prefix="elastic_ckpt_") as td:
        payload = run_elastic(td)
    s = payload["summary"]
    emit(f"fig_elastic/n{N}_d{D}_e{payload['epochs']}", None,
         f"lives={s['lives']} kills={s['kills']} "
         f"faults={s['fired_faults']}/{s['scripted_faults']} "
         f"lost<={s['max_steps_lost']}(ckpt_every={s['ckpt_every']}) "
         f"bitcompat={s['same_mesh_bitcompat']} regrow={s['regrow_ok']} "
         f"mll_rel={s['mll_rel_err']} errors={len(s['errors'])}")
    write_json("BENCH_elastic.json", payload)
    if (s["errors"] or not s["all_faults_fired"]
            or s["max_steps_lost"] > s["ckpt_every"]
            or not s["same_mesh_bitcompat"] or not s["regrow_ok"]
            or s["mll_rel_err"] > s["mll_fence"]):
        raise SystemExit("fig_elastic: elastic invariant violated: "
                         + json.dumps(s))


if __name__ == "__main__":
    main()
