"""Shared benchmark helpers: timing + CSV emission + JSON artifacts."""
from __future__ import annotations

import json
import os
import pathlib
import time

import jax

# machine-readable benchmark artifacts land next to the repo root so the
# perf trajectory can be tracked across PRs (BENCH_*.json)
ARTIFACT_DIR = pathlib.Path(os.environ.get(
    "BENCH_ARTIFACT_DIR", pathlib.Path(__file__).resolve().parents[1]))

# default subsample so `python -m benchmarks.run` finishes on 1 CPU core;
# crank BENCH_SCALE up for larger runs.
SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (blocks on device results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def require_cpu_rehearsal(name: str) -> None:
    """Refuse to run a CPU rehearsal on a TPU.

    fig_scaling, fig_elastic and fig_recovery start child processes that
    run on virtual CPU devices (``--xla_force_host_platform_device_count``,
    which only the CPU backend honours). On a TPU host this process holds
    the chip, so a child that reaches for it fails or hangs.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{name} is a CPU rehearsal on virtual devices and does not run "
            "on a TPU; run it with JAX_PLATFORMS=cpu")


def emit(name: str, seconds: float | None, derived: str):
    us = f"{seconds * 1e6:.1f}" if seconds is not None else ""
    print(f"{name},{us},{derived}")


def write_json(name: str, payload: dict) -> pathlib.Path:
    """Write a BENCH_*.json artifact (adds host metadata)."""
    out = dict(payload)
    out.setdefault("host", {})
    out["host"].update({
        "jax_backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "device_count": jax.device_count(),
        "bench_scale": SCALE,
    })
    path = ARTIFACT_DIR / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {path}")
    return path
