"""Fig 6: Simplex-GP MVM wall time vs exact MVM, across n — per backend.

The paper's claim: lattice MVMs overtake exact MVMs as n grows (10x at
n ~ 1e6 on GPU). On this CPU host the crossover appears at smaller n; the
benchmark reports both times and the speedup so the TREND is the check.
Amortization matters: the lattice build is done once per hyperparameter
setting, so per-MVM cost excludes the build (reported separately), exactly
like the paper's CG-loop usage.

Beyond the paper figure this also races the backend tiers of the fused
lattice-MVM rework (kernels/blur/ops.py):

  * per_direction — the pre-fusion path (segment_sum splat + one blur
    dispatch per direction + slice), jitted, on the same lattice;
  * fused — the policy-chosen fused backend (single fused kernel/program
    with the scatter-free sorted-segment splat).

Both run on ONE auto-capped lattice so the comparison isolates the fused
rework, and the fused output is checked against the op-for-op reference
(kernels/blur/ref.py). Results land in BENCH_mvm.json (per-backend µs/MVM,
build seconds, m) so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SCALE, emit, timeit, write_json
from repro.core import filtering
from repro.core import kernels_math as km
from repro.core.exact import chunked_mvm
from repro.core.lattice import build_lattice_auto
from repro.core.stencil import make_stencil
from repro.kernels.blur import ref as blur_ref
from repro.kernels.blur.ops import resolve_backend

SIZES = [1000, 4000, 16000, 64000]
D = 8
# exact O(n^2 d) MVMs get prohibitive on CPU well before the paper's n;
# the lattice backends are what must scale, so cap the oracle column.
EXACT_MAX_N = 16000


def main():
    rng = np.random.default_rng(0)
    st = make_stencil("matern32", 1)
    taps = tuple(st.weights)
    w = jnp.asarray(st.weights, jnp.float32)
    rows = []
    for n in [int(s * SCALE) for s in SIZES]:
        x = jnp.asarray(rng.normal(size=(n, D)) * 0.3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(n, 1)), jnp.float32)
        v = v / jnp.linalg.norm(v)  # unit RHS: abs err is scale-honest

        t0 = time.perf_counter()
        lat = build_lattice_auto(x, spacing=st.spacing, r=st.r)
        jax.block_until_ready(lat.nbr)
        build_s = time.perf_counter() - t0
        m = int(lat.m)

        fused_backend = resolve_backend("auto")
        per_dir = jax.jit(lambda lt, vv: filtering.filter_mvm(
            lt, vv, w, backend="xla"))
        fused = jax.jit(lambda lt, vv: filtering.filter_mvm(
            lt, vv, w, backend=fused_backend, taps=taps))

        per_dir_s = timeit(per_dir, lat, v)
        fused_s = timeit(fused, lat, v)

        # correctness: fused vs the op-for-op reference oracle
        algo = "hs" if fused_backend == "fused_pallas" else "scan"
        ref_out = blur_ref.filter_ref(lat, v, w, splat_algo=algo)
        err = float(jnp.max(jnp.abs(fused(lat, v) - ref_out)))

        exact_s = None
        if n <= EXACT_MAX_N * SCALE:
            exact_s = timeit(
                jax.jit(lambda xx, vv: chunked_mvm(km.MATERN32, xx, vv,
                                                   block=1024)), x, v)

        speedup = per_dir_s / fused_s
        emit(f"fig6/n{n}", fused_s,
             f"per_direction_s={per_dir_s:.4f} fused_s={fused_s:.4f} "
             f"fused_speedup={speedup:.2f}x "
             + (f"exact_s={exact_s:.4f} " if exact_s is not None else "")
             + f"build_s={build_s:.2f} m={m} cap={lat.cap} "
             f"backend={fused_backend} max_abs_err={err:.2e}")
        rows.append({
            "n": n, "d": D, "r": st.r, "m": m, "cap": lat.cap,
            "build_s": round(build_s, 4),
            "max_abs_err_fused_vs_ref": err,
            "backends": {
                "per_direction": {"us_per_mvm": per_dir_s * 1e6,
                                  "backend": "xla"},
                "fused": {"us_per_mvm": fused_s * 1e6,
                          "backend": fused_backend},
                **({"exact": {"us_per_mvm": exact_s * 1e6}}
                   if exact_s is not None else {}),
            },
            "fused_speedup": speedup,
        })
    write_json("BENCH_mvm.json", {"figure": "fig6_mvm_speed",
                                  "kernel": "matern32", "sizes": rows})


if __name__ == "__main__":
    main()
