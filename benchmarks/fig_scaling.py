"""Multi-device scaling: sharded lattice MVM + batched multi-RHS solves.

The measurement half runs in a SUBPROCESS with
``--xla_force_host_platform_device_count=8`` (XLA reads the flag once at
backend init, and the rest of the benchmark suite must keep the 1 real
device), mirroring the tier-1 ``multidevice`` pytest lane. It reports,
per size:

  * single-device fused MVM time vs the 8-virtual-device sharded MVM
    time, and their relative error (contract: <= 1e-5);
  * the collective count of one sharded MVM from its jaxpr (contract:
    exactly ONE psum, nothing else — DESIGN.md §10);
  * the multi-RHS mBCG contract: a [y | Z] block with k probes traces
    ONE batched lattice MVM per CG iteration (the ``mvm.calls`` and
    ``mvm.cols`` counters of ``repro.obs``), and the batched block solve
    is raced against the k+1 per-column solves it replaces.

On a CPU host the 8 "devices" share the physical cores, so sharded wall
time measures overhead, not speedup — the artifact records it honestly
as ``sharded_overhead_x`` next to the error/collective contracts that
ARE hardware-independent. Results land in BENCH_scaling.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER_ENV = "REPRO_SCALING_WORKER"
_DEVICES = 8


def _worker() -> None:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import SCALE
    from repro import obs
    from repro.core import filtering, lattice as lat_mod
    from repro.core.stencil import make_stencil
    from repro.kernels.blur.ops import lattice_mvm
    from repro.sharding import simplex as sx
    from repro.solvers.cg import cg

    def timeit(fn, *args, iters=3):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    d, c, k = 3, 8, 8
    sizes = [int(n * max(SCALE, 0.1)) // _DEVICES * _DEVICES
             for n in (4096, 16384)]
    st = make_stencil("matern32", 1)
    mesh = sx.data_mesh()
    results = []
    for n in sizes:
        rng = np.random.default_rng(0)
        z = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        lat = lat_mod.build_lattice_auto(z, spacing=st.spacing, r=st.r)
        w = jnp.asarray(st.weights, jnp.float32)

        single = jax.jit(lambda vv: lattice_mvm(lat, vv, w,
                                                backend="fused_xla"))
        sharded = jax.jit(lambda vv: sx.sharded_lattice_mvm(lat, vv, w,
                                                            mesh=mesh))
        t_single = timeit(single, v)
        t_sharded = timeit(sharded, v)
        rel = float(jnp.linalg.norm(sharded(v) - single(v))
                    / jnp.linalg.norm(single(v)))
        counts = sx.collective_counts(
            lambda vv: sx.sharded_lattice_mvm(lat, vv, w, mesh=mesh), v)

        # multi-RHS mBCG contract + batched-vs-per-column race
        matvec, _ = filtering.mvm_operator(z, st, cap=lat.cap)
        op = lambda vv: matvec(vv) + 0.1 * vv
        b = jnp.asarray(rng.normal(size=(n, 1 + k)), jnp.float32)
        c0, w0 = obs.counter("mvm.calls"), obs.counter("mvm.cols")
        cg(op, b, tol=1e-2, max_iters=20)
        traced_mvms = obs.counter("mvm.calls") - c0
        traced_cols = obs.counter("mvm.cols") - w0
        t_block = timeit(lambda bb: cg(op, bb, tol=1e-2, max_iters=20)[0], b)
        t_cols = timeit(lambda bb: [
            cg(op, bb[:, i:i + 1], tol=1e-2, max_iters=20)[0]
            for i in range(1 + k)], b)

        results.append(dict(
            n=n, d=d, c=c, cap=lat.cap, m=int(lat.m),
            single_mvm_s=t_single, sharded_mvm_s=t_sharded,
            sharded_overhead_x=t_sharded / t_single,
            sharded_rel_err=rel, psums_per_mvm=counts["psum"],
            other_collectives=sum(v_ for k_, v_ in counts.items()
                                  if k_ != "psum"),
            mbcg_probes=k, mbcg_traced_mvms=traced_mvms,
            mbcg_traced_cols=traced_cols,
            cg_block_s=t_block, cg_per_column_s=t_cols,
            batched_speedup_x=t_cols / t_block,
        ))
    print(json.dumps({"devices": jax.device_count(), "results": results}))


def main() -> None:
    if os.environ.get(_WORKER_ENV) == "1":
        _worker()
        return
    from benchmarks.common import emit, require_cpu_rehearsal, write_json
    require_cpu_rehearsal("fig_scaling")

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={_DEVICES}"
                        ).strip()
    env[_WORKER_ENV] = "1"
    out = subprocess.run([sys.executable, "-m", "benchmarks.fig_scaling"],
                         env=env, capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(f"scaling worker failed:\n{out.stderr[-3000:]}")
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    payload["figure"] = "fig_scaling"
    payload["contract"] = ("one psum per sharded MVM; sharded == fused to "
                           "<=1e-5; one batched lattice MVM per mBCG "
                           "iteration for the whole [y|Z] block")
    for row in payload["results"]:
        emit(f"fig_scaling/mvm_single/n{row['n']}", row["single_mvm_s"],
             f"err{row['sharded_rel_err']:.1e}")
        emit(f"fig_scaling/mvm_sharded8/n{row['n']}", row["sharded_mvm_s"],
             f"psums{row['psums_per_mvm']}")
        emit(f"fig_scaling/cg_block/n{row['n']}", row["cg_block_s"],
             f"{row['batched_speedup_x']:.1f}x_vs_per_col")
    write_json("BENCH_scaling.json", payload)


if __name__ == "__main__":
    main()
