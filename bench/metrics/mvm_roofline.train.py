"""Lattice MVM: share of the roofline of isolated jitted MVMs at the
training width c = 1 + num_probes, on the lattice of the training points
at the reset-start lengthscales. The least time comes from
``bench/counts.mvm`` (occupied vertices m, compulsory bytes) and the
chip's peaks; the time is the median of the calls."""
import time

import jax
import numpy as np

from bench import counts

CALLS = 20


def read(rec):
    from repro.core.lattice import build_lattice
    from repro.kernels.blur.ops import lattice_mvm
    o = rec["objects"]
    model = o["model"]
    st = model.stencil
    ls = model.constrained(o["params0"])[0]
    lat = jax.jit(lambda zz: build_lattice(
        zz, spacing=st.spacing, r=st.r, cap=o["cap"],
        backend=model.config.build_backend))(o["x"] / ls[None, :])
    v = jax.random.normal(jax.random.PRNGKey(0), (o["n"], o["c"]))
    taps = tuple(st.weights)
    fn = jax.jit(lambda lt, vv: lattice_mvm(lt, vv, taps=taps,
                                            symmetrize=model.config.symmetrize,
                                            backend=model.config.backend))
    jax.block_until_ready(fn(lat, v))
    times = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(lat, v))
        times.append(time.perf_counter() - t0)
    flops, nbytes = counts.mvm(o["n"], o["d"], o["m"], o["c"], st.r,
                               model.config.symmetrize)
    return 100.0 * counts.least_seconds(flops, nbytes, rec["peak"]) / float(
        np.median(times))
