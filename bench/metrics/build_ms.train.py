"""Lattice build: median milliseconds of isolated jitted builds of the
training points at the reset-start lengthscales and the step's cap."""
import time

import jax
import numpy as np

CALLS = 20


def read(rec):
    from repro.core.lattice import build_lattice
    o = rec["objects"]
    model = o["model"]
    st = model.stencil
    ls = model.constrained(o["params0"])[0]
    z = o["x"] / ls[None, :]
    fn = jax.jit(lambda zz: build_lattice(
        zz, spacing=st.spacing, r=st.r, cap=o["cap"],
        backend=model.config.build_backend))
    jax.block_until_ready(fn(z))
    times = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(z))
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))
