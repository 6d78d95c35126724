"""Compile the main path's programs for a TPU v5e that is described, not
attached.

Interpret mode runs a Pallas kernel on the CPU whether or not Mosaic, the
TPU kernel compiler, would lower it; these tests ask the TPU compiler
itself. Each one AOT-compiles, for one chip of a described ``v5e:2x2``,
what ``auto`` resolves to (the XLA tiers, on every host) at
``protein``'s published sizes (``data/synthetic_uci.SPECS``):
the lattice MVM at training width c = 1 + num_probes, the hash build and
lookup, the frozen slice at the engine's largest serving bucket, and the
jitted MLL value-and-grad step. Nothing runs, so nothing here is a
measurement.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test worker that cannot skips
this file's tests from the fixture instead of breaking collection.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lattice as lat_mod
from repro.core.filtering import slice_only
from repro.core.lattice import LatticeIndex
from repro.data.synthetic_uci import SPECS
from repro.gp import GPParams, SimplexGP, SimplexGPConfig, mll_value_and_grad
from repro.kernels.blur import ops as blur_ops
from repro.kernels.blur.fused import fused_filter_pallas
from repro.kernels.blur.kernel import (blur_direction_blocked_pallas,
                                       blur_direction_pallas)
from repro.kernels.hash import ops as hash_ops
from repro.kernels.hash.kernel import hash_insert_pallas, hash_lookup_pallas
from repro.kernels.slice import ops as slice_ops
from repro.kernels.slice.kernel import (slice_query_pallas,
                                        slice_query_tangent_pallas)
from repro.launch.serve_gp import EngineConfig

CFG = SimplexGPConfig()
MODEL = SimplexGP(CFG)
STENCIL = MODEL.stencil
TAPS = tuple(STENCIL.weights)
SPEC = SPECS["protein"]
D = SPEC["d"]
N = (4 * SPEC["n"]) // 9  # the training split of synthetic_uci.load
C_TRAIN = 1 + CFG.num_probes  # mBCG's [y | probes] block
CAP = lat_mod.suggest_capacity(N, D, STENCIL.spacing, r=STENCIL.r,
                               c=C_TRAIN)
HCAP = hash_ops.hash_capacity(CAP)
NPK = (D + 2) // 2  # packed int32 key words per vertex
M = SPEC["table3_m"]  # frozen lattice size (paper Table 3)
BUCKET = max(CFG.serve_buckets)
C_SERVE = 1 + EngineConfig().variance_rank  # [mean | LOVE root]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


def _lattice(spec):
    """Shapes of the protein training lattice, placed on the chip."""
    lat = jax.eval_shape(
        lambda z: lat_mod.build_lattice(z, spacing=STENCIL.spacing,
                                        r=STENCIL.r, cap=CAP,
                                        backend="hash_xla"),
        jax.ShapeDtypeStruct((N, D), jnp.float32))
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), lat)


def _index(spec):
    hcap = hash_ops.hash_capacity(M)
    return LatticeIndex(tkeys=spec((hcap, NPK), jnp.int32),
                        row_of_slot=spec((hcap,), jnp.int32),
                        slots=spec((M,), jnp.int32), d=D, hcap=hcap, m=M)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text()  # an executable for the described chip
    return compiled


def test_lattice_mvm_compiles(spec):
    backend = blur_ops.resolve_backend("auto")
    _compile(lambda lat, v: blur_ops.lattice_mvm(lat, v, taps=TAPS,
                                                 backend=backend,
                                                 interpret=False),
             _lattice(spec), spec((N, C_TRAIN), jnp.float32))


def test_hash_build_and_lookup_compile(spec):
    backend = hash_ops.resolve_build_backend("auto")
    _compile(lambda z: lat_mod.build_lattice(z, spacing=STENCIL.spacing,
                                             r=STENCIL.r, cap=CAP,
                                             backend=backend),
             spec((N, D), jnp.float32))
    nq = N * (D + 1)
    _compile(lambda tk, q, act: hash_ops.hash_lookup(
        tk, q, act, HCAP, backend=backend, interpret=False),
        spec((HCAP, NPK), jnp.int32), spec((nq, NPK), jnp.int32),
        spec((nq,), jnp.bool_))


def test_frozen_slice_compiles(spec):
    index = _index(spec)
    backend = slice_ops.resolve_slice_backend("auto")
    _compile(lambda idx, tab, zq: slice_only(idx, tab, zq,
                                             spacing=STENCIL.spacing,
                                             backend=backend,
                                             interpret=False),
             index, spec((M + 1, C_SERVE), jnp.float32),
             spec((BUCKET, D), jnp.float32))


def test_mll_value_and_grad_step_compiles(spec, cg_loop):
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), GPParams.init(D))
    compiled = _compile(
        lambda p, x, y, k: mll_value_and_grad(MODEL, p, x, y, k, cap=CAP),
        params, spec((N, D), jnp.float32), spec((N,), jnp.float32),
        spec((2,), jnp.uint32))
    # CG is a while loop that exits on its residuals: its condition reads
    # the (1 + num_probes,) active mask, not only the trip counter
    _, cond = cg_loop(compiled.as_text())
    assert any(re.search(rf"= pred\[{C_TRAIN}\]\S* get-tuple-element\(", ln)
               for ln in cond), cond
    # the step must fit one v5e chip's 16 GB of HBM
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


# The Pallas tiers that ``auto`` no longer picks on a TPU: an explicit
# request must still reach the TPU compiler and fail there, never fall
# back in silence. When one of these compiles, route ``auto`` to it in its
# resolve_* function (kernels/*/ops.py) and move it to the tests above.
def _refused_kernels(spec, lat):
    hcap_m = hash_ops.hash_capacity(M)
    nq = BUCKET * (D + 1)
    i32 = jnp.int32
    f32 = jnp.float32
    slice_args = (spec((hcap_m, NPK), i32), spec((hcap_m,), i32),
                  spec((M + 1, C_SERVE), f32), spec((nq, NPK), i32),
                  spec((BUCKET, D + 1), f32))
    return {
        "fused_pallas": (
            lambda lt, v: fused_filter_pallas(lt, v, TAPS),
            (lat, spec((N, C_TRAIN), jnp.float32))),
        "per_direction_pallas": (
            lambda v, nb: blur_direction_pallas(v, nb, TAPS),
            (spec((CAP + 1, C_TRAIN), f32),
             spec((CAP + 1, 2 * STENCIL.r), i32))),
        "per_direction_blocked_pallas": (
            lambda v, nb: blur_direction_blocked_pallas(v, nb, TAPS),
            (spec((CAP + 1, C_TRAIN), f32),
             spec((CAP + 1, 2 * STENCIL.r), i32))),
        "hash_insert_pallas": (
            lambda p: hash_insert_pallas(p, HCAP),
            (spec((N * (D + 1), NPK), i32),)),
        "hash_lookup_pallas": (
            hash_lookup_pallas,
            (spec((HCAP, NPK), i32), spec((N * (D + 1), NPK), i32),
             spec((N * (D + 1),), jnp.bool_))),
        "slice_pallas": (
            slice_query_pallas, slice_args + (spec((nq,), jnp.bool_),)),
        "slice_tangent_pallas": (
            slice_query_tangent_pallas,
            slice_args + (spec((BUCKET, D + 1), f32),
                          spec((nq,), jnp.bool_))),
    }


@pytest.mark.parametrize("kernel, message", [
    ("fused_pallas", "Shape mismatch in input, indices and output"),
    ("per_direction_pallas", "Shape mismatch in input, indices and output"),
    ("per_direction_blocked_pallas",
     "Shape mismatch in input, indices and output"),
    ("hash_insert_pallas", "Cannot store scalars to VMEM"),
    ("hash_lookup_pallas", "Shape mismatch in input, indices and output"),
    ("slice_pallas", "Shape mismatch in input, indices and output"),
    ("slice_tangent_pallas", "Shape mismatch in input, indices and output"),
])
def test_pallas_tier_refused_by_tpu_compiler(spec, kernel, message):
    fn, args = _refused_kernels(spec, _lattice(spec))[kernel]
    with pytest.raises(ValueError, match=message):
        jax.jit(fn).lower(*args).compile()
