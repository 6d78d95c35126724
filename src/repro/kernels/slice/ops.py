"""Serving-slice policy + dispatch for frozen-table queries (DESIGN.md §12).

Mirrors the blur (kernels/blur/ops.py) and build (kernels/hash/ops.py)
policies: ``auto`` resolves to a concrete backend, every tier stays
explicitly reachable, and off-TPU the Pallas kernel dispatches to the XLA
fallback unless the interpreter is requested.

Backend tiers:

  slice_pallas  one fused pallas_call per query batch: hash probe +
                dense-row translation + table gather + barycentric
                contraction with tkeys/row_of_slot/tables VMEM-resident
                (kernel.py). Interpret mode only: Mosaic does not
                compile it for the TPU.
  slice_xla     hash lookup (kernels/hash/ref.py) + gather + einsum —
                what ``auto`` resolves to on every host.
"""
from __future__ import annotations

import jax

from repro.core.lattice import LatticeIndex
from repro.kernels.slice.kernel import (slice_query_pallas,
                                        slice_query_tangent_pallas)
from repro.kernels.slice.ref import (slice_query_jacobian_xla,
                                     slice_query_tangent_xla,
                                     slice_query_xla)

Array = jax.Array

SLICE_BACKENDS = ("auto", "slice_pallas", "slice_xla")

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_slice_backend(backend: str) -> str:
    """The concrete backend a frozen-table query runs.

    ``auto`` is ``slice_xla`` on every host, the TPU included: Mosaic
    refuses the fused kernel's probe gather from the resident key table
    with "Shape mismatch in input, indices and output"
    (tests/test_tpu_compile.py). ``slice_pallas`` stays reachable
    explicitly.
    """
    if backend not in SLICE_BACKENDS:
        raise ValueError(f"unknown slice backend {backend!r}; want one of "
                         f"{SLICE_BACKENDS}")
    return "slice_xla" if backend == "auto" else backend


def slice_query(index: LatticeIndex, tables: Array, q_packed: Array,
                weights: Array, active: Array, *, backend: str = "auto",
                interpret: bool | None = None) -> tuple[Array, Array]:
    """Slice frozen ``tables`` at embedded queries -> (out (b, c), miss (b,)).

    ``q_packed`` is query-major ((b*(d+1), npk) packed vertex keys),
    ``weights`` the (b, d+1) barycentric weights, ``active`` a per-vertex
    validity mask (False forces a miss — padding rows, pack-overflowed
    queries). Misses contribute zero and their barycentric mass comes
    back as the per-query slice-miss diagnostic.
    """
    resolved = resolve_slice_backend(backend)
    if resolved == "slice_pallas":
        run_interp = interpret if interpret is not None else False
        if _on_tpu() or run_interp:
            return slice_query_pallas(index.tkeys, index.row_of_slot,
                                      tables, q_packed, weights, active,
                                      interpret=run_interp)
    return slice_query_xla(index.tkeys, index.row_of_slot, tables,
                           q_packed, weights, active, index.hcap)


def slice_query_tangent(index: LatticeIndex, tables: Array, q_packed: Array,
                        weights: Array, weights_dot: Array, active: Array, *,
                        backend: str = "auto",
                        interpret: bool | None = None
                        ) -> tuple[Array, Array, Array]:
    """Primal + directional-tangent slice -> (out, out_dot, miss).

    The query-space JVP of the frozen slice (DESIGN.md §15): the tables
    and probed rows are constant along the tangent, so the JVP is the
    SAME barycentric contraction against ``weights_dot`` (the directional
    derivative of the weights, ``lattice.embed_weight_tangent``) — fused
    with the primal so the pair costs one probe + one gather. Backend
    policy is identical to ``slice_query``: the Pallas tier runs the
    probe loop once and both contractions in-register; everywhere else
    the XLA reference gathers once and einsums twice.
    """
    resolved = resolve_slice_backend(backend)
    if resolved == "slice_pallas":
        run_interp = interpret if interpret is not None else False
        if _on_tpu() or run_interp:
            return slice_query_tangent_pallas(
                index.tkeys, index.row_of_slot, tables, q_packed, weights,
                weights_dot, active, interpret=run_interp)
    return slice_query_tangent_xla(index.tkeys, index.row_of_slot, tables,
                                   q_packed, weights, weights_dot, active,
                                   index.hcap)


def slice_query_jacobian(index: LatticeIndex, tables: Array, q_packed: Array,
                         weights: Array, wjac: Array, active: Array
                         ) -> tuple[Array, Array, Array]:
    """Primal + full query-space Jacobian -> (out, jac (b, c, d), miss).

    The d-directional generalization of ``slice_query_tangent`` (one
    probe, one gather, d+1 contractions); XLA-only — the serving
    gradient consumers (gp/serve.predict_grad) run it on the host, and
    its output is d+1 times the primal's so the VMEM-residency argument
    for a fused kernel does not transfer.
    """
    return slice_query_jacobian_xla(index.tkeys, index.row_of_slot, tables,
                                    q_packed, weights, wjac, active,
                                    index.hcap)


__all__ = ["SLICE_BACKENDS", "resolve_slice_backend", "slice_query",
           "slice_query_tangent", "slice_query_jacobian"]
