"""Build-backend policy + dispatch for the lattice hash table.

Mirrors the blur MVM policy (kernels/blur/ops.py): ``auto`` resolves to a
concrete backend, every tier stays explicitly reachable, and off-TPU the
Pallas kernels dispatch to the XLA fallback unless the interpreter is
requested.

Backend tiers (DESIGN.md §11):

  hash_pallas  accelerator-resident table: sequential-core insert +
               vectorized resident-table lookup (kernel.py). Interpret
               mode only: Mosaic does not compile either kernel.
  hash_xla     epoch-based scatter-min insert + while-loop probe lookup
               (ref.py) — what ``auto`` resolves to on every host.
  sort         the original two-pass lexicographic-sort build
               (core/lattice._build_lattice_impl). Bit-exact oracle: the
               hash backends must match it up to slot permutation.

``auto`` NEVER resolves to "sort": the hash build is the production
default (2.5-5x faster cold/warm on the host backend — BENCH_build.json);
the sort path is kept for verification and as the deterministic
lex-ordered reference.
"""
from __future__ import annotations

import jax

from repro.kernels.hash import ref
from repro.kernels.hash.kernel import hash_insert_pallas, hash_lookup_pallas
from repro.kernels.hash.ref import (hash_insert_xla, hash_lookup_xla,
                                    table_keys)

Array = jax.Array

BUILD_BACKENDS = ("auto", "hash_pallas", "hash_xla", "sort")

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def hash_capacity(cap: int) -> int:
    """Power-of-two table size >= 2*cap: occupancy <= 0.5 whenever the
    deduplicated point count fits the lattice capacity at all."""
    cap = max(int(cap), 8)
    return 2 * (1 << (cap - 1).bit_length())


def resolve_build_backend(backend: str) -> str:
    """The concrete build backend a ``build_lattice`` call runs.

    ``auto`` is ``hash_xla`` on every host, the TPU included: Mosaic
    compiles neither Pallas kernel. The lookup's vector row gather from the
    resident key table is refused with "Shape mismatch in input, indices
    and output", and the insert's scalar stores with "Cannot store scalars
    to VMEM" (tests/test_tpu_compile.py). ``hash_pallas`` stays reachable
    explicitly.
    """
    if backend not in BUILD_BACKENDS:
        raise ValueError(f"unknown build backend {backend!r}; want one of "
                         f"{BUILD_BACKENDS}")
    return "hash_xla" if backend == "auto" else backend


def hash_insert(packed: Array, hcap: int, *, backend: str = "hash_xla",
                interpret: bool | None = None):
    """Dedup-insert all packed key rows -> (owner, slot_of_row, ok).

    ``backend`` must be a concrete hash tier. Off-TPU, "hash_pallas"
    dispatches to the XLA fallback unless ``interpret=True`` explicitly
    asks for the Pallas interpreter (the blur-ops convention).
    """
    if backend == "hash_pallas":
        run_interp = interpret if interpret is not None else False
        if _on_tpu() or run_interp:
            return hash_insert_pallas(packed, hcap, interpret=run_interp)
    return hash_insert_xla(packed, hcap)


def hash_lookup(tkeys: Array, queries: Array, active: Array, hcap: int, *,
                backend: str = "hash_xla",
                interpret: bool | None = None) -> Array:
    """Slot of each query key, or -1 (absent / inactive)."""
    if backend == "hash_pallas":
        run_interp = interpret if interpret is not None else False
        if _on_tpu() or run_interp:
            return hash_lookup_pallas(tkeys, queries, active,
                                      interpret=run_interp)
    return hash_lookup_xla(tkeys, queries, active, hcap)


__all__ = ["BUILD_BACKENDS", "resolve_build_backend", "hash_capacity",
           "hash_insert", "hash_lookup", "table_keys", "ref"]
