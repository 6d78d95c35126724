"""Open-addressing hash table for the lattice build (DESIGN.md §11).

Replaces the two O(N log N) lexicographic sorts of the lattice build —
dedup over the n(d+1) vertex keys and the neighbor-table merge-sort —
with insert/lookup on a static-capacity linear-probe hash table, the
same design the paper's CUDA implementation uses. ops.py carries the
backend policy (hash_pallas / hash_xla, with "sort" as the oracle tier
kept in core/lattice.py).
"""
from repro.kernels.hash.ops import (BUILD_BACKENDS, hash_capacity,
                                    hash_insert, hash_lookup,
                                    resolve_build_backend, table_keys)

__all__ = ["BUILD_BACKENDS", "hash_capacity", "hash_insert", "hash_lookup",
           "resolve_build_backend", "table_keys"]
