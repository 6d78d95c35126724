"""The paper's primary contribution: Simplex-GP on the permutohedral lattice.

Submodules:
  lattice       — TPU-native permutohedral lattice (splat/blur/slice, §3.2)
  stencil       — generic stationary-kernel discretization (§4.1, Eq. 9)
  filtering     — the Simplex-GP MVM with §4.2 custom gradients
  kernels_math  — stationary profiles + dense oracles
  exact         — exact-GP baseline (KeOps role)
  ski_grid      — KISS-GP cubic-grid SKI baseline
  skip          — SKIP product-kernel low-rank baseline
  sgpr          — Titsias variational baseline
"""
from repro.core import kernels_math
from repro.core.filtering import (FilterSpec, LatticeCache, filter_mvm,
                                  lattice_filter, lattice_filter_with,
                                  mvm_operator, spec_for)
from repro.core.lattice import (Lattice, build_lattice, build_lattice_auto,
                                default_capacity, suggest_capacity)
from repro.core.stencil import Stencil, make_stencil

__all__ = [
    "kernels_math", "FilterSpec", "LatticeCache", "filter_mvm",
    "lattice_filter", "lattice_filter_with", "mvm_operator", "spec_for",
    "Lattice", "build_lattice", "build_lattice_auto",
    "default_capacity", "suggest_capacity", "Stencil", "make_stencil",
]
