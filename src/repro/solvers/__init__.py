"""Krylov solvers for MVM-based GP inference (BBMM)."""
from repro.solvers.cg import CGInfo, cg, lanczos_tridiag_from_cg
from repro.solvers.lanczos import (LanczosResult, lanczos, slq_logdet,
                                   slq_logdet_from_cg, slq_quadrature)
from repro.solvers.pivoted_cholesky import (PivotedCholesky, pivoted_cholesky,
                                            precond_logdet, woodbury_precond)
from repro.solvers.rrcg import RRCGResult, expected_iters, rrcg

__all__ = [
    "CGInfo", "cg", "lanczos_tridiag_from_cg",
    "LanczosResult", "lanczos", "slq_logdet", "slq_logdet_from_cg",
    "slq_quadrature",
    "PivotedCholesky", "pivoted_cholesky", "precond_logdet",
    "woodbury_precond",
    "RRCGResult", "expected_iters", "rrcg",
]
