# Unified solver facade (docs/API.md): one entry point for local, sharded
# and Pallas-backed solves, with batched multi-RHS support for serving.
from repro.api.backend import (Backend, resolve_backend, resolve_halo_mode,
                               resolve_matvec, resolve_precond)
from repro.api.options import HALO_MODES, LAYOUTS, SolverOptions
from repro.api.registry import (
    REGISTRY,
    fallback_chain,
    fused_solver_names,
    get_solver,
    solver_names,
    variant_pairs,
)
from repro.precond import PRECONDITIONERS, Preconditioner, make_precond, precond_names
from repro.api.session import SolverSession, solve, solve_batched
from repro.api.timing import timed, timed_result

__all__ = [
    "Backend",
    "HALO_MODES",
    "LAYOUTS",
    "PRECONDITIONERS",
    "Preconditioner",
    "REGISTRY",
    "SolverOptions",
    "SolverSession",
    "fallback_chain",
    "fused_solver_names",
    "get_solver",
    "make_precond",
    "precond_names",
    "resolve_backend",
    "resolve_halo_mode",
    "resolve_matvec",
    "resolve_precond",
    "solve",
    "solve_batched",
    "solver_names",
    "timed",
    "timed_result",
    "variant_pairs",
]
