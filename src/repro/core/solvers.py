"""Solver functions — the callable surface over ``repro.core.methods``.

Since PR 5 every algorithm is defined exactly ONCE as a
``repro.core.methods.MethodDef`` (init/step/finalize + declared state
layout) and executed by the generic ``run_method`` driver; this module
derives the familiar solver functions

    cg(A, b, x0, *, tol=1e-6, maxiter=500, dot=None, norm_ref=None)

from those definitions: ``SOLVERS`` holds one per registered method, and
the module-level names (``cg``, ``bicgstab_b1``, …) are its entries.  The same
definitions drive ``core.distributed.solve_shardmap`` /
``solve_step_shardmap`` and the fused Pallas path — the paper's design
where the algorithm is written once and the parallelisation (MPI /
MPI+tasks) is swapped underneath.

``LocalOp`` is the single-device operator (zero-padded halos == physical
boundary); its distributed counterpart is
``repro.core.distributed.DistributedOp`` (halos via ``lax.ppermute``,
reductions via ``lax.psum``) — both satisfy the operator protocol the
method definitions are written against.

The algorithmic commentary (barrier structure per §3.1/Fig. 1, the Alg. 1
sign-convention note, the reduction-hiding recurrences and their numerical
caveats) lives with the definitions in ``repro.core.methods``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.methods import (  # noqa: F401  (compat re-exports)
    METHODS,
    MethodDef,
    Ops,
    SolveResult,
    _cg_merged_scalars,
    _colour_mask,
    _hist_init,
    _plane_sweep,
    _rb_half_sweep,
    _stacked_dot,
    get_method,
    local_dot,
    run_method,
)
from repro.core.operators import Stencil


class LocalOp:
    """Single-device stencil operator (zero halos == physical boundary)."""

    def __init__(self, stencil: Stencil, matvec_padded: Callable | None = None):
        self.stencil = stencil
        self._mv_padded = matvec_padded or stencil.matvec_padded
        if not stencil.fuses_dot(matvec_padded):
            self.matvec_dot = None        # Ops.matvec_dot: matvec, then dot

    @property
    def diag(self) -> float:
        return self.stencil.diag

    def pad_exchange(self, x: jax.Array) -> jax.Array:
        return jnp.pad(x, 1)

    def matvec(self, x: jax.Array) -> jax.Array:
        return self._mv_padded(self.pad_exchange(x))

    def matvec_local(self, x: jax.Array) -> jax.Array:
        """Zero-halo apply on the local block (block-Jacobi's inner operator).
        On a single device the block IS the domain, so this == matvec."""
        return self.matvec(x)

    def matvec_dot(self, x: jax.Array) -> tuple:
        """``(A x, x·A x)`` from the built-in box apply's one pass
        (``Stencil.matvec_dot_padded``); ``None`` for any other apply."""
        return self.stencil.matvec_dot_padded(self.pad_exchange(x), local_dot)

    def dotn(self, *pairs) -> tuple:
        """Stacked dot products — locally just the dots (no collective to
        fuse); ``DistributedOp.dotn`` is the one-psum version."""
        return tuple(local_dot(a, b) for a, b in pairs)

    def sum_partials(self, *vals) -> tuple:
        """Reduce already-computed local partial scalars globally — locally
        the identity (``DistributedOp.sum_partials`` is the one-psum
        version); the fused kernels' dot partials ride this."""
        return vals


def make_solver(name: str) -> Callable:
    """The classic ``solver(A, b, x0, *, tol, maxiter, dot, norm_ref, ...)``
    callable for one registered MethodDef (plus ``M=`` for the
    preconditioned methods and the definition's declared tuning knobs —
    e.g. ``eps_restart=`` for bicgstab_b1 — threaded through
    ``Ops.params``).  ``SOLVERS`` holds one per registered method.
    """
    mdef = get_method(name)

    def solver(A, b, x0, *, tol=1e-6, maxiter=None, dot=None, norm_ref=None,
               M=None, telemetry=0, guard_spec=None, refresh_every=0,
               **params) -> SolveResult:
        if M is not None and not mdef.accepts_precond:
            raise TypeError(f"{name!r} takes no preconditioner (M=)")
        unknown = set(params) - set(mdef.params)
        if unknown:
            raise TypeError(
                f"{name}() got unexpected keyword argument(s) "
                f"{sorted(unknown)}; this method accepts "
                f"{sorted(mdef.params) or 'no extra parameters'}")
        ops = Ops(A, b, M=M, dot=dot, norm_ref=norm_ref, params=params)
        return run_method(mdef, ops, x0, tol=tol, maxiter=maxiter,
                          telemetry=telemetry, guard_spec=guard_spec,
                          refresh_every=refresh_every)

    solver.__name__ = name
    solver.__qualname__ = name
    solver.__doc__ = (mdef.step.__doc__ or "") + (
        "\n\n(Defined once in repro.core.methods; this callable runs the "
        "definition on the local/LocalOp protocol via run_method.)")
    solver.method_def = mdef
    return solver


#: one solver callable per registered MethodDef
SOLVERS: dict[str, Callable] = {name: make_solver(name) for name in METHODS}

cg = SOLVERS["cg"]
cg_nb = SOLVERS["cg_nb"]
pcg = SOLVERS["pcg"]
cg_merged = SOLVERS["cg_merged"]
bicgstab = SOLVERS["bicgstab"]
pbicgstab = SOLVERS["pbicgstab"]
bicgstab_b1 = SOLVERS["bicgstab_b1"]
jacobi = SOLVERS["jacobi"]
sym_gauss_seidel_relaxed = SOLVERS["gauss_seidel"]
sym_gauss_seidel_rb = SOLVERS["gauss_seidel_rb"]
