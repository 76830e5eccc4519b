"""HPCG/HPCCG problem generation (paper §4.1).

The linear system is the standard HPCG one: a centred stencil on a 3-D
hexahedral mesh, with the right-hand side defined analytically for the exact
solution ``x* = 1`` and the iterate initialised to ``x0 = 0``.  Convergence is
declared at ``||r||_2 < eps * ||b||_2`` with ``eps = 1e-6`` (x0 = 0 makes this
identical to the relative-to-r0 criterion), and the BiCGStab restart threshold
is ``1e-5`` (paper §4.1).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.operators import STENCILS, Stencil


def enable_f64() -> None:
    """Paper runs in double precision; call before building f64 problems."""
    jax.config.update("jax_enable_x64", True)


def default_dtype():
    """float64 when x64 is enabled (solver/benchmark paths), else float32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


@dataclasses.dataclass(frozen=True)
class HPCGProblem:
    stencil: Stencil
    shape: tuple[int, int, int]
    dtype: object

    @property
    def rows(self) -> int:
        nx, ny, nz = self.shape
        return nx * ny * nz

    def b(self) -> jax.Array:
        """RHS for x* = 1: b = A @ ones (zero in the interior for HPCG-27)."""
        return _rhs(self.stencil, self.shape, self.dtype)

    def x0(self, sharding=None) -> jax.Array:
        """The zero initial guess, made where ``sharding`` places it: on a
        mesh each chip writes its own block, no whole grid on one chip."""
        return jnp.zeros(self.shape, self.dtype, device=sharding)

    def x_true(self) -> jax.Array:
        return jnp.ones(self.shape, self.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _rhs(stencil: Stencil, shape, dtype) -> jax.Array:
    # jitted so XLA fuses the stencil's 27 terms: run op by op, each term is
    # a grid-sized buffer, and at 512³ float32 they reached 14.6 GB of peak
    # bytes in use on a 16.9 GB TPU v5e
    return stencil.matvec(jnp.ones(shape, dtype))


def make_problem(
    shape: tuple[int, int, int] = (128, 128, 128),
    stencil: str = "27pt",
    dtype=None,
) -> HPCGProblem:
    if stencil not in STENCILS:
        raise ValueError(f"unknown stencil {stencil!r}; options: {sorted(STENCILS)}")
    return HPCGProblem(
        stencil=STENCILS[stencil], shape=tuple(shape), dtype=dtype or default_dtype()
    )
