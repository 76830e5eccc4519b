"""Pallas TPU kernels for the preconditioner hot paths.

Two fused kernels, both on the ``stencil_spmv`` overlapping-window x-slab
tiling ((bx+2, ny+2, nz+2) VMEM windows, HBM traffic (bx+2)/bx):

  * ``cheb_fused_step`` — one Chebyshev recurrence step in ONE VMEM pass:
    the stencil apply ``A z`` plus the whole axpby chain
    ``d' = a·d + c·(r - A z); z' = z + d'``.  Unfused this is a matvec
    kernel plus two vector sweeps (the ``fused_axpby`` pattern); fusion
    removes both extra HBM round trips.  The coefficients ``a, c`` come
    from the *static* Chebyshev scalar schedule (precomputed from the
    Gershgorin bounds — see precond/chebyshev.py), so they are baked into
    the kernel as compile-time constants: the whole apply is a chain of
    ``degree-1`` such calls with no scalar traffic at all.

  * ``block_jacobi_sweep`` — one damped local Jacobi sweep
    ``z' = z + ω·(r - A z)/diag`` in one pass, the inner iteration of the
    block-Jacobi (two-stage multisplitting) preconditioner.  The caller
    zero-pads ``z`` (decomposed faces are physical boundary for the block
    operator), so the kernel is communication-free by construction.

Pure-jnp oracles live in kernels/ref.py; dispatch wrappers in
kernels/ops.py (interpret mode off-TPU, like every kernel here).
"""

from __future__ import annotations

import functools

import jax

from repro.core.operators import Stencil
from repro.kernels.blocks import out_struct, pallas_call
from repro.kernels.stencil_spmv import (apply_stencil_plane, centre_plane,
                                        compiler_params, plane_loop,
                                        slab_depth, slab_spec, window_spec)


def _cheb_kernel(stencil: Stencil, bx: int, ny: int, nz: int,
                 a: float, c: float):
    def body(zin, rin, din, zout, dout):
        def plane(p, parts):
            az = apply_stencil_plane(stencil, zin, p, ny, nz)
            d_new = a * din[p] + c * (rin[p] - az)
            dout[p] = d_new
            zout[p] = centre_plane(zin, p, ny, nz) + d_new
            return parts

        plane_loop(bx, plane)

    return body


@functools.partial(
    jax.jit, static_argnames=("stencil", "a", "c", "bz", "interpret")
)
def cheb_fused_step(
    zp: jax.Array,
    r: jax.Array,
    d: jax.Array,
    *,
    stencil: Stencil,
    a: float,
    c: float,
    bz: int = 8,
    interpret: bool,
):
    """One fused Chebyshev step from the halo-padded ``zp``.

    Returns ``(z_new, d_new)`` with ``d_new = a·d + c·(r - A z)`` and
    ``z_new = z + d_new``; shapes (nx, ny, nz) from ``zp``'s interior.
    """
    nx, ny, nz = r.shape
    bx = slab_depth(r.shape, r.dtype, bz, blocks=4)
    slab = slab_spec(bx, ny, nz)
    z_new, d_new = pallas_call(
        _cheb_kernel(stencil, bx, ny, nz, a, c),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), slab, slab],
        out_specs=[slab, slab],
        out_shape=[out_struct((nx, ny, nz), r.dtype, r)] * 2,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(zp, r, d)
    return z_new, d_new


def _bj_kernel(stencil: Stencil, bx: int, ny: int, nz: int, omega: float):
    def body(zin, rin, out):
        def plane(p, parts):
            az = apply_stencil_plane(stencil, zin, p, ny, nz)
            out[p] = (centre_plane(zin, p, ny, nz)
                      + omega * (rin[p] - az) / stencil.diag)
            return parts

        plane_loop(bx, plane)

    return body


@functools.partial(
    jax.jit, static_argnames=("stencil", "omega", "bz", "interpret")
)
def block_jacobi_sweep(
    zp: jax.Array,
    r: jax.Array,
    *,
    stencil: Stencil,
    omega: float = 1.0,
    bz: int = 8,
    interpret: bool,
) -> jax.Array:
    """``z + ω·(r - A z)/diag`` from the zero-padded local ``zp``, one pass."""
    nx, ny, nz = r.shape
    bx = slab_depth(r.shape, r.dtype, bz, blocks=2)
    slab = slab_spec(bx, ny, nz)
    return pallas_call(
        _bj_kernel(stencil, bx, ny, nz, omega),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), slab],
        out_specs=slab,
        out_shape=out_struct((nx, ny, nz), r.dtype, r),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(zp, r)
