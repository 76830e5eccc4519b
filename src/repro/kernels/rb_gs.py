"""Pallas TPU kernel: red-black Gauss-Seidel half-sweep (paper §3.4).

One colour's update, fused with the stencil application:

    x[i,j,k] <- (b[i,j,k] - Σ_off c·x[neigh]) / diag     where (i+j+k)%2 == colour
    x[i,j,k] <- x[i,j,k]                                  otherwise

Same x-slab overlapping-window tiling as ``stencil_spmv``; the parity mask is
built per plane from (y, z) iotas plus the plane's global x index.  The
colour is a Python static (two specialisations), mirroring the paper's
two-colour scheme.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.operators import Stencil
from repro.kernels.blocks import out_struct, pallas_call
from repro.kernels.stencil_spmv import (apply_stencil_plane, centre_plane,
                                        compiler_params, plane_loop,
                                        slab_depth, slab_spec, window_spec)


def _kernel(stencil: Stencil, bx: int, ny: int, nz: int, colour: int):
    def body(xin, bin_, out):
        jj = jax.lax.broadcasted_iota(jnp.int32, (ny, nz), 0)
        kk = jax.lax.broadcasted_iota(jnp.int32, (ny, nz), 1)
        x0 = pl.program_id(0) * bx

        def plane(p, parts):
            off = apply_stencil_plane(stencil, xin, p, ny, nz, diag=False)
            gs = (bin_[p] - off) / stencil.diag
            mask = ((x0 + p + jj + kk) % 2) == colour
            out[p] = jnp.where(mask, gs, centre_plane(xin, p, ny, nz))
            return parts

        plane_loop(bx, plane)

    return body


@functools.partial(jax.jit, static_argnames=("stencil", "colour", "bz", "interpret"))
def rb_gs_half_sweep(
    xp: jax.Array,
    b: jax.Array,
    *,
    stencil: Stencil,
    colour: int,
    bz: int = 8,
    interpret: bool,
) -> jax.Array:
    """One coloured half-sweep from padded ``xp``; returns the updated grid."""
    nx, ny, nz = b.shape
    bx = slab_depth(b.shape, b.dtype, bz, blocks=2)
    return pallas_call(
        _kernel(stencil, bx, ny, nz, colour),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), slab_spec(bx, ny, nz)],
        out_specs=slab_spec(bx, ny, nz),
        out_shape=out_struct((nx, ny, nz), b.dtype, b),
        compiler_params=compiler_params(),
        interpret=interpret,
    )(xp, b)
