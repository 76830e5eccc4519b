"""Pallas TPU kernel: stencil SpMV + BOTH merged-CG dot partials, one pass.

The merged-reduction CG iteration (``core.solvers.cg_merged``) needs exactly
two scalars per iteration — ``γ = r·r`` and ``δ = (A r)·r`` — and the one
SpMV that produces ``w = A r``.  Streaming the slab once and accumulating
both partials alongside the stencil apply turns the classic
SpMV + dot + dot sequence (three HBM sweeps, two kernel-switch barriers)
into a single VMEM pass: the memory-side analogue of stacking the two
``MPI_Allreduce``s into one.

Extends ``kernels/stencil_spmv.py``'s ``fuse_dot`` (which emits only
``(A x)·x``) with the second accumulator; same overlapping-window x-slab
BlockSpec, same sequential-grid accumulation (TPU grid steps run in order,
so the revisited (1, 2) SMEM accumulator is well-defined).  Oracle:
``kernels/ref.py::stencil_spmv_dots_ref``.

``stencil_spmv_dots3`` (PR 10) is the same pass with a second (unpadded)
streamed operand ``r`` and a (1, 3) accumulator — the reduction triple the
preconditioned/pipelined variants need: with ``x = u`` it yields merged
PCG's ``(A u, (A u)·u, r·u, r·r)``; with ``x = w`` pipelined CG reads the
``r·w``/``r·r`` slots and ignores the first.  Oracle:
``kernels/ref.py::stencil_spmv_dots3_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.operators import Stencil
from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)
from repro.kernels.stencil_spmv import (apply_stencil_plane, centre_plane,
                                        compiler_params, plane_loop,
                                        slab_depth, slab_spec, window_spec)


def _kernel(stencil: Stencil, bx: int, ny: int, nz: int):
    def body(xin, out, acc):
        # xin: (bx+2, ny+2, nz+2) overlapping window; out: (bx, ny, nz);
        # acc: (1, 2) = [Σ y·x, Σ x·x] partials, revisited every grid step
        def plane(p, parts):
            y = apply_stencil_plane(stencil, xin, p, ny, nz)
            out[p] = y
            c = centre_plane(xin, p, ny, nz)
            return (parts[0] + jnp.sum(y * c).astype(acc.dtype),
                    parts[1] + jnp.sum(c * c).astype(acc.dtype))

        accumulate(acc, plane_loop(bx, plane, 2, acc.dtype))

    return body


@functools.partial(jax.jit, static_argnames=("stencil", "bz", "interpret"))
def stencil_spmv_dots(
    xp: jax.Array,
    *,
    stencil: Stencil,
    bz: int = 8,
    interpret: bool,
):
    """``y = A·x``, ``y·x`` and ``x·x`` from the halo-padded ``xp``.

    ``xp``: (nx+2, ny+2, nz+2).  Returns ``(y, y·x, x·x)`` — for merged CG,
    with ``x = r``: ``w = A r``, ``δ`` and ``γ`` in one HBM pass.
    """
    nx, ny, nz = xp.shape[0] - 2, xp.shape[1] - 2, xp.shape[2] - 2
    bx = slab_depth((nx, ny, nz), xp.dtype, bz, blocks=1)

    y, acc = pallas_call(
        _kernel(stencil, bx, ny, nz),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz)],
        out_specs=[slab_spec(bx, ny, nz), scalar_spec()],
        out_shape=[
            out_struct((nx, ny, nz), xp.dtype, xp),
            out_struct((1, 2), acc_dtype(xp.dtype), xp),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(xp)
    return y, acc[0, 0], acc[0, 1]


def _kernel3(stencil: Stencil, bx: int, ny: int, nz: int):
    def body(xin, rin, out, acc):
        # xin: (bx+2, ny+2, nz+2) overlapping window; rin/out: (bx, ny, nz);
        # acc: (1, 3) = [Σ y·x, Σ r·x, Σ r·r] partials, revisited per step
        def plane(p, parts):
            y = apply_stencil_plane(stencil, xin, p, ny, nz)
            out[p] = y
            c = centre_plane(xin, p, ny, nz)
            r = rin[p]
            return (parts[0] + jnp.sum(y * c).astype(acc.dtype),
                    parts[1] + jnp.sum(r * c).astype(acc.dtype),
                    parts[2] + jnp.sum(r * r).astype(acc.dtype))

        accumulate(acc, plane_loop(bx, plane, 3, acc.dtype))

    return body


@functools.partial(jax.jit, static_argnames=("stencil", "bz", "interpret"))
def stencil_spmv_dots3(
    xp: jax.Array,
    r: jax.Array,
    *,
    stencil: Stencil,
    bz: int = 8,
    interpret: bool,
):
    """``y = A·x`` plus the THREE partials ``(y·x, r·x, r·r)``, one pass.

    ``xp``: (nx+2, ny+2, nz+2) halo-padded SpMV operand; ``r``: (nx, ny, nz)
    streamed alongside.  For merged PCG with ``x = u = M⁻¹r`` this is
    ``(w, δ, γ, ‖r‖²)``; pipelined CG calls it with ``x = w`` and reads the
    ``r·w``/``r·r`` slots.
    """
    nx, ny, nz = xp.shape[0] - 2, xp.shape[1] - 2, xp.shape[2] - 2
    bx = slab_depth((nx, ny, nz), xp.dtype, bz, blocks=2)

    y, acc = pallas_call(
        _kernel3(stencil, bx, ny, nz),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), slab_spec(bx, ny, nz)],
        out_specs=[slab_spec(bx, ny, nz), scalar_spec()],
        out_shape=[
            out_struct((nx, ny, nz), xp.dtype, xp),
            out_struct((1, 3), acc_dtype(xp.dtype), xp),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(xp, r)
    return y, acc[0, 0], acc[0, 1], acc[0, 2]
