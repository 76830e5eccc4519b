"""Pallas TPU kernels: single-reduction BiCGStab's two fused SpMV sweeps.

The merged BiCGStab iteration (``core.methods.bicgstab_merged``) does two
SpMVs and NINE stacked dot partials per step.  Unfused that is ~11 HBM
sweeps; these two kernels plus ``fused_bodies.bicgstab_fused_update1``
collapse the iteration to three passes:

  1. ``bicgstab_fused_spmv_dots`` — the first SpMV ``v = A·z̃`` (z̃ = M(z)
     for the preconditioned variant) fused with the intermediate vectors
     ``q = r − αs``, ``y = w − αz`` AND all nine reduction partials
     ``(q·y, y·y, q·q, r̂·q, r̂·y, r̂·t, r̂·v, r̂·z, r̂·s)`` — one slab
     sweep feeds the iteration's single all-reduce.
  2. ``bicgstab_fused_spmv_update`` — the second SpMV ``t' = A·w̃`` fused
     with the three direction recurrences ``p' = r + β(p − ωs)``,
     ``s' = w + β(s − ωz)``, ``z' = t' + β(z − ωv)``.

Both reuse the overlapping-window x-slab BlockSpec of ``stencil_spmv``;
traced scalar coefficients ride a (1, k) SMEM block.  Partial accumulation
follows the sequential-TPU-grid idiom of ``spmv_dot.py`` (init at step 0,
``+=`` on the revisited accumulator block), so the plane-ordered sums are
deterministic for a fixed tiling.  Oracles:
``kernels/ref.py::bicgstab_spmv_dots_ref`` / ``bicgstab_spmv_update_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.operators import Stencil
from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)
from repro.kernels.stencil_spmv import (apply_stencil_plane, compiler_params,
                                        plane_loop, slab_depth, slab_spec,
                                        window_spec)


def _dots_kernel(stencil: Stencil, bx: int, ny: int, nz: int):
    def body(zin, coef, z, r, w, s, rhat, t, v_o, q_o, y_o, acc):
        # zin: (bx+2, ny+2, nz+2) window; coef: (1, 1) = [α]; the six plain
        # slabs and three outputs: (bx, ny, nz); acc: (1, 9) partials
        alpha = coef[0, 0]

        def plane(p, parts):
            v = apply_stencil_plane(stencil, zin, p, ny, nz)
            sp, zp = s[p], z[p]
            q = r[p] - alpha * sp
            y = w[p] - alpha * zp
            rh = rhat[p]
            v_o[p] = v
            q_o[p] = q
            y_o[p] = y
            terms = (q * y, y * y, q * q, rh * q, rh * y, rh * t[p], rh * v,
                     rh * zp, rh * sp)
            return tuple(a + jnp.sum(b).astype(acc.dtype)
                         for a, b in zip(parts, terms))

        accumulate(acc, plane_loop(bx, plane, 9, acc.dtype))

    return body


@functools.partial(jax.jit, static_argnames=("stencil", "bz", "interpret"))
def bicgstab_fused_spmv_dots(
    zp: jax.Array,
    z: jax.Array,
    r: jax.Array,
    w: jax.Array,
    s: jax.Array,
    rhat: jax.Array,
    t: jax.Array,
    alpha: jax.Array,
    *,
    stencil: Stencil,
    bz: int = 8,
    interpret: bool,
):
    """``v = A·z̃`` + intermediates ``q, y`` + all 9 partials, one sweep.

    ``zp``: (nx+2, ny+2, nz+2) halo-padded SpMV operand (``M(z)`` when
    preconditioned, else ``z``); the six interior-shaped vectors stream
    alongside.  Returns ``(v, q, y, parts)`` with ``parts`` the 9-tuple
    ``(q·y, y·y, q·q, r̂·q, r̂·y, r̂·t, r̂·v, r̂·z, r̂·s)``.
    """
    nx, ny, nz = zp.shape[0] - 2, zp.shape[1] - 2, zp.shape[2] - 2
    bx = slab_depth((nx, ny, nz), zp.dtype, bz, blocks=9)
    coef = alpha.astype(zp.dtype).reshape(1, 1)
    slab = slab_spec(bx, ny, nz)

    v, q, y, acc = pallas_call(
        _dots_kernel(stencil, bx, ny, nz),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), scalar_spec()] + [slab] * 6,
        out_specs=[slab] * 3 + [scalar_spec()],
        out_shape=[out_struct((nx, ny, nz), zp.dtype, zp)] * 3
        + [out_struct((1, 9), acc_dtype(zp.dtype), zp)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(zp, coef, z, r, w, s, rhat, t)
    return v, q, y, tuple(acc[0, k] for k in range(9))


def _update_kernel(stencil: Stencil, bx: int, ny: int, nz: int):
    def body(win, coef, w, r, p_in, s, z, v, t_o, p_o, s_o, z_o):
        # win: (bx+2, ny+2, nz+2) window; coef: (1, 2) = [ω, β]
        omega = coef[0, 0]
        beta = coef[0, 1]

        def plane(p, parts):
            t_new = apply_stencil_plane(stencil, win, p, ny, nz)
            sp, zp = s[p], z[p]
            t_o[p] = t_new
            p_o[p] = r[p] + beta * (p_in[p] - omega * sp)
            s_o[p] = w[p] + beta * (sp - omega * zp)
            z_o[p] = t_new + beta * (zp - omega * v[p])
            return parts

        plane_loop(bx, plane)

    return body


@functools.partial(jax.jit, static_argnames=("stencil", "bz", "interpret"))
def bicgstab_fused_spmv_update(
    wp: jax.Array,
    w: jax.Array,
    r: jax.Array,
    p: jax.Array,
    s: jax.Array,
    z: jax.Array,
    v: jax.Array,
    omega: jax.Array,
    beta: jax.Array,
    *,
    stencil: Stencil,
    bz: int = 8,
    interpret: bool,
):
    """``t' = A·w̃`` + the three direction recurrences, one sweep.

    ``wp``: (nx+2, ny+2, nz+2) halo-padded SpMV operand (``M(w')`` when
    preconditioned, else ``w'``).  Returns ``(t', p', s', z')`` with
    ``p' = r + β(p − ωs)``, ``s' = w + β(s − ωz)``, ``z' = t' + β(z − ωv)``.
    """
    nx, ny, nz = wp.shape[0] - 2, wp.shape[1] - 2, wp.shape[2] - 2
    bx = slab_depth((nx, ny, nz), wp.dtype, bz, blocks=10)
    coef = jnp.stack([omega, beta]).astype(wp.dtype).reshape(1, 2)
    slab = slab_spec(bx, ny, nz)

    outs = pallas_call(
        _update_kernel(stencil, bx, ny, nz),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz), scalar_spec()] + [slab] * 6,
        out_specs=[slab] * 4,
        out_shape=[out_struct((nx, ny, nz), wp.dtype, wp)] * 4,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(wp, coef, w, r, p, s, z, v)
    return tuple(outs)
