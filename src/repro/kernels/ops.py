"""Jit'd dispatch wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively; everywhere else they run in
``interpret=True`` mode (the kernel body executed op-by-op on CPU), which is
how this repo validates them.  ``use_pallas=False`` falls back to the jnp
oracle — the solvers take a ``matvec_padded`` hook, so the whole solver suite
can run on either implementation (tests assert they agree).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.operators import Stencil
from repro.kernels.cg_fused_update import (
    cg_fused_update as _cg_fused_update,
    fused_cg_body as _fused_cg_body,
)
from repro.kernels.spmv_dot import (
    stencil_spmv_dots as _stencil_spmv_dots,
    stencil_spmv_dots3 as _stencil_spmv_dots3,
)
from repro.kernels.fused_axpby import (
    fused_axpby as _fused_axpby,
    fused_axpby_dot as _fused_axpby_dot,
)
from repro.kernels.fused_bodies import (
    bicgstab_fused_update1 as _bicgstab_fused_update1,
    fused_dots as _fused_dots,
    fused_pcg_body as _fused_pcg_body,
    fused_pipe_body as _fused_pipe_body,
    fused_ppipe_body as _fused_ppipe_body,
)
from repro.kernels.bicgstab_fused import (
    bicgstab_fused_spmv_dots as _bicgstab_fused_spmv_dots,
    bicgstab_fused_spmv_update as _bicgstab_fused_spmv_update,
)
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.precond import (
    block_jacobi_sweep as _block_jacobi_sweep,
    cheb_fused_step as _cheb_fused_step,
)
from repro.kernels.rb_gs import rb_gs_half_sweep as _rb_gs_half_sweep
from repro.kernels.stencil_spmv import stencil_spmv as _stencil_spmv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def check_dtype(dtype) -> None:
    """Refuse a float64 problem where the kernels compile for the chip: the
    TPU's kernel compiler lowers no 64-bit floats (interpret mode runs it)."""
    if not _interpret() and jnp.dtype(dtype) == jnp.float64:
        raise ValueError(
            "the Pallas kernels run in float32 or bfloat16 on a TPU; solve "
            "this float64 problem with pallas=False (XLA), or in float32 "
            "(SolverOptions(f64=False))")


def spmv(xp: jax.Array, stencil: Stencil, *, bz: int = 8) -> jax.Array:
    return _stencil_spmv(xp, stencil=stencil, bz=bz, interpret=_interpret())


def spmv_dot(xp: jax.Array, stencil: Stencil, *, bz: int = 8):
    return _stencil_spmv(
        xp, stencil=stencil, bz=bz, fuse_dot=True, interpret=_interpret()
    )


def axpbypcz(a, x, b, y, c, z):
    return _fused_axpby(a, x, b, y, c, z, interpret=_interpret())


def axpbypcz_dot(a, x, b, y, c, z, w):
    return _fused_axpby_dot(a, x, b, y, c, z, w, interpret=_interpret())


def spmv_dots(xp: jax.Array, stencil: Stencil, *, bz: int = 8):
    """``(A·x, (A·x)·x, x·x)`` in one VMEM pass (merged CG's reduction pair)."""
    return _stencil_spmv_dots(xp, stencil=stencil, bz=bz,
                              interpret=_interpret())


def spmv_dots3(xp: jax.Array, r: jax.Array, stencil: Stencil, *, bz: int = 8):
    """``(A·x, (A·x)·x, r·x, r·r)`` in one pass (PCG/pipe reduction triple)."""
    return _stencil_spmv_dots3(xp, r, stencil=stencil, bz=bz,
                               interpret=_interpret())


def fused_dots(a, b, c, *, br: int = 256):
    """Stacked partial dots ``(a·b, c·b, a·a)`` in one read pass."""
    return _fused_dots(a, b, c, br=br, interpret=_interpret())


def pipe_body(alpha, beta, x, r, w, p, s, z, n, *, br: int = 64):
    """Pipelined CG's six recurrences -> (x', r', w', p', s', z')."""
    return _fused_pipe_body(alpha, beta, x, r, w, p, s, z, n, br=br,
                            interpret=_interpret())


def pcg_body(alpha, beta, x, r, u, p, s, w, *, br: int = 128):
    """Merged PCG's four vector updates -> (x', r', p', s')."""
    return _fused_pcg_body(alpha, beta, x, r, u, p, s, w, br=br,
                           interpret=_interpret())


def ppipe_body(alpha, beta, x, r, u, w, p, s, q, z, m, n, *, br: int = 64):
    """Pipelined PCG's eight recurrences -> (x', r', u', w', p', s', q', z')."""
    return _fused_ppipe_body(alpha, beta, x, r, u, w, p, s, q, z, m, n,
                             br=br, interpret=_interpret())


def bicgstab_update1(alpha, omega, y, p, q, yv, t, v, *, br: int = 128):
    """BiCGStab's ω-half x/r/w updates -> (y', r', w')."""
    return _bicgstab_fused_update1(alpha, omega, y, p, q, yv, t, v, br=br,
                                   interpret=_interpret())


def bicgstab_spmv_dots(zp, z, r, w, s, rhat, t, alpha, stencil: Stencil, *,
                       bz: int = 8):
    """BiCGStab sweep 1: ``v = A·z̃`` + ``q``/``y`` + 9 dot partials."""
    return _bicgstab_fused_spmv_dots(
        zp, z, r, w, s, rhat, t, alpha, stencil=stencil, bz=bz,
        interpret=_interpret()
    )


def bicgstab_spmv_update(wp, w, r, p, s, z, v, omega, beta, stencil: Stencil,
                         *, bz: int = 8):
    """BiCGStab sweep 2: ``t' = A·w̃`` + direction recurrences."""
    return _bicgstab_fused_spmv_update(
        wp, w, r, p, s, z, v, omega, beta, stencil=stencil, bz=bz,
        interpret=_interpret()
    )


def cg_update(beta, r, ar, p, ap):
    return _cg_fused_update(beta, r, ar, p, ap, interpret=_interpret())


def cg_body(alpha, beta, x, r, p, s, w, *, br: int = 128):
    """Merged-CG's four vector updates in one VMEM pass -> (x', r', p', s')."""
    return _fused_cg_body(alpha, beta, x, r, p, s, w, br=br,
                          interpret=_interpret())


def gs_half_sweep(xp, b, stencil: Stencil, colour: int, *, bz: int = 8):
    return _rb_gs_half_sweep(
        xp, b, stencil=stencil, colour=colour, bz=bz, interpret=_interpret()
    )


def cheb_step(zp, r, d, stencil: Stencil, *, a: float, c: float, bz: int = 8):
    return _cheb_fused_step(
        zp, r, d, stencil=stencil, a=a, c=c, bz=bz, interpret=_interpret()
    )


def jacobi_sweep(zp, r, stencil: Stencil, *, omega: float = 1.0, bz: int = 8):
    return _block_jacobi_sweep(
        zp, r, stencil=stencil, omega=omega, bz=bz, interpret=_interpret()
    )


def flash_attention(q, k, v, *, bq: int = 256, bkv: int = 256,
                    window: int = 0):
    return _flash_attention(q, k, v, bq=bq, bkv=bkv, window=window,
                            interpret=_interpret())


def make_matvec_padded(stencil: Stencil, *, bz: int = 8):
    """A ``matvec_padded`` hook (for LocalOp/DistributedOp) backed by Pallas."""

    def mv(xp: jax.Array) -> jax.Array:
        return spmv(xp, stencil, bz=bz)

    return mv
