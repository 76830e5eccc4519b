"""Pallas TPU kernels: fused CG vector-update passes.

``cg_fused_update`` — CG-NB's fused Tk1&2 (+Tk2's reduction partial).
Alg. 1 lines 6-8 share all their operands, so the paper assigns them to
adjacent tasks; the TPU analogue is a single VMEM pass computing

    Ap_new = Ar + β·Ap
    p_new  = r  + β·p
    α_d    = Σ Ap_new · p_new        (partial, reduced outside)

One read of {r, Ar, p, Ap} + one write of {p_new, Ap_new} instead of three
separate kernels (two axpbys + a dot) costing 6 reads + 2 writes.

``fused_cg_body`` (PR 4) — the ENTIRE vector-update half of a merged-CG
iteration (``core.solvers.cg_merged``) in one VMEM pass:

    p' = r + β·p,   s' = w + β·s,   x' = x + α·p',   r' = r − α·s'

5 reads + 4 writes instead of the four separate axpys' 8 reads + 4 writes
(and three kernel-switch HBM round trips).  Together with
``spmv_dot.stencil_spmv_dots`` this collapses a merged-CG iteration to two
HBM passes — the "single-pass fused iteration" benchmarked by
benchmarks/bench_kernels.py.  Oracle: ``ref.fused_cg_body_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_axpby import ROW, _to_2d
from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)


def _kernel(*refs):
    coef, r, ar, p, ap, p_out, ap_out, acc = refs
    beta = coef[0, 0]
    p_new = r[...] + beta * p[...]
    ap_new = ar[...] + beta * ap[...]
    p_out[...] = p_new
    ap_out[...] = ap_new
    accumulate(acc, [jnp.sum(ap_new * p_new).astype(acc.dtype)])


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def cg_fused_update(
    beta: jax.Array,
    r: jax.Array,
    ar: jax.Array,
    p: jax.Array,
    ap: jax.Array,
    *,
    br: int = 256,
    interpret: bool,
):
    """Returns ``(p_new, Ap_new, partial_dot)``."""
    shape = r.shape
    r2, n = _to_2d(r)
    ar2, _ = _to_2d(ar)
    p2, _ = _to_2d(p)
    ap2, _ = _to_2d(ap)
    rows = r2.shape[0]
    brr = min(br, rows)
    while rows % brr:
        brr -= 1
    coef = beta.astype(r.dtype).reshape(1, 1)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    p_new, ap_new, acc = pallas_call(
        _kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), blk(), blk(), blk(), blk()],
        out_specs=[blk(), blk(), scalar_spec()],
        out_shape=[
            out_struct(r2.shape, r.dtype, r),
            out_struct(r2.shape, r.dtype, r),
            out_struct((1, 1), acc_dtype(r.dtype), r),
        ],
        interpret=interpret,
    )(coef, r2, ar2, p2, ap2)
    return (
        p_new.reshape(-1)[:n].reshape(shape),
        ap_new.reshape(-1)[:n].reshape(shape),
        acc[0, 0],
    )


def _body_kernel(*refs):
    coef, x, r, p, s, w, x_out, r_out, p_out, s_out = refs
    alpha = coef[0, 0]
    beta = coef[0, 1]
    p_new = r[...] + beta * p[...]
    s_new = w[...] + beta * s[...]
    p_out[...] = p_new
    s_out[...] = s_new
    x_out[...] = x[...] + alpha * p_new
    r_out[...] = r[...] - alpha * s_new


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_cg_body(
    alpha: jax.Array,
    beta: jax.Array,
    x: jax.Array,
    r: jax.Array,
    p: jax.Array,
    s: jax.Array,
    w: jax.Array,
    *,
    # 9 live blocks (5 in + 4 out): br=256 would double-buffer past 16 MiB
    # VMEM (repro.analysis.lint_kernels)
    br: int = 128,
    interpret: bool,
):
    """One merged-CG iteration's four vector updates in one VMEM pass.

    Returns ``(x', r', p', s')`` with ``p' = r + β p``, ``s' = w + β s``,
    ``x' = x + α p'``, ``r' = r − α s'`` (the Chronopoulos–Gear ordering:
    x/r consume the UPDATED p/s).
    """
    shape = x.shape
    x2, n = _to_2d(x)
    r2, _ = _to_2d(r)
    p2, _ = _to_2d(p)
    s2, _ = _to_2d(s)
    w2, _ = _to_2d(w)
    rows = x2.shape[0]
    brr = min(br, rows)
    while rows % brr:
        brr -= 1
    coef = jnp.stack([alpha, beta]).astype(x.dtype).reshape(1, 2)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    outs = pallas_call(
        _body_kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)),
                  blk(), blk(), blk(), blk(), blk()],
        out_specs=[blk(), blk(), blk(), blk()],
        out_shape=[out_struct(x2.shape, x.dtype, x)] * 4,
        interpret=interpret,
    )(coef, x2, r2, p2, s2, w2)
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)
