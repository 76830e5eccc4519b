"""Pallas TPU kernels: the vector-update halves of every fused Krylov body.

PR 4 gave ``cg_merged`` a single-pass vector-update kernel
(``cg_fused_update.fused_cg_body``); this module (PR 10) extends the family
to the rest of the reduction-hiding variants, so each of their
``MethodDef.fused_step`` bodies runs as two-to-three VMEM-resident HBM
passes instead of the 5–9 separate axpy/dot dispatches of the fork-join
form:

  * ``fused_pipe_body``  — pipelined CG's SIX recurrences (z, s, p, x, r, w)
    in one pass (``cg_pipe``).
  * ``fused_pcg_body``   — merged PCG's four updates; identical to
    ``fused_cg_body`` except ``p' = u + β p`` reads the *preconditioned*
    residual (``pcg_merged``).
  * ``fused_ppipe_body`` — pipelined PCG's EIGHT recurrences (``pcg_pipe``).
  * ``fused_dots``       — the stacked partial-dot triple ``(a·b, c·b, a·a)``
    with no SpMV attached: pipelined PCG needs its reduction on carried
    state *before* the preconditioner apply, so the dots get their own
    single read pass.
  * ``bicgstab_fused_update1`` — single-reduction BiCGStab's mid-iteration
    x/r/w updates (the ω half), between the two SpMV passes of
    ``bicgstab_fused.py``.

All use the flattened (br, 1024) row tiling of ``fused_axpby``; scalars ride
a (1, k) coefficient block.  Block sizes are VMEM-budgeted in
``repro.analysis.lint_kernels`` (n_live_blocks × br × 1024, double-buffered)
and tunable via ``kernels.autotune``.  Oracles: ``kernels/ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_axpby import ROW, _to_2d
from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)


def _tile(v):
    v2, n = _to_2d(v)
    return v2, n


def _row_grid(rows: int, br: int) -> int:
    brr = min(br, rows)
    while rows % brr:
        brr -= 1
    return brr


def _dots_kernel(*refs):
    a, b, c, acc = refs
    av, bv, cv = a[...], b[...], c[...]
    accumulate(acc, [jnp.sum(av * bv).astype(acc.dtype),
                     jnp.sum(cv * bv).astype(acc.dtype),
                     jnp.sum(av * av).astype(acc.dtype)])


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_dots(
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    br: int = 256,
    interpret: bool,
):
    """Stacked partial dots ``(a·b, c·b, a·a)`` in ONE read pass.

    Pipelined PCG's reduction triple on carried state: with
    ``(a, b, c) = (r, u, w)`` this is ``(γ = r·u, δ = w·u, ‖r‖²)``.
    """
    a2, _ = _tile(a)
    b2, _ = _tile(b)
    c2, _ = _tile(c)
    rows = a2.shape[0]
    brr = _row_grid(rows, br)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    acc = pallas_call(
        _dots_kernel,
        grid=(rows // brr,),
        in_specs=[blk(), blk(), blk()],
        out_specs=[scalar_spec()],
        out_shape=[out_struct((1, 3), acc_dtype(a.dtype), a)],
        interpret=interpret,
    )(a2, b2, c2)[0]
    return acc[0, 0], acc[0, 1], acc[0, 2]


def _pipe_kernel(*refs):
    coef, x, r, w, p, s, z, n, x_o, r_o, w_o, p_o, s_o, z_o = refs
    alpha = coef[0, 0]
    beta = coef[0, 1]
    z_new = n[...] + beta * z[...]
    s_new = w[...] + beta * s[...]
    p_new = r[...] + beta * p[...]
    z_o[...] = z_new
    s_o[...] = s_new
    p_o[...] = p_new
    x_o[...] = x[...] + alpha * p_new
    r_o[...] = r[...] - alpha * s_new
    w_o[...] = w[...] - alpha * z_new


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_pipe_body(
    alpha: jax.Array,
    beta: jax.Array,
    x: jax.Array,
    r: jax.Array,
    w: jax.Array,
    p: jax.Array,
    s: jax.Array,
    z: jax.Array,
    n: jax.Array,
    *,
    br: int = 64,   # 13 live blocks (7 in + 6 out): see lint_kernels budget
    interpret: bool,
):
    """Pipelined CG's six vector recurrences in one VMEM pass.

    ``z' = n + βz``, ``s' = w + βs``, ``p' = r + βp``, ``x' = x + αp'``,
    ``r' = r − αs'``, ``w' = w − αz'`` (Ghysels–Vanroose ordering).
    Returns ``(x', r', w', p', s', z')``.
    """
    shape = x.shape
    tiles = [_tile(v)[0] for v in (x, r, w, p, s, z, n)]
    nflat = x.size
    rows = tiles[0].shape[0]
    brr = _row_grid(rows, br)
    coef = jnp.stack([alpha, beta]).astype(x.dtype).reshape(1, 2)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    outs = pallas_call(
        _pipe_kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0))] + [blk()] * 7,
        out_specs=[blk()] * 6,
        out_shape=[out_struct(tiles[0].shape, x.dtype, x)] * 6,
        interpret=interpret,
    )(coef, *tiles)
    return tuple(o.reshape(-1)[:nflat].reshape(shape) for o in outs)


def _pcg_kernel(*refs):
    coef, x, r, u, p, s, w, x_o, r_o, p_o, s_o = refs
    alpha = coef[0, 0]
    beta = coef[0, 1]
    p_new = u[...] + beta * p[...]
    s_new = w[...] + beta * s[...]
    p_o[...] = p_new
    s_o[...] = s_new
    x_o[...] = x[...] + alpha * p_new
    r_o[...] = r[...] - alpha * s_new


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_pcg_body(
    alpha: jax.Array,
    beta: jax.Array,
    x: jax.Array,
    r: jax.Array,
    u: jax.Array,
    p: jax.Array,
    s: jax.Array,
    w: jax.Array,
    *,
    br: int = 128,   # 10 live blocks (6 in + 4 out)
    interpret: bool,
):
    """Merged PCG's four vector updates in one VMEM pass.

    ``p' = u + βp`` (the preconditioned residual drives the search
    direction), ``s' = w + βs``, ``x' = x + αp'``, ``r' = r − αs'``.
    Returns ``(x', r', p', s')``.
    """
    shape = x.shape
    tiles = [_tile(v)[0] for v in (x, r, u, p, s, w)]
    nflat = x.size
    rows = tiles[0].shape[0]
    brr = _row_grid(rows, br)
    coef = jnp.stack([alpha, beta]).astype(x.dtype).reshape(1, 2)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    outs = pallas_call(
        _pcg_kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0))] + [blk()] * 6,
        out_specs=[blk()] * 4,
        out_shape=[out_struct(tiles[0].shape, x.dtype, x)] * 4,
        interpret=interpret,
    )(coef, *tiles)
    return tuple(o.reshape(-1)[:nflat].reshape(shape) for o in outs)


def _ppipe_kernel(*refs):
    (coef, x, r, u, w, p, s, q, z, m, n,
     x_o, r_o, u_o, w_o, p_o, s_o, q_o, z_o) = refs
    alpha = coef[0, 0]
    beta = coef[0, 1]
    z_new = n[...] + beta * z[...]
    q_new = m[...] + beta * q[...]
    s_new = w[...] + beta * s[...]
    p_new = u[...] + beta * p[...]
    z_o[...] = z_new
    q_o[...] = q_new
    s_o[...] = s_new
    p_o[...] = p_new
    x_o[...] = x[...] + alpha * p_new
    r_o[...] = r[...] - alpha * s_new
    u_o[...] = u[...] - alpha * q_new
    w_o[...] = w[...] - alpha * z_new


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_ppipe_body(
    alpha: jax.Array,
    beta: jax.Array,
    x: jax.Array,
    r: jax.Array,
    u: jax.Array,
    w: jax.Array,
    p: jax.Array,
    s: jax.Array,
    q: jax.Array,
    z: jax.Array,
    m: jax.Array,
    n: jax.Array,
    *,
    br: int = 64,   # 18 live blocks (10 in + 8 out)
    interpret: bool,
):
    """Pipelined PCG's eight vector recurrences in one VMEM pass.

    ``z' = n + βz``, ``q' = m + βq``, ``s' = w + βs``, ``p' = u + βp``,
    ``x' = x + αp'``, ``r' = r − αs'``, ``u' = u − αq'``, ``w' = w − αz'``.
    Returns ``(x', r', u', w', p', s', q', z')``.
    """
    shape = x.shape
    tiles = [_tile(v)[0] for v in (x, r, u, w, p, s, q, z, m, n)]
    nflat = x.size
    rows = tiles[0].shape[0]
    brr = _row_grid(rows, br)
    coef = jnp.stack([alpha, beta]).astype(x.dtype).reshape(1, 2)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    outs = pallas_call(
        _ppipe_kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0))] + [blk()] * 10,
        out_specs=[blk()] * 8,
        out_shape=[out_struct(tiles[0].shape, x.dtype, x)] * 8,
        interpret=interpret,
    )(coef, *tiles)
    return tuple(o.reshape(-1)[:nflat].reshape(shape) for o in outs)


def _bicgstab_u1_kernel(*refs):
    coef, y, p, q, yv, t, v, y_o, r_o, w_o = refs
    alpha = coef[0, 0]
    omega = coef[0, 1]
    y_o[...] = y[...] + alpha * p[...] + omega * q[...]
    r_o[...] = q[...] - omega * yv[...]
    w_o[...] = yv[...] - omega * (t[...] - alpha * v[...])


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def bicgstab_fused_update1(
    alpha: jax.Array,
    omega: jax.Array,
    y: jax.Array,
    p: jax.Array,
    q: jax.Array,
    yv: jax.Array,
    t: jax.Array,
    v: jax.Array,
    *,
    br: int = 128,   # 9 live blocks (6 in + 3 out)
    interpret: bool,
):
    """Single-reduction BiCGStab's ω-half updates in one VMEM pass.

    ``y' = y + αp + ωq``, ``r' = q − ω·yv``, ``w' = yv − ω(t − αv)``
    (Cools–Vanroose recurrences between the iteration's two SpMVs).
    Returns ``(y', r', w')``.
    """
    shape = y.shape
    tiles = [_tile(v_)[0] for v_ in (y, p, q, yv, t, v)]
    nflat = y.size
    rows = tiles[0].shape[0]
    brr = _row_grid(rows, br)
    coef = jnp.stack([alpha, omega]).astype(y.dtype).reshape(1, 2)
    blk = lambda: pl.BlockSpec((brr, ROW), lambda i: (i, 0))
    outs = pallas_call(
        _bicgstab_u1_kernel,
        grid=(rows // brr,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0))] + [blk()] * 6,
        out_specs=[blk()] * 3,
        out_shape=[out_struct(tiles[0].shape, y.dtype, y)] * 3,
        interpret=interpret,
    )(coef, *tiles)
    return tuple(o.reshape(-1)[:nflat].reshape(shape) for o in outs)
