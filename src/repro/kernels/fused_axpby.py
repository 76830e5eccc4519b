"""Pallas TPU kernel: the paper's ad hoc vector-update kernel.

§3.1: CG-NB's extra vector update "can be optimised via the ad hoc kernel
``z := a·x + b·y + c·z`` that reuses memory".  This kernel does exactly that
in one VMEM pass, optionally fusing a dot-product partial (``out·w``) so the
following reduction needs no extra sweep — the fork-join "kernel switch
barrier" the paper's tasking removes corresponds here to an extra HBM round
trip, removed by fusion.

Data is processed as (rows, 128·k) tiles: the wrapper reshapes flat vectors
into lane-aligned 2-D blocks (TPU VPU registers are 8×128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)

#: lane-aligned row width used by the flat-vector wrappers
ROW = 1024


def _kernel(fuse_dot: bool, br: int, cols: int):
    def body(*refs):
        if fuse_dot:
            coef, x, y, z, w, out, acc = refs
        else:
            coef, x, y, z, out = refs
        a = coef[0, 0]
        b = coef[0, 1]
        c = coef[0, 2]
        r = a * x[...] + b * y[...] + c * z[...]
        out[...] = r
        if fuse_dot:
            accumulate(acc, [jnp.sum(r * w[...]).astype(acc.dtype)])

    return body


def _to_2d(v: jax.Array) -> tuple[jax.Array, int]:
    n = v.size
    pad = (-n) % ROW
    if pad:
        v = jnp.concatenate([v.reshape(-1), jnp.zeros((pad,), v.dtype)])
    return v.reshape(-1, ROW), n


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_axpby(
    a: jax.Array,
    x: jax.Array,
    b: jax.Array,
    y: jax.Array,
    c: jax.Array,
    z: jax.Array,
    *,
    br: int = 256,
    interpret: bool,
) -> jax.Array:
    """``a·x + b·y + c·z`` elementwise, any (matching) shapes."""
    shape = x.shape
    x2, n = _to_2d(x)
    y2, _ = _to_2d(y)
    z2, _ = _to_2d(z)
    rows = x2.shape[0]
    brr = min(br, rows)
    while rows % brr:
        brr -= 1
    coef = jnp.stack([a, b, c]).astype(x.dtype).reshape(1, 3)
    out = pallas_call(
        _kernel(False, brr, ROW),
        grid=(rows // brr,),
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
        out_shape=out_struct(x2.shape, x.dtype, x),
        interpret=interpret,
    )(coef, x2, y2, z2)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def fused_axpby_dot(
    a: jax.Array,
    x: jax.Array,
    b: jax.Array,
    y: jax.Array,
    c: jax.Array,
    z: jax.Array,
    w: jax.Array,
    *,
    br: int = 256,
    interpret: bool,
):
    """``out = a·x + b·y + c·z`` and the fused partial ``dot(out, w)``."""
    shape = x.shape
    x2, n = _to_2d(x)
    y2, _ = _to_2d(y)
    z2, _ = _to_2d(z)
    w2, _ = _to_2d(w)
    rows = x2.shape[0]
    brr = min(br, rows)
    while rows % brr:
        brr -= 1
    coef = jnp.stack([a, b, c]).astype(x.dtype).reshape(1, 3)
    out, acc = pallas_call(
        _kernel(True, brr, ROW),
        grid=(rows // brr,),
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((brr, ROW), lambda i: (i, 0)),
            scalar_spec(),
        ],
        out_shape=[
            out_struct(x2.shape, x.dtype, x),
            out_struct((1, 1), acc_dtype(x.dtype), x),
        ],
        interpret=interpret,
    )(coef, x2, y2, z2, w2)
    return out.reshape(-1)[:n].reshape(shape), acc[0, 0]
