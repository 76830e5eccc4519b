"""Pallas TPU kernel: 7/27-point stencil SpMV with x-slab VMEM tiling.

The paper's hot kernel is the CSR SpMV (Code 1/3).  On TPU we exploit the
structure (DESIGN.md §2): the operator is a constant-coefficient stencil, so
each grid step streams a slab of ``bx`` x-planes (plus one halo plane on each
side — an *overlapping-window* ``pl.Element`` BlockSpec, HBM traffic
(bx+2)/bx instead of re-reading neighbours) into VMEM and applies the
stencil plane by plane as shifted 2-D adds on the VPU.

Layout: the slab runs over the leading axis, so every block's last two dims
are whole (y, z) planes — equal to the array's own dims, which is what the
TPU compiler requires of a block whose dims are not multiples of (8, 128).
z is the lane dim and y the sublane dim.  Inside a grid step a
``fori_loop`` walks the ``bx`` output planes, so the live values are a few
planes whatever the slab depth.

Fusion (the task-merging analogue, §3.3): ``fuse_dot=True`` additionally
accumulates the partial ``(A·x)·x`` reduction in the same VMEM pass — this is
what lets CG compute ``α_d = (A·p)·p`` without a second memory sweep.  The
accumulator output lives in SMEM and revisits the same block every grid
step; TPU grid iterations are sequential, so the accumulation is
well-defined.

VMEM per grid step, double-buffered: (bx+2) padded planes of the window
plus bx planes per unpadded operand.  ``slab_depth`` shrinks the requested
``bx`` until that fits ``VMEM_BLOCK_BUDGET``; a 512² f32 plane is ~1 MiB,
so the SpMV runs 512³ at bx=8 in ~42 MiB under the raised
``VMEM_LIMIT_BYTES``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.operators import Stencil
from repro.kernels.blocks import (accumulate, acc_dtype, out_struct,
                                  pallas_call, scalar_spec)

#: scoped VMEM a stencil kernel may claim (TPU v5e has 128 MiB of VMEM; the
#: compiler's default scoped limit is 16 MiB)
VMEM_LIMIT_BYTES = 96 * 2 ** 20
#: what the double-buffered blocks may take of it; the rest holds the
#: kernel's live planes
VMEM_BLOCK_BUDGET = 64 * 2 ** 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _plane_bytes(ny: int, nz: int, itemsize: int) -> int:
    """VMEM bytes of one (ny, nz) plane on the TPU's (sublane, 128) tiles."""
    return _round_up(ny, max(8, 32 // itemsize)) * _round_up(nz, 128) * itemsize


def slab_depth(shape, dtype, requested: int, *, blocks: int) -> int:
    """The x-slab depth: the largest divisor of ``nx`` that is at most
    ``requested`` and whose double-buffered window plus ``blocks`` unpadded
    (bx, ny, nz) in/out blocks fit ``VMEM_BLOCK_BUDGET``."""
    nx, ny, nz = shape
    item = jnp.dtype(dtype).itemsize
    win = _plane_bytes(ny + 2, nz + 2, item)
    blk = _plane_bytes(ny, nz, item)
    for bx in range(min(requested, nx), 0, -1):
        if nx % bx == 0 and 2 * ((bx + 2) * win + bx * blocks * blk) \
                <= VMEM_BLOCK_BUDGET:
            return bx
    raise ValueError(
        f"a ({ny}, {nz}) plane is too large for the x-slab stencil kernels: "
        f"even one plane per grid step exceeds {VMEM_BLOCK_BUDGET} bytes "
        f"of VMEM")


def window_spec(bx: int, ny: int, nz: int) -> pl.BlockSpec:
    """The overlapping (bx+2, ny+2, nz+2) input window, x-indexed by element
    offset ``i*bx``; the (y, z) dims are whole planes."""
    return pl.BlockSpec(
        (pl.Element(bx + 2), pl.Element(ny + 2), pl.Element(nz + 2)),
        lambda i: (i * bx, 0, 0))


def slab_spec(bx: int, ny: int, nz: int) -> pl.BlockSpec:
    """An unpadded (bx, ny, nz) slab of an interior-shaped operand."""
    return pl.BlockSpec((bx, ny, nz), lambda i: (i, 0, 0))


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def apply_stencil_plane(stencil: Stencil, xin, p, ny: int, nz: int, *,
                        diag: bool = True):
    """``A x`` (or its off-diagonal part, ``diag=False``) on output plane
    ``p`` of a window ref: reads window planes ``p``, ``p+1`` and ``p+2``,
    each loaded once and shifted in (y, z).

    The shared plane-apply of every stencil-consuming kernel (SpMV here,
    the fused solver and preconditioner steps elsewhere).
    """
    by_dx: dict[int, list[tuple[int, int, float]]] = {-1: [], 0: [], 1: []}
    if diag:
        by_dx[0].append((0, 0, stencil.diag))
    for dx, dy, dz in stencil.offsets:
        by_dx[dx].append((dy, dz, stencil.off_coeff))
    y = None
    for dx, terms in by_dx.items():
        if not terms:
            continue
        plane = xin[p + 1 + dx]
        for dy, dz, c in terms:
            t = c * plane[1 + dy: 1 + dy + ny, 1 + dz: 1 + dz + nz]
            y = t if y is None else y + t
    return y


def centre_plane(xin, p, ny: int, nz: int):
    """The interior of window plane ``p+1`` (output plane ``p``'s own x)."""
    return xin[p + 1, 1: 1 + ny, 1: 1 + nz]


def plane_loop(bx: int, body, n_acc: int = 0, dtype=jnp.float32):
    """Run ``body(p, partials) -> partials`` over the slab's ``bx`` planes,
    carrying ``n_acc`` scalar dot partials; returns them."""
    init = tuple(jnp.zeros((), dtype) for _ in range(n_acc))
    return jax.lax.fori_loop(0, bx, body, init)


def _kernel(stencil: Stencil, bx: int, ny: int, nz: int, fuse_dot: bool):
    def body(*refs):
        if fuse_dot:
            xin, out, acc = refs
        else:
            xin, out = refs

        def plane(p, parts):
            y = apply_stencil_plane(stencil, xin, p, ny, nz)
            out[p] = y
            if not fuse_dot:
                return parts
            c = centre_plane(xin, p, ny, nz)
            return (parts[0] + jnp.sum(y * c).astype(acc.dtype),)

        parts = plane_loop(bx, plane, 1 if fuse_dot else 0,
                           acc.dtype if fuse_dot else jnp.float32)
        if fuse_dot:
            accumulate(acc, parts)

    return body


@functools.partial(
    jax.jit, static_argnames=("stencil", "bz", "fuse_dot", "interpret")
)
def stencil_spmv(
    xp: jax.Array,
    *,
    stencil: Stencil,
    bz: int = 8,
    fuse_dot: bool = False,
    interpret: bool,
):
    """``y = A·x`` (and optionally ``y·x``) from the halo-padded ``xp``.

    ``xp``: (nx+2, ny+2, nz+2); ``bz`` is the requested slab depth in
    x-planes.  Returns ``y`` of shape (nx, ny, nz), or ``(y, dot)`` when
    ``fuse_dot``.
    """
    nx, ny, nz = xp.shape[0] - 2, xp.shape[1] - 2, xp.shape[2] - 2
    bx = slab_depth((nx, ny, nz), xp.dtype, bz, blocks=1)

    out_shape = [out_struct((nx, ny, nz), xp.dtype, xp)]
    out_specs = [slab_spec(bx, ny, nz)]
    if fuse_dot:
        out_shape.append(out_struct((1, 1), acc_dtype(xp.dtype), xp))
        out_specs.append(scalar_spec())

    res = pallas_call(
        _kernel(stencil, bx, ny, nz, fuse_dot),
        grid=(nx // bx,),
        in_specs=[window_spec(bx, ny, nz)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(xp)
    if fuse_dot:
        return res[0], res[1][0, 0]
    return res[0]
