"""The plain reference: HPCG's 27-point operator, written out, and a plain CG.

Independent of the code under test: nothing here imports ``repro``.

HPCG's 27-point matrix has ``27`` on the diagonal and ``-1`` for each of
the 26 neighbours of a point on the 3-D grid; neighbours outside the grid
are dropped (HPCG reference, ``GenerateProblem``).  So

    (A x)[i, j, k] = 27 x[i, j, k] - sum of x over the 26 neighbours

with the sum taken over the neighbours that exist.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

DIAG = 27.0
OFFSETS = tuple(o for o in itertools.product((-1, 0, 1), repeat=3)
                if o != (0, 0, 0))


def neighbour_sum(xp: jax.Array) -> jax.Array:
    """Sum of the 26 neighbours of every interior point of ``xp``, an array
    carrying one extra plane on each side of each dim."""
    nx, ny, nz = (s - 2 for s in xp.shape)
    acc = jnp.zeros((nx, ny, nz), xp.dtype)
    for dx, dy, dz in OFFSETS:
        acc = acc + xp[1 + dx:1 + dx + nx, 1 + dy:1 + dy + ny,
                       1 + dz:1 + dz + nz]
    return acc


def apply27(x: jax.Array) -> jax.Array:
    """``A x`` on the whole grid, in ``x``'s dtype."""
    return DIAG * x - neighbour_sum(jnp.pad(x, 1))


@functools.partial(jax.jit, static_argnums=(3,))
def _slab_sums(xpx: jax.Array, b: jax.Array, i0: jax.Array, slab: int):
    """(‖b − A x‖², ‖b‖²) over x-planes ``[i0, i0 + slab)`` in float64.

    ``xpx`` is ``x`` with one zero plane added at each end of the first dim,
    so the slab reads its neighbour planes from it.
    """
    xs = jax.lax.dynamic_slice_in_dim(xpx, i0, slab + 2, axis=0)
    xs = jnp.pad(xs.astype(jnp.float64), ((0, 0), (1, 1), (1, 1)))
    bs = jax.lax.dynamic_slice_in_dim(b, i0, slab, axis=0)
    bs = bs.astype(jnp.float64)
    r = bs - (DIAG * xs[1:-1, 1:-1, 1:-1] - neighbour_sum(xs))
    return jnp.sum(r * r), jnp.sum(bs * bs)


def residual_norms(x: jax.Array, b: jax.Array, slab: int = 32
                   ) -> tuple[float, float]:
    """``(‖b − A x‖, ‖b‖)`` computed in float64, a slab of x-planes at a
    time so that it fits beside whatever else the device holds.  Works on a
    sharded ``x``/``b`` as well: the slabs run along the first dim, and XLA
    exchanges the halos of any dim that is split.  Needs x64 enabled."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError("true_rel_residual needs jax_enable_x64")
    nx = x.shape[0]
    slab = min(slab, nx)
    while nx % slab:
        slab -= 1
    xpx = jnp.pad(x, ((1, 1), (0, 0), (0, 0)))
    rr = bb = 0.0
    for i0 in range(0, nx, slab):
        r2, b2 = _slab_sums(xpx, b, jnp.int32(i0), slab)
        rr += float(r2)
        bb += float(b2)
    return rr ** 0.5, bb ** 0.5


def true_rel_residual(x: jax.Array, b: jax.Array, slab: int = 32) -> float:
    """``‖b − A x‖ / ‖b‖`` by :func:`residual_norms`."""
    r, bn = residual_norms(x, b, slab)
    return r / bn


@functools.partial(jax.jit, static_argnames=("maxiter",))
def cg(b: jax.Array, tol: float, maxiter: int):
    """Plain conjugate gradients on ``A x = b`` from ``x0 = 0``, every
    vector and scalar in ``b``'s dtype, stopping at ‖r‖ < tol·‖b‖ or after
    ``maxiter`` iterations.  Returns ``(x, iterations)``."""
    dt = b.dtype
    stop = (jnp.asarray(tol, dt) ** 2) * jnp.vdot(b, b)
    x = jnp.zeros_like(b)
    rr = jnp.vdot(b, b)

    def cond(c):
        _, _, _, rr, k = c
        return (rr >= stop) & (k < maxiter)

    def body(c):
        x, r, p, rr, k = c
        q = apply27(p)
        alpha = rr / jnp.vdot(p, q)
        x = x + alpha * p
        r = r - alpha * q
        rr_new = jnp.vdot(r, r)
        p = r + (rr_new / rr) * p
        return x, r, p, rr_new, k + 1

    x, _, _, _, k = jax.lax.while_loop(cond, body, (x, b, b, rr, 0))
    return x, k
