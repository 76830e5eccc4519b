"""``PallasOp``: the LocalOp-protocol operator backed by the fused kernels.

Before PR 5 the fused Pallas iteration (``fused_cg_body`` + ``spmv_dots``)
was a local-only special case hard-wired to ``cg_merged`` in the facade.
``PallasOp`` turns it into a *backend*: it wraps any operator satisfying the
``LocalOp`` protocol (``LocalOp`` itself, or a ``DistributedOp`` inside a
``shard_map`` body) and supplies

  * the protocol surface (``matvec``/``matvec_local``/``pad_exchange``/
    ``diag``/``dot``/``dotn``) with the stencil apply running on the Pallas
    SpMV kernel, and
  * the fused-iteration hooks the ``MethodDef.fused_step`` bodies are
    written against.  PR 10 grew these from the lone merged-CG pair
    (``cg_body`` + ``spmv_dots``) to the full reduction-hiding family:
    ``spmv_dots3``/``pcg_body`` (merged PCG), ``pipe_body`` (pipelined CG),
    ``fused_dots``/``ppipe_body`` (pipelined PCG) and the three-kernel
    BiCGStab set (``bicgstab_spmv_dots``/``bicgstab_update1``/
    ``bicgstab_spmv_update``).

Tile sizes come from ``kernels.autotune`` unless pinned: with the default
``bz=None`` each call resolves the persisted ``(stencil, grid, dtype,
device_kind)`` cache entry (falling back to the documented default table)
at trace time, so a tuning run changes the compiled tilings without any
call-site change.

Halo exchange comes from the wrapped operator (``jnp.pad`` locally,
ppermutes on a mesh) and the fused kernels' locally-accumulated dot
partials are made global through the wrapped operator's ``sum_partials``
(identity locally, ONE stacked psum on a mesh) — so the same fused method
body executes single-device and inside shard_map, which is how
``cg_merged`` + ``pallas=True`` now runs distributed.

The preconditioner fused kernels (``cheb_fused_step``, ``block_jacobi_sweep``)
ride the same wrapper: ``repro.precond`` binds against the PallasOp like any
other operator, so ``use_pallas`` preconditioners compose with the fused
solvers inside shard_map too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.methods import local_dot
from repro.kernels import autotune, ops


class PallasOp:
    """Pallas-kernel execution of a wrapped LocalOp-protocol operator.

    ``bz=None`` (the default) consults the autotune cache per call; an
    explicit ``bz`` pins the slab depth and skips tuning entirely.
    """

    def __init__(self, base, *, bz: int | None = None):
        self.base = base
        self.stencil = base.stencil
        self.bz = bz

    def _tiles(self, x: jax.Array) -> tuple[int, int | None]:
        """(bz, br) for the local interior shape ``x`` — pinned or tuned.

        Runs at trace time (shapes/dtypes are static), so the cache lookup
        costs nothing per iteration; ``br=None`` keeps each row-tiled
        kernel's own VMEM-budgeted default.
        """
        if self.bz is not None:
            return self.bz, None
        dec = autotune.resolve(self.stencil.name, x.shape, x.dtype)
        return dec.bz, dec.br

    @property
    def diag(self) -> float:
        return self.base.diag

    # --- protocol surface (halos/reductions delegate to the wrapped op) ------
    def pad_exchange(self, x: jax.Array) -> jax.Array:
        return self.base.pad_exchange(x)

    def matvec(self, x: jax.Array) -> jax.Array:
        return ops.spmv(self.pad_exchange(x), self.stencil, bz=self._tiles(x)[0])

    def matvec_local(self, x: jax.Array) -> jax.Array:
        return ops.spmv(jnp.pad(x, 1), self.stencil, bz=self._tiles(x)[0])

    @property
    def dot(self):
        d = getattr(self.base, "dot", None)
        return d if d is not None else local_dot

    def dotn(self, *pairs) -> tuple:
        return self.base.dotn(*pairs)

    def sum_partials(self, *vals) -> tuple:
        return self.base.sum_partials(*vals)

    # --- fused-iteration hooks (what MethodDef.fused_step is written against)
    def spmv_dots(self, x: jax.Array) -> tuple:
        """``(A·x, (A·x)·x, x·x)`` in one VMEM pass; the two dot partials are
        accumulated per local block inside the kernel and reduced globally
        through the wrapped operator (one stacked psum on a mesh)."""
        w, delta, gamma = ops.spmv_dots(self.pad_exchange(x), self.stencil,
                                        bz=self._tiles(x)[0])
        delta, gamma = self.sum_partials(delta, gamma)
        return w, delta, gamma

    def spmv_dots3(self, x: jax.Array, r: jax.Array) -> tuple:
        """``(A·x, (A·x)·x, r·x, r·r)`` in one VMEM pass — merged PCG's
        reduction triple (``x = u``) and pipelined CG's (``x = w``, first
        slot unused).  One stacked psum on a mesh."""
        y, yx, rx, rr = ops.spmv_dots3(self.pad_exchange(x), r, self.stencil,
                                       bz=self._tiles(x)[0])
        yx, rx, rr = self.sum_partials(yx, rx, rr)
        return y, yx, rx, rr

    def fused_dots(self, r, u, w) -> tuple:
        """``(r·u, w·u, r·r)`` in one read pass (pipelined PCG's triple on
        carried state); one stacked psum on a mesh."""
        return self.sum_partials(*ops.fused_dots(r, u, w))

    def cg_body(self, alpha, beta, x, r, p, s, w) -> tuple:
        """Merged-CG's four vector updates in one VMEM pass (shard-local —
        no communication, so it needs no wrapping)."""
        br = self._tiles(x)[1]
        if br is not None:
            return ops.cg_body(alpha, beta, x, r, p, s, w, br=br)
        return ops.cg_body(alpha, beta, x, r, p, s, w)

    def pcg_body(self, alpha, beta, x, r, u, p, s, w) -> tuple:
        """Merged PCG's four vector updates (shard-local)."""
        br = self._tiles(x)[1]
        if br is not None:
            return ops.pcg_body(alpha, beta, x, r, u, p, s, w, br=br)
        return ops.pcg_body(alpha, beta, x, r, u, p, s, w)

    def pipe_body(self, alpha, beta, x, r, w, p, s, z, n) -> tuple:
        """Pipelined CG's six vector recurrences (shard-local)."""
        return ops.pipe_body(alpha, beta, x, r, w, p, s, z, n)

    def ppipe_body(self, alpha, beta, x, r, u, w, p, s, q, z, m, n) -> tuple:
        """Pipelined PCG's eight vector recurrences (shard-local)."""
        return ops.ppipe_body(alpha, beta, x, r, u, w, p, s, q, z, m, n)

    def bicgstab_spmv_dots(self, zi, z, r, w, s, rhat, t, alpha) -> tuple:
        """BiCGStab sweep 1: ``v = A·z̃`` + ``q``/``y`` + all 9 partials;
        the partials ride ONE stacked psum on a mesh."""
        v, q, y, parts = ops.bicgstab_spmv_dots(
            self.pad_exchange(zi), z, r, w, s, rhat, t, alpha, self.stencil,
            bz=self._tiles(z)[0])
        return v, q, y, self.sum_partials(*parts)

    def bicgstab_update1(self, alpha, omega, y, p, q, yv, t, v) -> tuple:
        """BiCGStab's ω-half x/r/w updates (shard-local)."""
        return ops.bicgstab_update1(alpha, omega, y, p, q, yv, t, v)

    def bicgstab_spmv_update(self, wi, w, r, p, s, z, v, omega, beta) -> tuple:
        """BiCGStab sweep 2: ``t' = A·w̃`` + the direction recurrences."""
        return ops.bicgstab_spmv_update(
            self.pad_exchange(wi), w, r, p, s, z, v, omega, beta,
            self.stencil, bz=self._tiles(w)[0])
