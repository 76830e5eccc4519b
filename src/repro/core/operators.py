"""Sparse operators for HPCG-class problems, in a TPU-native formulation.

The paper (Martinez-Ferrer et al., JPDC 2023) works on the HPCCG/HPCG sparse
system: a 7-point or 27-point centred stencil on a 3-D hexahedral grid, stored
in CSR and applied with an irregular-gather SpMV (their Code 1/3).

TPU adaptation (DESIGN.md §2): irregular gathers are hostile to the VPU, but
the HPCG operator *is* a constant-coefficient stencil, so we keep the grid
dense, shaped ``(nx, ny, nz)``, and apply the operator as shifted adds over a
zero-padded array.  Zero halos reproduce the HPCG boundary treatment exactly
because the matrix keeps a constant diagonal and simply drops out-of-domain
neighbours (``-1 * 0 == dropped``).

The 27-point operator has one coefficient for all 26 neighbours, so its
apply is ``(diag - off) * x + off * box(x)`` with ``box`` the 3x3x3 sum
including the centre.  The box sum separates into three 3-point sums, one
per axis: 6 adds in place of 26, and each shift lies along one dim only
(XLA tiles the two minor dims, where a shift costs a relayout).  The 7-point
cross gains nothing from separating and keeps one shifted add per neighbour.

An ELLPACK path (`ELLOperator`) is retained for generality (any bounded-row
sparse matrix) and doubles as the cross-check oracle for the stencil path.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp


def _offsets_7pt() -> tuple[tuple[int, int, int], ...]:
    return (
        (-1, 0, 0), (1, 0, 0),
        (0, -1, 0), (0, 1, 0),
        (0, 0, -1), (0, 0, 1),
    )


def _offsets_27pt() -> tuple[tuple[int, int, int], ...]:
    offs = []
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                if (dx, dy, dz) != (0, 0, 0):
                    offs.append((dx, dy, dz))
    return tuple(offs)


def _box_sum(xp: jax.Array) -> jax.Array:
    """The 3x3x3 sum of a padded array, ``(nx+2, ny+2, nz+2)`` ->
    ``(nx, ny, nz)``: a 3-point sum along z, then y, then x.

    The order is fixed, so every output element takes the same additions
    wherever it lies (the interior/shell split stays exact).  z goes first
    because it is the lane dim of the one-chip layout, where a shift costs
    most; each sum is one pass over a partial that XLA keeps.
    """
    for axis in (2, 1, 0):
        n = xp.shape[axis] - 2
        xp = (jax.lax.slice_in_dim(xp, 0, n, axis=axis)
              + jax.lax.slice_in_dim(xp, 1, n + 1, axis=axis)
              + jax.lax.slice_in_dim(xp, 2, n + 2, axis=axis))
    return xp


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class Stencil:
    """Constant-coefficient centred stencil operator on a 3-D grid.

    ``A x`` for row (i,j,k):  ``diag * x[i,j,k] + off_coeff * sum(neigh x)``
    with out-of-domain neighbours dropped (== zero-padded halo).  When the
    offsets are the whole 26-neighbour box (``is_box``), ``matvec_padded``
    applies it as the separable box sum; any other offset set is applied
    one shifted add per neighbour.
    """

    name: str
    offsets: tuple[tuple[int, int, int], ...]
    diag: float
    off_coeff: float = -1.0

    @property
    def npoint(self) -> int:
        return len(self.offsets) + 1

    @property
    def nbar(self) -> int:
        """Average nonzeros per row (paper's n̄): 7 or 27 for interior rows."""
        return self.npoint

    @property
    def is_box(self) -> bool:
        """Whether the offsets are exactly the 26 neighbours of the 3x3x3
        box, each once (in any order): the off-diagonal part is then
        ``off_coeff * (box(x) - x)``."""
        return (len(self.offsets) == 26
                and set(self.offsets) == set(_offsets_27pt()))

    def matvec_padded(self, xp: jax.Array) -> jax.Array:
        """Apply to a halo-padded array ``(nx+2, ny+2, nz+2)`` -> ``(nx, ny, nz)``.

        This is the pure-jnp oracle; kernels/stencil_spmv.py is the Pallas
        version with explicit VMEM tiling.  A box stencil is applied as
        ``(diag - off_coeff) * x + off_coeff * box(x)``.  The barrier makes
        the result one array written by the apply: without it XLA recomputes
        the last axis sum and the combine inside each consumer of the
        result (CG's r update), outside the apply's pass.
        """
        if self.is_box:
            q = ((self.diag - self.off_coeff) * xp[1:-1, 1:-1, 1:-1]
                 + self.off_coeff * _box_sum(xp))
            return jax.lax.optimization_barrier(q)
        nx, ny, nz = xp.shape[0] - 2, xp.shape[1] - 2, xp.shape[2] - 2
        acc = self.diag * xp[1:-1, 1:-1, 1:-1]
        for dx, dy, dz in self.offsets:
            acc = acc + self.off_coeff * jax.lax.slice(
                xp, (1 + dx, 1 + dy, 1 + dz), (1 + dx + nx, 1 + dy + ny, 1 + dz + nz)
            )
        return acc

    def matvec(self, x: jax.Array) -> jax.Array:
        """Apply to an unpadded grid array ``(nx, ny, nz)`` with zero boundary."""
        return self.matvec_padded(jnp.pad(x, 1))

    # --- Gauss-Seidel helpers -------------------------------------------------
    def offdiag_apply_padded(self, xp: jax.Array) -> jax.Array:
        """(A - D) x on a padded array."""
        nx, ny, nz = xp.shape[0] - 2, xp.shape[1] - 2, xp.shape[2] - 2
        acc = jnp.zeros((nx, ny, nz), xp.dtype)
        for dx, dy, dz in self.offsets:
            acc = acc + self.off_coeff * jax.lax.slice(
                xp, (1 + dx, 1 + dy, 1 + dz), (1 + dx + nx, 1 + dy + ny, 1 + dz + nz)
            )
        return acc

    def plane_offdiag_apply(self, xp: jax.Array, k: jax.Array) -> jax.Array:
        """(A - D) x restricted to z-plane ``k`` of the interior.

        ``xp`` is the fully padded array; ``k`` may be traced (used inside the
        plane-sweep relaxed Gauss-Seidel loops).
        """
        nx, ny = xp.shape[0] - 2, xp.shape[1] - 2
        acc = jnp.zeros((nx, ny), xp.dtype)
        for dx, dy, dz in self.offsets:
            plane = jax.lax.dynamic_slice(
                xp, (1 + dx, 1 + dy, k + 1 + dz), (nx, ny, 1)
            )[:, :, 0]
            acc = acc + self.off_coeff * plane
        return acc


# -----------------------------------------------------------------------------
# Interior/boundary-shell split (the overlapped halo-exchange SpMV)
# -----------------------------------------------------------------------------
# The split is the task-based stencil decomposition of the paper's
# exchange_externals + SpMV: output cells at distance >= 1 from every
# decomposed face read no exchanged halo, so they can be computed while the
# ppermutes are in flight; only the one-cell-thick boundary shell waits for
# the received planes.  Both functions delegate the actual apply to a
# ``matvec_padded`` callable, so the slice-add and Pallas formulations
# split the same way.  Each output element's arithmetic is
# position-independent, so the split reproduces the monolithic apply exactly
# up to the compiler's per-shape FMA contraction choices; in the solver
# programs the results are bit-for-bit identical across halo modes
# (asserted by tests/test_halo_overlap.py on 7pt/27pt × 1-D/3-D layouts).

def interior_matvec(mv_padded, x: jax.Array,
                    split_dims: Sequence[int]) -> jax.Array:
    """Apply the stencil to the halo-independent interior of a local block.

    ``x`` is the UNPADDED local block.  Along each dim in ``split_dims`` the
    block itself provides the one-cell support of its interior (output extent
    ``n-2``); unsplit dims get the usual zero halo (physical boundary).
    """
    pad = [(0, 0) if d in split_dims else (1, 1) for d in range(3)]
    return mv_padded(jnp.pad(x, pad))


def shell_assemble(mv_padded, xp: jax.Array, y_interior: jax.Array,
                   split_dims: Sequence[int]) -> jax.Array:
    """Finish the split apply: boundary-shell slabs from the exchanged
    padded array ``xp``, concatenated around ``y_interior``.

    Slabs are computed per split dim (outermost last) over the still-interior
    extent of the dims assembled before them, so edge/corner cells are
    produced exactly once per assembly step from the same ``xp`` values the
    monolithic apply reads.
    """
    y = y_interior
    done: set[int] = set()
    for d in sorted(split_dims, reverse=True):
        def slab(lo: bool) -> jax.Array:
            starts, limits = [], []
            for e in range(3):
                pe = xp.shape[e]
                if e == d:                     # 3 planes -> 1 output plane
                    s = 0 if lo else pe - 3
                    starts.append(s)
                    limits.append(s + 3)
                elif e in split_dims and e not in done:
                    starts.append(1)           # dim still at interior extent
                    limits.append(pe - 1)
                else:
                    starts.append(0)           # assembled/unsplit: full extent
                    limits.append(pe)
            return mv_padded(jax.lax.slice(xp, starts, limits))

        y = jnp.concatenate([slab(True), y, slab(False)], axis=d)
        done.add(d)
    return y


# HPCCG's generator (the paper's host code) puts 27.0 on the diagonal and -1
# on every neighbour, for BOTH sparsity levels.  This makes the 7-pt matrix
# strongly diagonally dominant (27 vs 6), which is what yields the paper's
# §4.1 iteration counts (e.g. Jacobi converging in 18 iterations at 128^3);
# the 27-pt matrix is near-marginally dominant (27 vs 26) and converges slowly
# (515 Jacobi iterations).  Validated in benchmarks/table_iterations.py.
STENCIL_7PT = Stencil(name="7pt", offsets=_offsets_7pt(), diag=27.0)
STENCIL_27PT = Stencil(name="27pt", offsets=_offsets_27pt(), diag=27.0)

STENCILS = {"7pt": STENCIL_7PT, "27pt": STENCIL_27PT}


# -----------------------------------------------------------------------------
# ELLPACK general-sparse path (oracle + unstructured matrices)
# -----------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ELLOperator:
    """ELLPACK sparse matrix: fixed nonzeros-per-row, masked.

    ``indices``: (rows, k) int32 column ids (any value where mask is 0).
    ``values`` : (rows, k) float coefficients (0 where masked out).
    TPU note: the gather in ``matvec`` lowers to ``jnp.take`` — acceptable for
    moderate k, but the stencil path should be preferred for HPCG matrices.
    """

    indices: jax.Array
    values: jax.Array

    def tree_flatten(self):
        return (self.indices, self.values), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def rows(self) -> int:
        return self.indices.shape[0]

    def matvec(self, x: jax.Array) -> jax.Array:
        flat = x.reshape(-1)
        gathered = jnp.take(flat, self.indices, axis=0)  # (rows, k)
        y = jnp.sum(self.values * gathered, axis=1)
        return y.reshape(x.shape)


def build_ell_from_stencil(stencil: Stencil, shape: tuple[int, int, int]) -> ELLOperator:
    """Materialise the stencil on ``shape`` as an ELL matrix (host-side)."""
    nx, ny, nz = shape
    n = nx * ny * nz
    k = stencil.npoint
    idx = np.zeros((n, k), dtype=np.int32)
    val = np.zeros((n, k), dtype=np.float64)
    grid = np.arange(n).reshape(shape)
    # slot 0: diagonal
    idx[:, 0] = np.arange(n)
    val[:, 0] = stencil.diag
    for s, (dx, dy, dz) in enumerate(stencil.offsets, start=1):
        I, J, K = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz), indexing="ij")
        In, Jn, Kn = I + dx, J + dy, K + dz
        ok = (
            (In >= 0) & (In < nx) & (Jn >= 0) & (Jn < ny) & (Kn >= 0) & (Kn < nz)
        )
        neigh = grid[np.clip(In, 0, nx - 1), np.clip(Jn, 0, ny - 1), np.clip(Kn, 0, nz - 1)]
        idx[:, s] = np.where(ok, neigh, 0).reshape(-1)
        val[:, s] = np.where(ok, stencil.off_coeff, 0.0).reshape(-1)
    return ELLOperator(indices=jnp.asarray(idx), values=jnp.asarray(val))


def build_dense_from_stencil(stencil: Stencil, shape: tuple[int, int, int]) -> np.ndarray:
    """Dense matrix for tiny grids — used by tests against numpy/scipy solves."""
    ell = build_ell_from_stencil(stencil, shape)
    n = int(np.prod(shape))
    A = np.zeros((n, n))
    idx = np.asarray(ell.indices)
    val = np.asarray(ell.values)
    for r in range(n):
        for c, v in zip(idx[r], val[r]):
            A[r, c] += v
    return A


def touched_elements_per_iter(method: str, nbar: int) -> int:
    """Paper §3.1 analytic memory-traffic model, elements touched per row.

    CG: (12+n̄)r, CG-NB: (15+n̄)r, BiCGStab: (21+2n̄)r, BiCGStab-B1: (24+2n̄)r.
    Jacobi/GS counts derived with the same accounting (SpMV reads n̄+1 per row
    incl. the row of coefficients, plus the vector traffic of the updates).
    """
    table = {
        "cg": 12 + nbar,
        "cg_nb": 15 + nbar,
        "bicgstab": 21 + 2 * nbar,
        "bicgstab_b1": 24 + 2 * nbar,
        # preconditioned forms: the baseline's traffic + the z (pcg) or
        # phat/shat (pbicgstab) vector updates; the preconditioner apply's
        # own traffic is accounted separately (Preconditioner.
        # touched_elements_per_apply × MethodDef.precond_applies_per_iter)
        "pcg": 16 + nbar,
        "pbicgstab": 27 + 2 * nbar,
        # reduction-hiding variants (PR 4), same accounting (3 per
        # two-operand vector update, dot reads folded in like cg's 12):
        # merged CG adds the s = A p recurrence (+3 over cg); pipelined CG
        # adds z and the w recurrence on top (+6 over merged); the
        # preconditioned forms add the u/q image traffic like pcg does;
        # merged BiCGStab streams 8 recurrence updates + 9 fused dots.
        "cg_merged": 15 + nbar,
        "cg_pipe": 21 + nbar,
        "pcg_merged": 19 + nbar,
        "pcg_pipe": 28 + nbar,
        "bicgstab_merged": 33 + 2 * nbar,
        "pbicgstab_merged": 33 + 2 * nbar,
        "jacobi": 4 + nbar,
        "gauss_seidel": 6 + 2 * nbar,
        # red-black symmetric GS: 4 coloured half-sweeps + residual, each
        # half-sweep streams the full offdiag stencil (same accounting as
        # the relaxed variant; the colouring changes convergence, not the
        # per-sweep traffic)
        "gauss_seidel_rb": 6 + 2 * nbar,
    }
    return table[method]
