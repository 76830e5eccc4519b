"""Operator layer: stencil vs ELL vs dense; SPD structure."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.operators import (
    STENCIL_7PT,
    STENCIL_27PT,
    Stencil,
    build_dense_from_stencil,
    build_ell_from_stencil,
    touched_elements_per_iter,
)

SHAPES = [(4, 4, 4), (5, 3, 6), (8, 8, 8)]


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT], ids=lambda s: s.name)
@pytest.mark.parametrize("shape", SHAPES)
def test_stencil_matches_ell(stencil, shape):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, shape, jnp.float32)
    y_st = stencil.matvec(x)
    ell = build_ell_from_stencil(stencil, shape)
    y_ell = ell.matvec(x)
    np.testing.assert_allclose(np.asarray(y_st), np.asarray(y_ell),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT], ids=lambda s: s.name)
def test_dense_symmetric_positive_definite(stencil):
    A = build_dense_from_stencil(stencil, (4, 4, 4))
    np.testing.assert_allclose(A, A.T)
    w = np.linalg.eigvalsh(A)
    assert w.min() > 0, "HPCG matrix must be SPD"


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT], ids=lambda s: s.name)
def test_matvec_adjoint(stencil):
    """A symmetric => <Ax, y> == <x, Ay>."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (6, 5, 4), jnp.float32)
    y = jax.random.normal(k2, (6, 5, 4), jnp.float32)
    lhs = jnp.vdot(stencil.matvec(x), y)
    rhs = jnp.vdot(x, stencil.matvec(y))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-4)


def test_offdiag_consistency():
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 5, 5), jnp.float32)
    xp = jnp.pad(x, 1)
    full = STENCIL_27PT.matvec_padded(xp)
    off = STENCIL_27PT.offdiag_apply_padded(xp)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(off + STENCIL_27PT.diag * x),
                               rtol=1e-5, atol=1e-5)


def test_plane_offdiag_matches_full():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 4, 6), jnp.float32)
    xp = jnp.pad(x, 1)
    off_full = STENCIL_27PT.offdiag_apply_padded(xp)
    for k in range(6):
        plane = STENCIL_27PT.plane_offdiag_apply(xp, k)
        np.testing.assert_allclose(np.asarray(plane),
                                   np.asarray(off_full[:, :, k]),
                                   rtol=1e-5, atol=1e-5)


def test_touched_elements_paper_table():
    """§3.1: CG (12+n)r vs CG-NB (15+n)r; BiCGStab (21+2n)r vs B1 (24+2n)r."""
    for nbar in (7, 27):
        assert touched_elements_per_iter("cg_nb", nbar) - \
            touched_elements_per_iter("cg", nbar) == 3
        assert touched_elements_per_iter("bicgstab_b1", nbar) - \
            touched_elements_per_iter("bicgstab", nbar) == 3
    # the paper's headline relative increases
    assert abs(3 / (12 + 7) - 0.158) < 1e-2
    assert abs(3 / (21 + 2 * 7) - 0.086) < 1e-2


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("split_dims", [(2,), (0, 2), (0, 1, 2)])
def test_interior_shell_split_matches_monolithic(stencil, split_dims):
    """The overlapped-SpMV decomposition: interior apply on the raw block +
    shell slabs from the padded array must reassemble to exactly the
    monolithic apply (the slice-add formulation), for any split set."""
    from repro.core.operators import interior_matvec, shell_assemble

    mv = stencil.matvec_padded
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 10), jnp.float32)
    # an arbitrary "exchanged" padded array: random halos on split dims
    xp = jax.random.normal(jax.random.PRNGKey(2), (8, 10, 12), jnp.float32)
    xp = xp.at[1:-1, 1:-1, 1:-1].set(x)
    for d in range(3):
        if d not in split_dims:     # unsplit dims keep the zero halo
            lo = [slice(None)] * 3
            hi = [slice(None)] * 3
            lo[d], hi[d] = 0, -1
            xp = xp.at[tuple(lo)].set(0.0).at[tuple(hi)].set(0.0)

    y_ref = jax.jit(mv)(xp)
    y_int = jax.jit(lambda a: interior_matvec(mv, a, split_dims))(x)
    y = jax.jit(lambda a, yi: shell_assemble(mv, a, yi, split_dims))(xp, y_int)
    assert y.shape == y_ref.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


def _slice_add(stencil, xp):
    """The apply as one shifted add per neighbour, in the stencil's order."""
    nx, ny, nz = (s - 2 for s in xp.shape)
    acc = stencil.diag * xp[1:-1, 1:-1, 1:-1]
    for dx, dy, dz in stencil.offsets:
        acc = acc + stencil.off_coeff * xp[1 + dx:1 + dx + nx,
                                           1 + dy:1 + dy + ny,
                                           1 + dz:1 + dz + nz]
    return acc


@pytest.mark.parametrize("padded", [(10, 10, 10), (7, 9, 12), (5, 7, 3)],
                         ids=["cubic", "non-cubic", "shell-slab"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT],
                         ids=lambda s: s.name)
def test_apply_matches_slice_add(stencil, dtype, padded, f64):
    """The 27-point apply (the separable box sum) equals the 26-slice sum
    to rounding; the 7-point apply is the slice-add formula bit for bit."""
    xp = jax.random.normal(jax.random.PRNGKey(4), padded, jnp.dtype(dtype))
    q = np.asarray(stencil.matvec_padded(xp))
    ref = np.asarray(_slice_add(stencil, xp))
    assert q.dtype == ref.dtype == np.dtype(dtype)
    assert q.shape == tuple(s - 2 for s in padded)
    if stencil.is_box:
        rtol = {"float32": 1e-6, "float64": 1e-14}[dtype]
        assert np.max(np.abs(q - ref)) <= rtol * np.max(np.abs(ref))
    else:
        np.testing.assert_array_equal(q, ref)


@pytest.mark.parametrize("offsets,box", [
    (STENCIL_27PT.offsets, True),
    (tuple(reversed(STENCIL_27PT.offsets)), True),
    (STENCIL_7PT.offsets, False),
    (STENCIL_27PT.offsets[1:], False),
], ids=["27pt", "27pt-permuted", "7pt", "27pt-less-one"])
def test_is_box(offsets, box):
    assert Stencil(name="s", offsets=offsets, diag=27.0).is_box is box
