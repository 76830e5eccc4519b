"""The plain 27-point reference against a dense matrix built here."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, reference


def dense27(n: int) -> np.ndarray:
    """HPCG's 27-point matrix on an n³ grid: 27 on the diagonal, -1 for
    each neighbour inside the grid."""
    idx = np.arange(n ** 3).reshape(n, n, n)
    a = np.zeros((n ** 3, n ** 3))
    for i, j, k in itertools.product(range(n), repeat=3):
        row = idx[i, j, k]
        for dx, dy, dz in itertools.product((-1, 0, 1), repeat=3):
            p, q, r = i + dx, j + dy, k + dz
            if 0 <= p < n and 0 <= q < n and 0 <= r < n:
                a[row, idx[p, q, r]] = 27.0 if (dx, dy, dz) == (0, 0, 0) \
                    else -1.0
    return a


@pytest.fixture(scope="module")
def a8():
    return dense27(8)


def test_apply27_matches_dense_at_8(a8):
    x = np.random.default_rng(0).uniform(0.5, 1.5, (8, 8, 8))
    got = np.asarray(reference.apply27(jnp.asarray(x, jnp.float32)))
    want = (a8 @ x.reshape(-1)).reshape(8, 8, 8)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("slab", [1, 3, 8])
def test_true_rel_residual_matches_dense(a8, slab):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 8, 8)).astype(np.float32)
    b = rng.standard_normal((8, 8, 8)).astype(np.float32)
    r = b.reshape(-1).astype(np.float64) - a8 @ x.reshape(-1)
    want = np.linalg.norm(r) / np.linalg.norm(b.astype(np.float64))
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        got = reference.true_rel_residual(jnp.asarray(x), jnp.asarray(b),
                                          slab=slab)
    finally:
        jax.config.update("jax_enable_x64", was)
    assert got == pytest.approx(want, rel=1e-12)


def test_reference_cg_solves_the_dense_system(a8):
    b = jnp.asarray(np.random.default_rng(2).uniform(-1, 1, (8, 8, 8)),
                    jnp.float32)
    x, k = reference.cg(b, 1e-5, 600)
    want = np.linalg.solve(a8, np.asarray(b, np.float64).reshape(-1))
    assert 0 < int(k) < 600
    np.testing.assert_allclose(np.asarray(x).reshape(-1), want,
                               rtol=0, atol=1e-4 * np.abs(want).max())


def test_rhs_are_the_operator_applied_to_a_seeded_solution():
    traffic = {"rhs_pool": 3, "check_sample": 1,
               "x_star": {"kind": "uniform", "low": 0.5, "high": 1.5}}
    seed = 2 ** 31 + 99
    pool = generator.make_rhs(traffic, seed, (8, 8, 8), jnp.float32)
    again = generator.make_rhs(traffic, seed, (8, 8, 8), jnp.float32)
    other = generator.make_rhs(traffic, seed + 2 ** 32, (8, 8, 8),
                               jnp.float32)
    assert len(pool) == 3
    for b, b2, b3 in zip(pool, again, other):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(b2))
        assert not np.array_equal(np.asarray(b), np.asarray(b3))
    assert not np.array_equal(np.asarray(pool[0]), np.asarray(pool[1]))
