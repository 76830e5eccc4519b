"""The one traffic generator: a mix file's parameters and a seed in, the
right-hand sides and the order they are solved in out.

A mix (``bench/traffic/<name>.json``) says how many right-hand sides the
caller cycles through (``rhs_pool``), how the exact solutions are drawn
(``x_star``: uniform on ``[low, high)``), and how many of the window's
solves the check compares (``check_sample``).  Every right-hand side is
``b = A x*`` with the plain reference operator, made on the device(s) in
one jitted call, already placed as the solve takes it.
"""

from __future__ import annotations

import functools

import jax

from bench.reference import apply27


def prng_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, wider ones than 32 bits included."""
    seed %= 2 ** 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def make_rhs(traffic: dict, seed: int, shape: tuple[int, int, int],
             dtype, sharding=None) -> tuple[jax.Array, ...]:
    """``traffic["rhs_pool"]`` right-hand sides ``A x*`` for the seed."""
    n = int(traffic["rhs_pool"])
    xs = traffic["x_star"]
    if xs["kind"] != "uniform":
        raise ValueError(f"unknown x_star kind {xs['kind']!r}")
    low, high = float(xs["low"]), float(xs["high"])

    @functools.partial(jax.jit, out_shardings=(
        None if sharding is None else (sharding,) * n))
    def gen(key):
        out = []
        for k in jax.random.split(key, n):
            x = jax.random.uniform(k, shape, dtype, low, high)
            out.append(apply27(x))
        return tuple(out)

    return jax.block_until_ready(gen(prng_key(seed)))
