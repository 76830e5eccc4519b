"""The repro.api facade: backend resolution, registry metadata, and —
critically — that the facade is a *zero-cost* abstraction: ``repro.api.solve``
must produce bit-for-bit the same ``SolveResult`` as calling the solver
functions directly, on both the local and the shard_map path, and
``solve_batched`` must match per-RHS single solves."""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_multidevice

from repro.api import (
    REGISTRY,
    SolverOptions,
    SolverSession,
    get_solver,
    resolve_backend,
    solve,
    solve_batched,
    solver_names,
    variant_pairs,
)
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS, VARIANT_OF, LocalOp

pytestmark = pytest.mark.usefixtures("f64")

SHAPE = (10, 10, 12)


@pytest.fixture(scope="module")
def problem():
    return make_problem(SHAPE, "27pt")


# -----------------------------------------------------------------------------
# local path: facade == direct solver call, bit for bit
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("method", sorted(SOLVERS))
def test_local_matches_direct_bitwise(problem, method):
    res = solve(problem, method=method, tol=1e-8, maxiter=2000)
    ref = SOLVERS[method](LocalOp(problem.stencil), problem.b(), problem.x0(),
                          tol=1e-8, maxiter=2000, norm_ref=1.0)
    assert int(res.iters) == int(ref.iters)
    assert float(res.res_norm) == float(ref.res_norm)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    np.testing.assert_array_equal(np.asarray(res.history),
                                  np.asarray(ref.history))


def test_session_reuses_compiled_fn(problem):
    sess = SolverSession(problem, method="cg",
                         options=SolverOptions(tol=1e-8, maxiter=500))
    r1 = sess.solve()
    fn = sess._executables[tuple(problem.shape)]
    r2 = sess.solve()
    assert sess._executables[tuple(problem.shape)] is fn
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    # the compile-cache observability: one real compile, then hits
    st = sess.cache_stats()[(tuple(problem.shape), "cg", "none")]
    assert st["misses"] == 1 and st["hits"] == 1 and st["compile_s"] > 0


def test_timed_solve_returns_blocked_stats(problem):
    sess = SolverSession(problem, method="jacobi",
                         options=SolverOptions(tol=1e-6, maxiter=50))
    res, stats = sess.timed_solve(repeats=2)
    assert int(res.iters) == 50
    assert stats["median"] > 0.0
    assert stats["q1"] <= stats["median"] <= stats["q3"]


# -----------------------------------------------------------------------------
# batched multi-RHS path (the serving workload)
# -----------------------------------------------------------------------------

def test_solve_batched_matches_single_solves(problem):
    sess = SolverSession(problem, method="cg", options=SolverOptions(
        tol=1e-8, maxiter=400, norm_ref=None))
    rng = np.random.default_rng(0)
    bs = jnp.asarray(rng.standard_normal((8, *SHAPE)))
    bres = sess.solve_batched(bs)            # 8 RHS, ONE compiled call
    assert bres.x.shape == (8, *SHAPE)
    for i in range(8):
        single = sess.solve(b=bs[i])
        assert int(bres.iters[i]) == int(single.iters), i
        np.testing.assert_allclose(np.asarray(bres.x[i]),
                                   np.asarray(single.x), atol=1e-12)


def test_solve_batched_facade_and_validation(problem):
    bs = jnp.stack([problem.b()] * 2)
    res = solve_batched(bs, problem, method="jacobi", maxiter=30)
    assert res.x.shape == (2, *SHAPE)
    sess = SolverSession(problem, method="jacobi")
    with pytest.raises(ValueError, match="batch"):
        sess.solve_batched(problem.b())                 # missing batch axis
    with pytest.raises(ValueError, match="grid"):
        sess.solve_batched(jnp.zeros((2, 4, 4, 4)))     # wrong grid


def test_batched_bicgstab_b1_vmaps(problem):
    """The optimization_barrier in Alg. 2 must be batchable (JAX batches it)."""
    bs = jnp.stack([problem.b()] * 2)
    res = solve_batched(bs, problem, method="bicgstab_b1", tol=1e-6,
                        maxiter=200)
    ref = solve(problem, method="bicgstab_b1", tol=1e-6, maxiter=200)
    assert int(res.iters[0]) == int(ref.iters)
    np.testing.assert_allclose(np.asarray(res.x[0]), np.asarray(ref.x),
                               atol=1e-12)


# -----------------------------------------------------------------------------
# options / backend / registry
# -----------------------------------------------------------------------------

def test_options_validation():
    with pytest.raises(ValueError, match="layout"):
        SolverOptions(layout="4d")
    with pytest.raises(ValueError, match="maxiter"):
        SolverOptions(maxiter=-1)
    opts = SolverOptions(tol=1e-4).replace(maxiter=7)
    assert opts.maxiter == 7 and opts.tol == 1e-4


def test_backend_resolution_rules():
    assert resolve_backend(SolverOptions(), n_devices=1).kind == "local"
    assert resolve_backend(SolverOptions(layout="local"),
                           n_devices=8).kind == "local"
    with pytest.raises(ValueError):
        resolve_backend(SolverOptions(layout="3d"), n_devices=4)
    # multi-device mesh construction is exercised in the shard_map
    # subprocess below (a 1-device host cannot build an 8-device mesh)


def test_unknown_method_raises(problem):
    with pytest.raises(KeyError, match="unknown method"):
        solve(problem, method="sor")


def test_f64_mismatch_with_prebuilt_problem_raises(problem):
    """An f64 problem + f64=False (or the converse) is a configuration
    error, not something to silently ignore."""
    with pytest.raises(ValueError, match="conflicts"):
        SolverSession(problem, method="cg",
                      options=SolverOptions(f64=False))
    f32_prob = make_problem(SHAPE, "27pt", dtype=jnp.float32)
    with pytest.raises(ValueError, match="conflicts"):
        SolverSession(f32_prob, method="cg")          # default f64=True


def test_facade_never_flips_global_x64():
    """Building an f64 problem without x64 enabled raises instead of
    flipping the process-global flag from inside the constructor."""
    import jax
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(ValueError, match="enable_f64"):
            SolverSession(method="cg", grid=(4, 4, 4))
        assert jax.config.jax_enable_x64 is False     # untouched
        sess = SolverSession(method="cg", grid=(4, 4, 4),
                             options=SolverOptions(f64=False))
        assert jnp.dtype(sess.problem.dtype) == jnp.dtype(jnp.float32)
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_halo_mode_validation():
    with pytest.raises(ValueError, match="halo_mode"):
        SolverOptions(halo_mode="eager")
    from repro.api.backend import resolve_halo_mode
    assert resolve_halo_mode(SolverOptions()) == "overlap"
    assert resolve_halo_mode(SolverOptions(pallas=True)) == "concat"
    assert resolve_halo_mode(
        SolverOptions(matvec_padded=lambda xp: xp)) == "concat"
    assert resolve_halo_mode(SolverOptions(halo_mode="scatter")) == "scatter"


def test_hpcg_config_wires_into_facade():
    from repro.configs.hpcg import SOLVER_CONFIGS
    cfg = SOLVER_CONFIGS["hpcg-cg-7pt"]
    opts = cfg.to_options(maxiter=30)
    assert opts.tol == cfg.tol and opts.maxiter == 30
    res = cfg.session(grid=(8, 8, 8), maxiter=30).solve()
    assert 0 < int(res.iters) <= 30


def test_registry_subsumes_core_dicts():
    assert set(REGISTRY) == set(SOLVERS)
    assert solver_names() == sorted(SOLVERS)
    for variant, base in VARIANT_OF.items():
        assert get_solver(variant).variant_of == base
    assert (base_variant := dict(variant_pairs())) and all(
        base in REGISTRY for base in base_variant)


def test_registry_consistency_check_raises_real_exceptions():
    """The import-time registry/core cross-check must raise (not assert:
    asserts vanish under ``python -O``, silently disabling the guard)."""
    import dataclasses
    from repro.api import RegistryConsistencyError, check_consistent_with_core

    check_consistent_with_core()                     # current state is good
    missing = dict(REGISTRY)
    missing.pop("cg")
    with pytest.raises(RegistryConsistencyError, match="core-only"):
        check_consistent_with_core(registry=missing)
    wrong_fn = dict(REGISTRY)
    wrong_fn["cg"] = dataclasses.replace(REGISTRY["cg"],
                                         fn=lambda *a, **k: None)
    with pytest.raises(RegistryConsistencyError, match="registered fn"):
        check_consistent_with_core(registry=wrong_fn)
    with pytest.raises(RegistryConsistencyError, match="variant_of"):
        check_consistent_with_core(variant_of={"cg_nb": "bicgstab"})
    # and the guard really is exception-based, not assert-based: it must
    # keep firing when Python strips asserts (compile with optimize=2)
    import inspect
    src = inspect.getsource(check_consistent_with_core)
    assert "assert " not in src


def test_registry_barrier_metadata_matches_paper():
    """Hard-barrier counts per §3.1: CG 1, CG-NB 0, BiCGStab 2, B1 1."""
    assert REGISTRY["cg"].blocking_reductions == 1
    assert REGISTRY["cg_nb"].blocking_reductions == 0
    assert REGISTRY["bicgstab"].blocking_reductions == 2
    assert REGISTRY["bicgstab_b1"].blocking_reductions == 1
    assert REGISTRY["cg"].reductions_per_iter == 2
    assert REGISTRY["bicgstab"].reductions_per_iter == 3
    for m in ("cg", "cg_nb"):
        assert REGISTRY[m].spd_required
    for m in ("jacobi", "gauss_seidel", "gauss_seidel_rb"):
        assert REGISTRY[m].stationary


# -----------------------------------------------------------------------------
# shard_map path (subprocess: the main pytest process must keep 1 device)
# -----------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.api import SolverOptions, SolverSession, solve
from repro.core.distributed import solve_shardmap
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS
from repro.launch.mesh import make_solver_mesh

from repro.api import resolve_backend

prob = make_problem((12, 12, 16), "27pt")
mesh = make_solver_mesh(8)
opts = SolverOptions(tol=1e-6, maxiter=600)
out = {}

b_auto = resolve_backend(SolverOptions(layout="auto"))
b_2d = resolve_backend(SolverOptions(layout="2d"))
b_3d = resolve_backend(SolverOptions(layout="3d"))
out["backends"] = dict(
    auto_kind=b_auto.kind,
    auto_axes=list(b_auto.mesh.axis_names),
    auto_dim_axes=[a for a in b_auto.layout.dim_axes],
    d2_axes=sorted(b_2d.mesh.axis_names),
    d3_axes=list(b_3d.mesh.axis_names),
)
for m in sorted(SOLVERS):
    res = solve(prob, method=m, mesh=mesh, options=opts)
    fn, layout = solve_shardmap(prob, m, mesh, tol=1e-6, maxiter=600)
    sh = NamedSharding(mesh, layout.spec())
    ref = jax.jit(fn)(jax.device_put(prob.b(), sh),
                      jax.device_put(prob.x0(), sh))
    out[m] = dict(
        iters=int(res.iters), ref_iters=int(ref.iters),
        bitwise=bool(np.array_equal(np.asarray(res.x), np.asarray(ref.x))),
    )
sess = SolverSession(prob, method="cg_nb", mesh=mesh, options=opts)
rng = np.random.default_rng(1)
bs = jnp.asarray(rng.standard_normal((8, 12, 12, 16)))
bres = sess.solve_batched(bs)
dx = max(float(jnp.abs(bres.x[i] - sess.solve(b=bs[i]).x).max())
         for i in (0, 7))
out["batched"] = dict(shape=list(bres.x.shape), max_dx=dx)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def shard_results():
    return run_multidevice(_SHARD_SCRIPT)


def test_shard_backend_resolution(shard_results):
    b = shard_results["backends"]
    assert b["auto_kind"] == "shard_map"
    assert b["auto_axes"] == ["cells"]             # paper-faithful 1-D z
    assert b["auto_dim_axes"] == [None, None, "cells"]
    assert b["d2_axes"] == ["data", "model"]
    assert b["d3_axes"] == ["pod", "data", "model"]


def test_shard_path_matches_direct_shardmap(shard_results):
    for m in sorted(SOLVERS):
        r = shard_results[m]
        assert r["iters"] == r["ref_iters"], (m, r)
        assert r["bitwise"], m


def test_shard_path_batched(shard_results):
    r = shard_results["batched"]
    assert r["shape"] == [8, 12, 12, 16]
    assert r["max_dx"] < 1e-10
