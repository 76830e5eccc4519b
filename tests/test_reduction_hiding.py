"""Reduction-hiding Krylov variants (PR 4): convergence parity, registry
metadata, preconditioner composition, the fused-kernel facade path, buffer
donation, and the scaling model's t_reduce term.

The HLO-level one-all-reduce claim lives in tests/test_hlo_analysis.py; the
kernel-vs-oracle precision checks in tests/test_kernels.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import REGISTRY, SolverOptions, SolverSession, solve
from repro.core.problems import make_problem

pytestmark = pytest.mark.usefixtures("f64")

#: variant -> the classic whose iteration counts it must track (+10%)
VARIANTS = {
    "cg_merged": "cg",
    "cg_pipe": "cg",
    "pcg_merged": "pcg",
    "pcg_pipe": "pcg",
    "bicgstab_merged": "bicgstab",
    "pbicgstab_merged": "pbicgstab",
}

GRIDS = [(32, 32, 32), (64, 64, 64)]
TOL = 1e-6

_classic_cache: dict = {}


def _solve(method, grid, stencil, **kw):
    return solve(method=method, grid=grid, stencil=stencil,
                 options=SolverOptions(tol=TOL, maxiter=1500, **kw))


def _classic(method, grid, stencil):
    key = (method, grid, stencil)
    if key not in _classic_cache:
        _classic_cache[key] = _solve(method, grid, stencil)
    return _classic_cache[key]


# -----------------------------------------------------------------------------
# Convergence parity: same tolerance, ≤ +10% iterations, on 7pt/27pt × 32³/64³
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}^3")
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_variant_matches_classic_iterations(grid, stencil, variant):
    ref = _classic(VARIANTS[variant], grid, stencil)
    res = _solve(variant, grid, stencil)
    assert float(res.res_norm) < TOL, (variant, float(res.res_norm))
    # +10% (+1 for the pipelined variants' one-iteration-stale check)
    budget = int(np.ceil(1.1 * int(ref.iters))) + 1
    assert int(res.iters) <= budget, (variant, int(res.iters), int(ref.iters))
    # same solution, not just same count
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               atol=1e-5, err_msg=variant)


def test_true_residual_matches_estimate_at_convergence():
    """The recurrence-based ‖r‖ estimates must not drift from the truth by
    convergence time (the docs' numerical-stability caveat, quantified)."""
    prob = make_problem((32, 32, 32), "27pt")
    from repro.core.solvers import SOLVERS, LocalOp
    A = LocalOp(prob.stencil)
    for m in sorted(VARIANTS):
        kw = {"M": None} if REGISTRY[m].accepts_precond else {}
        res = SOLVERS[m](A, prob.b(), prob.x0(), tol=TOL, maxiter=1500,
                         norm_ref=1.0, **kw)
        true_r = float(jnp.linalg.norm(
            (prob.b() - A.matvec(res.x)).reshape(-1)))
        # the estimate declared convergence; the TRUE residual must agree
        # to within an order of magnitude of the tolerance
        assert true_r < 10 * TOL, (m, true_r, float(res.res_norm))


# -----------------------------------------------------------------------------
# Preconditioner composition: all four PR-3 preconditioners
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("precond", ["jacobi", "block_jacobi", "ssor",
                                     "chebyshev"])
@pytest.mark.parametrize("method", ["pcg_merged", "pcg_pipe",
                                    "pbicgstab_merged"])
def test_composes_with_preconditioners(method, precond):
    grid, stencil = (24, 24, 24), "27pt"
    classic = VARIANTS[method]
    ref = _solve(classic, grid, stencil, precond=precond)
    res = _solve(method, grid, stencil, precond=precond)
    assert float(res.res_norm) < TOL, (method, precond)
    budget = int(np.ceil(1.1 * int(ref.iters))) + 1
    assert int(res.iters) <= budget, (method, precond,
                                      int(res.iters), int(ref.iters))
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               atol=1e-5, err_msg=f"{method}+{precond}")


def test_preconditioned_merged_beats_plain_iterations():
    """The whole point of composing: fewer iterations AND one reduction."""
    grid, stencil = (48, 48, 48), "7pt"
    plain = _solve("cg_merged", grid, stencil)
    pre = _solve("pcg_merged", grid, stencil, precond="chebyshev")
    assert int(pre.iters) < int(plain.iters)


# -----------------------------------------------------------------------------
# Batched serving path (vmap inside the facade)
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["cg_merged", "bicgstab_merged"])
def test_batched_matches_single_solves(method):
    prob = make_problem((10, 10, 12), "27pt")
    sess = SolverSession(prob, method=method,
                         options=SolverOptions(tol=1e-8, maxiter=400,
                                               norm_ref=None))
    rng = np.random.default_rng(3)
    bs = jnp.asarray(rng.standard_normal((4, 10, 10, 12)))
    bres = sess.solve_batched(bs)
    for i in (0, 3):
        single = sess.solve(b=bs[i])
        assert int(bres.iters[i]) == int(single.iters), (method, i)
        np.testing.assert_allclose(np.asarray(bres.x[i]),
                                   np.asarray(single.x), atol=1e-11)


# -----------------------------------------------------------------------------
# The fused Pallas iteration path (pallas=True: the whole reduction-hiding
# family since PR 10, local AND shard_map)
# -----------------------------------------------------------------------------

#: every method with a MethodDef fused body (PR 10 grew this from cg_merged
#: to the full family) — each gets the same facade-parity gate cg_merged had
FUSED_METHODS = sorted(VARIANTS)


@pytest.mark.parametrize("method", FUSED_METHODS)
def test_fused_facade_path_matches_unfused(method):
    """pallas=True routes to the fused Pallas body; same iteration count
    and machine-precision agreement with the unfused facade solve."""
    kw = dict(method=method, grid=(16, 16, 16), stencil="27pt")
    plain = solve(**kw, options=SolverOptions(tol=1e-8, maxiter=300))
    fused = solve(**kw, options=SolverOptions(tol=1e-8, maxiter=300,
                                              pallas=True))
    assert int(fused.iters) == int(plain.iters), method
    np.testing.assert_allclose(np.asarray(fused.x), np.asarray(plain.x),
                               rtol=1e-12, atol=1e-12, err_msg=method)


@pytest.mark.parametrize("method", ["cg_merged", "cg_pipe",
                                    "bicgstab_merged"])
def test_fused_runs_under_shard_map(method, mesh1):
    """PR 5: the fused Pallas body is no longer a local-only special case —
    on a mesh backend the facade routes ``pallas=True`` through
    ``solve_shardmap(pallas_fused=True)`` (PallasOp inside the shard_map
    body).  On the trivial 1-device mesh the result must match the local
    fused solve."""
    prob = make_problem((16, 16, 16), "27pt")
    opts = SolverOptions(tol=1e-8, maxiter=300, pallas=True)
    local = solve(prob, method=method, options=opts)
    dist = solve(prob, method=method, options=opts, mesh=mesh1)
    assert int(dist.iters) == int(local.iters), method
    np.testing.assert_allclose(np.asarray(dist.x), np.asarray(local.x),
                               rtol=1e-12, atol=1e-12, err_msg=method)


@pytest.mark.parametrize("precond", ["chebyshev", "block_jacobi"])
def test_fused_pcg_merged_composes_preconditioner(precond):
    """The tentpole composition: ``pcg_merged`` + a fused-kernel
    preconditioner runs END-TO-END on the fused path (the preconditioner's
    own Pallas kernels inside the fused Krylov body) with bitwise-equal
    iteration counts and machine-precision agreement vs the unfused
    facade."""
    kw = dict(method="pcg_merged", grid=(16, 16, 16), stencil="27pt")
    plain = solve(**kw, options=SolverOptions(tol=1e-8, maxiter=300,
                                              precond=precond))
    fused = solve(**kw, options=SolverOptions(tol=1e-8, maxiter=300,
                                              precond=precond, pallas=True))
    assert int(fused.iters) == int(plain.iters), precond
    np.testing.assert_allclose(np.asarray(fused.x), np.asarray(plain.x),
                               rtol=1e-12, atol=1e-12, err_msg=precond)


def test_fused_pcg_merged_chebyshev_under_shard_map(mesh1):
    """The composed fused path (pcg_merged + chebyshev) under shard_map:
    PallasOp wraps the DistributedOp, the preconditioner binds against it,
    and the result matches the local composed fused solve."""
    prob = make_problem((16, 16, 16), "27pt")
    opts = SolverOptions(tol=1e-8, maxiter=300, precond="chebyshev",
                         pallas=True)
    local = solve(prob, method="pcg_merged", options=opts)
    dist = solve(prob, method="pcg_merged", options=opts, mesh=mesh1)
    assert int(dist.iters) == int(local.iters)
    np.testing.assert_allclose(np.asarray(dist.x), np.asarray(local.x),
                               rtol=1e-12, atol=1e-12)


def test_fused_routing_is_capability_based():
    """The facade's Pallas routing queries the registry capability (any
    method whose MethodDef declares a fused body), not a hard-coded name."""
    from repro.api.registry import fused_solver_names
    assert fused_solver_names() == FUSED_METHODS
    prob = make_problem((8, 8, 8), "27pt")
    fused = SolverSession(prob, method="cg_merged",
                          options=SolverOptions(pallas=True))
    assert fused._use_fused_body()
    assert fused.mdef.has_fused_body
    # pallas=True on a non-fused method still swaps the SpMV kernel only
    plain = SolverSession(prob, method="cg",
                          options=SolverOptions(pallas=True))
    assert not plain._use_fused_body()
    assert not plain.mdef.has_fused_body


def test_fused_solve_matches_solver_loop():
    from repro.core.solvers import LocalOp, cg_merged
    from repro.kernels.fused_cg import cg_merged_fused
    prob = make_problem((12, 12, 16), "27pt")
    A = LocalOp(prob.stencil)
    ref = cg_merged(A, prob.b(), prob.x0(), tol=1e-8, maxiter=300,
                    norm_ref=1.0)
    res = jax.jit(lambda b, x0: cg_merged_fused(
        prob.stencil, b, x0, tol=1e-8, maxiter=300, norm_ref=1.0))(
            prob.b(), prob.x0())
    assert int(res.iters) == int(ref.iters)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=1e-12, atol=1e-12)


# -----------------------------------------------------------------------------
# Buffer donation on the solver hot loops
# -----------------------------------------------------------------------------

def test_solve_donates_x0_buffer():
    """options.donate=True must register the x0 -> output aliasing in the
    lowered HLO (input_output_alias) — and it is live on CPU too (see
    test_donated_x0_is_invalidated)."""
    prob = make_problem((8, 8, 8), "27pt")
    sess = SolverSession(prob, method="cg_merged",
                         options=SolverOptions(tol=1e-6, maxiter=50))
    txt = sess._build_fn().lower(prob.b(), prob.x0()).as_text()
    assert "tf.aliasing_output" in txt
    off = sess._build_fn(donate=False).lower(prob.b(), prob.x0()).as_text()
    assert "tf.aliasing_output" not in off
    # b is NOT donated (stationary methods re-read it; callers keep it):
    # exactly one of the two array args carries the aliasing attribute
    assert txt.count("tf.aliasing_output") == 1


def test_batched_solve_donates_and_still_matches():
    prob = make_problem((8, 8, 8), "27pt")
    sess = SolverSession(prob, method="cg",
                         options=SolverOptions(tol=1e-8, maxiter=200))
    bs = jnp.stack([prob.b()] * 2)
    txt = sess._build_batched_fn().lower(bs, jnp.zeros_like(bs)).as_text()
    assert "tf.aliasing_output" in txt
    res = sess.solve_batched(bs)           # donated path end-to-end
    ref = sess.solve()
    np.testing.assert_array_equal(np.asarray(res.x[0]), np.asarray(ref.x))


def test_donated_x0_is_invalidated():
    """The documented donation semantics: reusing a caller-supplied x0
    after a donating solve raises; donate=False keeps it alive."""
    prob = make_problem((8, 8, 8), "27pt")
    sess = SolverSession(prob, method="cg",
                         options=SolverOptions(tol=1e-6, maxiter=20))
    x0 = prob.x0()
    sess.solve(x0=x0)
    with pytest.raises(Exception, match="deleted or donated"):
        sess.solve(x0=x0)
    keep = SolverSession(prob, method="cg",
                         options=SolverOptions(tol=1e-6, maxiter=20,
                                               donate=False))
    x0 = prob.x0()
    keep.solve(x0=x0)
    keep.solve(x0=x0)                       # still alive


def test_repeated_session_solves_with_donation():
    """problem.b()/x0() hand out fresh buffers, so back-to-back solves on a
    donating session must keep working (the serving loop)."""
    sess = SolverSession(method="bicgstab_merged", grid=(8, 8, 8),
                         options=SolverOptions(tol=1e-8, maxiter=200))
    r1, r2 = sess.solve(), sess.solve()
    assert int(r1.iters) == int(r2.iters)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))


# -----------------------------------------------------------------------------
# Registry metadata + the scaling model's t_reduce term
# -----------------------------------------------------------------------------

def test_registry_reduction_hiding_metadata():
    for variant, base in VARIANTS.items():
        spec = REGISTRY[variant]
        assert spec.variant_of == base, variant
        assert spec.reductions_per_iter == 1, variant
        assert spec.reduce_hide in ("merged", "pipelined"), variant
        assert spec.spmvs_per_iter == REGISTRY[base].spmvs_per_iter, variant
    assert REGISTRY["cg_merged"].blocking_reductions == 1
    assert REGISTRY["cg_pipe"].blocking_reductions == 0
    assert REGISTRY["cg_pipe"].reduce_hide == "pipelined"
    assert REGISTRY["bicgstab_merged"].reduce_hide == "merged"
    for classic in ("cg", "cg_nb", "bicgstab", "bicgstab_b1"):
        assert REGISTRY[classic].reduce_hide == "none"


def test_scaling_model_t_reduce_term():
    """Merged pays Λ once (vs 2–3×); pipelined hides that one payment behind
    the SpMV — the fig3/fig56 pipelined-overlap curves' driving term."""
    from benchmarks.scaling_model import iteration_time, reduction_latency
    kw = dict(nbar=27, local_grid=(128, 128, 128), chips=4096,
              noise="noisy", halo_mode="overlap")
    t_cg = iteration_time("cg", **kw)
    t_merged = iteration_time("cg_merged", **kw)
    t_pipe = iteration_time("cg_pipe", **kw)
    assert t_pipe < t_merged < t_cg
    assert iteration_time("bicgstab_merged", **kw) < iteration_time(
        "bicgstab", **kw)
    # the pipelined win IS the hidden reduction: under the MPI regime
    # (no overlap) the pipe variant loses its edge over merged
    kw_mpi = dict(kw, execution="mpi")
    assert iteration_time("cg_pipe", **kw_mpi) >= iteration_time(
        "cg_merged", **kw_mpi)
    assert reduction_latency(1) == 0.0
    assert reduction_latency(4096, noise="noisy") > reduction_latency(
        4096, noise="tpu")


def test_step_state_layouts_consistent():
    from repro.core.distributed import init_step_state, step_state_layout
    from repro.core.solvers import LocalOp
    prob = make_problem((6, 6, 8), "7pt")
    A = LocalOp(prob.stencil)
    for m in REGISTRY:
        vecs, scals = step_state_layout(m)
        state = init_step_state(m, A, prob.b(), prob.x0())
        assert len(state) == 1 + len(vecs) + len(scals), m
        for v in state[1:1 + len(vecs)]:
            assert v.shape == prob.shape, m
        for sc in state[1 + len(vecs):]:
            assert jnp.shape(sc) == (), m
