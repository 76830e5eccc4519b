"""Step/solver parity: one ``solve_step_shardmap`` iteration must compute the
SAME numbers as one ``lax.while_loop`` body of the corresponding solver, for
every method in ``repro.api.REGISTRY``.

Since PR 5 both programs are literally the same ``MethodDef.step`` executed
by different drivers, so parity is structural — but this test still pins it
end-to-end (the step fn runs inside shard_map over a ``DistributedOp`` with
concat halos while the solver runs the plain ``LocalOp``, so a drifted
operator protocol or state-layout derivation would surface here exactly as
the old hand-written ladder's drift did).  The residual slot, the exit
correction and the state signature are all derived from the ``MethodDef``
— no per-method tables.

Also covers the PR-5 additions: the fused Pallas body of ``cg_merged``
running INSIDE shard_map (one step == one local fused iteration), and the
unregistered-method regression (``solve_step_shardmap`` used to fall
through silently until trace time).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import REGISTRY
from repro.core.distributed import (init_step_state, solve_step_shardmap,
                                    step_state_layout)
from repro.core.methods import Ops, get_method
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS, LocalOp

pytestmark = pytest.mark.usefixtures("f64")

SHAPE = (8, 8, 10)


@pytest.mark.parametrize("method", sorted(REGISTRY))
def test_one_step_matches_one_solver_iteration(mesh1, method):
    prob = make_problem(SHAPE, "27pt")
    A = LocalOp(prob.stencil)
    b, x0 = prob.b(), prob.x0()
    mdef = get_method(method)

    fn, layout = solve_step_shardmap(prob, method, mesh1)
    out = jax.jit(fn)(*init_step_state(method, A, b, x0))
    res_step = jnp.sqrt(out[mdef.res_index])
    # the method's own exit correction (cg_nb's lagged x update,
    # pbicgstab_merged's x = x0 + M^-1 y recovery) — from the definition,
    # not a per-method special case in the test
    ops = Ops(A, b, norm_ref=1.0)
    x_step = mdef.finalize(ops, x0, out) if mdef.finalize else out[0]

    ref = SOLVERS[method](A, b, x0, tol=1e-30, maxiter=1, norm_ref=1.0)
    assert int(ref.iters) == 1

    # ULP-tight: the two programs fuse differently (pad vs concat halos,
    # paired vs separate dots), so allow last-digit rounding only — the
    # gauss_seidel regression this pins was off by ~1e0, not 1e-13
    np.testing.assert_allclose(np.asarray(x_step), np.asarray(ref.x),
                               rtol=1e-13, atol=1e-13, err_msg=method)
    np.testing.assert_allclose(float(res_step), float(ref.res_norm),
                               rtol=1e-12, err_msg=method)


def test_step_state_derived_from_method_def():
    """The step-state signature is the MethodDef's declared layout — the
    hand-written STEP_STATE table is gone; pin the documented layouts of
    the reduction-hiding variants so a definition edit that silently
    reshapes the analysis surface fails loudly."""
    assert step_state_layout("cg") == (("x", "r", "p"), ("rr",))
    assert step_state_layout("cg_merged") == (
        ("x", "r", "p", "s", "w"),
        ("gamma", "delta", "gamma_prev", "alpha_prev"))
    assert step_state_layout("pcg_pipe") == (
        ("x", "r", "u", "w", "p", "s", "q", "z"),
        ("gamma_prev", "alpha_prev", "rr"))
    assert step_state_layout("bicgstab_merged") == (
        ("x", "r", "w", "t", "p", "s", "z", "rhat"),
        ("rho", "alpha", "rr"))
    for method, spec in REGISTRY.items():
        vecs, scals = step_state_layout(method)
        assert (vecs, scals) == (spec.vectors, spec.scalars)
        assert vecs[0] == "x"
        assert spec.res_scalar in scals


def test_unregistered_method_raises_with_known_list(mesh1):
    """Regression: an unknown method name must raise immediately (it used
    to fall through to a trace-time error deep in the ladder) and the
    message must list the registered methods."""
    prob = make_problem(SHAPE, "27pt")
    with pytest.raises(ValueError, match="unknown method 'sor'"):
        solve_step_shardmap(prob, "sor", mesh1)
    with pytest.raises(ValueError, match="cg_merged"):
        solve_step_shardmap(prob, "sor", mesh1)


def test_fused_step_matches_local_fused_iteration(mesh1):
    """cg_merged + pallas now runs INSIDE shard_map: one fused step on the
    (trivial) mesh must equal one local fused iteration — same kernels,
    halos from the DistributedOp, partials through the stacked psum."""
    from repro.kernels.pallas_op import PallasOp
    prob = make_problem(SHAPE, "27pt")
    A = LocalOp(prob.stencil)
    b, x0 = prob.b(), prob.x0()
    mdef = get_method("cg_merged")

    fn, _ = solve_step_shardmap(prob, "cg_merged", mesh1, pallas_fused=True)
    ops = Ops(PallasOp(A), b, norm_ref=1.0)
    state0 = tuple(mdef.fused_init(ops, x0))
    out = jax.jit(fn)(b, *state0)
    ref = mdef.fused_step(ops, state0)
    for slot, (got, want) in enumerate(zip(out, ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-13, atol=1e-13,
                                   err_msg=f"slot {slot}")


def test_fused_step_rejects_methods_without_fused_body(mesh1):
    prob = make_problem(SHAPE, "27pt")
    with pytest.raises(ValueError, match="declares no fused kernels"):
        solve_step_shardmap(prob, "cg", mesh1, pallas_fused=True)


def test_gauss_seidel_step_applies_both_sweeps(mesh1):
    """Regression: the backward sweep must consume the forward-sweep result.
    Feeding it ``x0`` again makes one step equal a *backward-only* sweep of
    x0 (plus a wasted forward sweep) — strictly worse residual."""
    from repro.core.solvers import _plane_sweep
    prob = make_problem(SHAPE, "27pt")
    A = LocalOp(prob.stencil)
    b, x0 = prob.b(), prob.x0()
    fn, _ = solve_step_shardmap(prob, "gauss_seidel", mesh1)
    out = jax.jit(fn)(*init_step_state("gauss_seidel", A, b, x0))

    x_fwd = _plane_sweep(A, b, x0, forward=True)
    x_sym = _plane_sweep(A, b, x_fwd, forward=False)
    x_back_only = _plane_sweep(A, b, x0, forward=False)

    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x_sym))
    assert not np.array_equal(np.asarray(out[0]), np.asarray(x_back_only))
