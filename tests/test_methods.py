"""The MethodDef layer (PR 5): the single-source contract.

Covers the pieces the refactor introduced: the declared-state machinery
(init matches the layout, res_scalar resolves), the generic ``run_method``
driver (a brand-new method authored per docs/API.md §"Authoring a new
method" solves the system without touching any driver), the checks on the
declared communication metadata, and the clear-error paths.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.registry import REGISTRY
from repro.core.methods import (METHODS, MethodDef, Ops, get_method,
                                method_names, register_method, run_method)
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS, LocalOp

pytestmark = pytest.mark.usefixtures("f64")


# -----------------------------------------------------------------------------
# Contract: declared layouts match what init/step actually produce
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(METHODS))
def test_init_and_step_match_declared_layout(name):
    mdef = METHODS[name]
    prob = make_problem((6, 6, 8), "7pt")
    ops = Ops(LocalOp(prob.stencil), prob.b(), norm_ref=1.0)
    state = mdef.init(ops, prob.x0())
    nvec, nscal = len(mdef.vectors), len(mdef.scalars)
    assert len(state) == nvec + nscal, name
    for v in state[:nvec]:
        assert v.shape == prob.shape, name
    for sc in state[nvec:]:
        assert jnp.shape(sc) == (), name
    out = mdef.step(ops, state)
    assert len(out) == nvec + nscal, name
    assert mdef.res_index == nvec + mdef.scalars.index(mdef.res_scalar)
    # one registered solver callable per definition, and vice versa
    assert set(METHODS) == set(SOLVERS) == set(REGISTRY)
    assert SOLVERS[name].method_def is mdef
    assert REGISTRY[name] is mdef


def test_solver_wrappers_reject_unknown_kwargs():
    """The derived solver callables must keep the old explicit-signature
    behaviour: a typo'd keyword raises instead of being silently ignored."""
    prob = make_problem((6, 6, 8), "7pt")
    A = LocalOp(prob.stencil)
    with pytest.raises(TypeError, match="unexpected keyword"):
        SOLVERS["cg"](A, prob.b(), prob.x0(), maxiters=10)
    with pytest.raises(TypeError, match="no preconditioner"):
        SOLVERS["cg"](A, prob.b(), prob.x0(), M=lambda v: v)
    # declared tuning knobs still pass through (bicgstab_b1's restart eps)
    res = SOLVERS["bicgstab_b1"](A, prob.b(), prob.x0(), tol=1e-6,
                                 maxiter=50, norm_ref=1.0, eps_restart=1e-4)
    assert float(res.res_norm) < 1e-6


def test_get_method_unknown_name_lists_registry():
    with pytest.raises(ValueError, match="unknown method 'sor'"):
        get_method("sor")
    with pytest.raises(ValueError, match="bicgstab_merged"):
        get_method("sor")
    assert method_names() == sorted(METHODS)


def test_method_def_validates_declarations():
    dummy = lambda ops, *a: a  # noqa: E731
    comms = dict(reduction_hides=("none",), spmvs_per_iter=1)
    with pytest.raises(ValueError, match="res_scalar"):
        MethodDef(name="bad", vectors=("x",), scalars=("rr",),
                  res_scalar="nope", init=dummy, step=dummy, **comms)
    with pytest.raises(ValueError, match="fused"):
        MethodDef(name="bad", vectors=("x",), scalars=("rr",),
                  res_scalar="rr", init=dummy, step=dummy,
                  fused_kernels=("spmv_dots",), **comms)


# -----------------------------------------------------------------------------
# The declared communication metadata is checked at definition time
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("base,override,match", [
    ("cg", dict(halo_hides=("interior", "none")), "one entry per SpMV"),
    ("cg", dict(precond_applies_per_iter=1),
     "precond_applies_per_iter without accepts_precond"),
    ("cg", dict(reduce_hide="stacked"), "unknown reduce_hide"),
    ("cg_merged", dict(reduction_hides=("none", "none")),
     "ONE stacked reduction per iteration"),
    ("cg_merged", dict(reduce_hide="pipelined"),
     "hides behind the next SpMV"),
    ("cg_merged", dict(allreduces_per_iter=2), "claims ONE stacked"),
    ("cg", dict(allreduces_per_iter=3), "exceeds the declared logical"),
    ("gauss_seidel", dict(halo_exchanges_per_iter=1),
     "below spmvs_per_iter"),
], ids=["halo_hides_len", "precond_applies", "reduce_hide_vocab",
        "merged_many_reductions", "pipelined_not_pipe",
        "stacked_allreduces", "allreduces_exceed", "halo_exchanges_low"])
def test_method_def_rejects_inconsistent_metadata(base, override, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(METHODS[base], name="bad", **override)


FUSED = [n for n in sorted(METHODS) if METHODS[n].fused_kernels]


@pytest.mark.parametrize("name", FUSED)
def test_fused_kernels_require_fused_body_and_pallas_hooks(name):
    """A method may only advertise ``fused_kernels`` that are real
    ``PallasOp`` hooks, with a fused body beside them — otherwise session
    routing would fail at trace time, or fall back silently."""
    from repro.kernels.pallas_op import PallasOp
    mdef = METHODS[name]
    assert len(FUSED) == 6
    assert mdef.fused_step is not None and mdef.fused_init is not None
    for hook in mdef.fused_kernels:
        assert callable(getattr(PallasOp, hook, None)), (name, hook)


# -----------------------------------------------------------------------------
# Authoring path: the docs' toy Richardson iteration, end to end
# -----------------------------------------------------------------------------

def _richardson_def(omega: float = 0.035) -> MethodDef:
    """The worked example from docs/API.md §"Authoring a new method"."""
    def init(ops, x0):
        r = ops.b - ops.matvec(x0)
        return (x0, r, ops.dot(r, r))

    def step(ops, state):
        x, r, rr = state
        x = x + omega * r
        r = ops.b - ops.matvec(x)
        return (x, r, ops.dot(r, r))

    return MethodDef(name="richardson", vectors=("x", "r"), scalars=("rr",),
                     res_scalar="rr", init=init, step=step, stationary=True,
                     default_maxiter=5000,
                     reduction_hides=("none",), spmvs_per_iter=1)


def test_toy_richardson_solves_via_generic_driver():
    """A new method is ONE MethodDef: run_method drives it to convergence
    with no solver-, distributed- or facade-layer code."""
    mdef = _richardson_def()
    prob = make_problem((12, 12, 12), "7pt")
    A = LocalOp(prob.stencil)
    ops = Ops(A, prob.b(), norm_ref=1.0)
    res = run_method(mdef, ops, prob.x0(), tol=1e-8)
    assert float(res.res_norm) < 1e-8
    assert int(res.iters) < 5000
    true_r = float(jnp.linalg.norm(
        (prob.b() - A.matvec(res.x)).reshape(-1)))
    assert true_r < 1e-7


def test_registered_method_drives_step_backend_too():
    """Registering the toy method makes the STEP machinery (the dry-run's
    analysis surface) pick it up with zero extra code."""
    from repro.core.distributed import (init_step_state, solve_step_shardmap,
                                        step_state_layout)
    from repro.core.compat import make_mesh
    mdef = _richardson_def()
    register_method(mdef)
    try:
        prob = make_problem((6, 6, 8), "7pt")
        A = LocalOp(prob.stencil)
        assert step_state_layout("richardson") == (("x", "r"), ("rr",))
        mesh = make_mesh((1, 1), ("data", "model"))
        fn, _ = solve_step_shardmap(prob, "richardson", mesh)
        state = init_step_state("richardson", A, prob.b(), prob.x0())
        out = fn(*state)
        ref = mdef.step(Ops(A, prob.b(), norm_ref=1.0), state[1:])
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-13, atol=1e-13)
    finally:
        METHODS.pop("richardson")
