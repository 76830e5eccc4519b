"""The contract auditor turned on itself: each pass must flag a deliberately
injected violation — and ONLY the pass that owns the invariant — while the
real registry/kernel table runs clean.

Three injections, one per pass:

* a ``MethodDef`` whose ``allreduces_per_iter`` understates what the HLO
  contains → the comms comparator flags the ``all-reduce`` count;
* a ``MethodDef`` whose step branches (Python ``if``) on a traced scalar →
  the AST lint flags ``traced_branch``;
* a ``KernelSpec`` whose block footprint exceeds the VMEM budget → the
  kernel lint flags ``vmem_bytes``.

The comparator tests run on synthetic measurement records (compare() is
pure), so they are fast; one slow test drives the real subprocess worker
over a one-method subset end to end.
"""

import dataclasses

import pytest

from repro.analysis.audit import compare, compare_baseline, expected_comms
from repro.analysis.lint_kernels import (
    KERNEL_TABLE,
    VMEM_BUDGET_BYTES,
    KernelSpec,
    check_kernels,
)
from repro.analysis.lint_methods import check_method, check_methods
from repro.api.registry import REGISTRY
from repro.core.methods import MethodDef


def _fake_measured(registry, mesh="1d", halo="concat"):
    """Measurement records exactly matching the registry's expectations."""
    comms = {}
    for name, spec in registry.items():
        want = expected_comms(spec, mesh)
        comms[f"{name}|{mesh}|{halo}|xla|none"] = {
            "counts": {op: n for op, n in want.items() if n},
            "bytes": 1000,
        }
    return {"comms": comms}


# -----------------------------------------------------------------------------
# injection 1: wrong registry comms metadata -> the comms pass flags it
# -----------------------------------------------------------------------------

def test_clean_registry_compares_clean():
    measured = _fake_measured(REGISTRY)
    assert compare(measured) == []


def test_wrong_allreduce_count_flagged():
    # cg really compiles to 2 all-reduces; doctor the spec to claim 1
    bad_cg = dataclasses.replace(REGISTRY["cg"], allreduces_per_iter=1)
    registry = dict(REGISTRY, cg=bad_cg)
    measured = _fake_measured({"cg": REGISTRY["cg"]})
    found = compare(measured, registry=registry)
    assert len(found) == 1
    v = found[0]
    assert (v.pass_name, v.field) == ("comms", "all-reduce")
    assert v.expected == 1 and v.actual == 2


def test_unexpected_collective_flagged():
    measured = _fake_measured({"cg": REGISTRY["cg"]})
    key = next(iter(measured["comms"]))
    measured["comms"][key]["counts"]["all-gather"] = 1   # lost sharding symptom
    found = compare(measured)
    assert [v.field for v in found] == ["all-gather"]
    assert found[0].expected == 0 and found[0].actual == 1


def test_donation_and_alias_violations_flagged():
    measured = {
        "donate_mesh": {"cg|1d": {"on": 0, "off": 0}},
        "local": {"cg": {"markers_on": 1, "markers_off": 1,
                         "collectives": {"all-reduce": 2},
                         "aliased_params": []}},
        "mesh_aliases": {"cg|1d": []},
    }
    found = compare(measured)
    fields = sorted((v.pass_name, v.field) for v in found)
    assert ("donation", "markers_on") in fields          # mesh: not annotated
    assert ("donation", "markers_off") in fields         # local: leaks donation
    assert ("comms", "collectives") in fields            # local: not collective-free
    assert ("donation", "input_output_alias") in fields  # alias not granted
    assert len(found) == 5                               # + mesh alias record


def test_baseline_drift_flagged():
    key = "cg|1d|concat|xla|none"
    rec = {"counts": {"all-reduce": 2}, "bytes": 1616}
    drifted = {"counts": {"all-reduce": 2}, "bytes": 3232}
    base = {"measured": {"comms": {key: rec}}}
    assert compare_baseline({"comms": {key: rec}}, base) == []
    found = compare_baseline({"comms": {key: drifted}}, base)
    assert [v.field for v in found] == ["drift"]
    missing = compare_baseline({"comms": {}}, base)
    new = compare_baseline(
        {"comms": {key: rec, "cg|3d|auto|xla|none": rec}}, base)
    assert [v.field for v in missing] == ["coverage"]
    assert [v.field for v in new] == ["coverage"]


def test_precond_configs_add_expected_traffic():
    plain = expected_comms(REGISTRY["pcg"], "2d")
    withp = expected_comms(REGISTRY["pcg"], "2d", precond="jacobi")
    assert withp["all-reduce"] == plain["all-reduce"]    # Jacobi: no extra psum
    assert withp["collective-permute"] > plain["collective-permute"]


# -----------------------------------------------------------------------------
# injection 2: MethodDef branching on a traced scalar -> the AST lint flags it
# -----------------------------------------------------------------------------

def _branchy_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    return (x0, ops.dot(r, r))


def _branchy_step(ops, state):
    x, res = state
    if res > 1e-3:          # Python branch on a traced value: the injection
        x = x + 1.0
    return (x, res * 0.5)


_BRANCHY = MethodDef(
    name="_audit_branchy", vectors=("x",), scalars=("res2",),
    res_scalar="res2", init=_branchy_init, step=_branchy_step,
    reduction_hides=("none",), spmvs_per_iter=1)


def test_traced_branch_flagged():
    found = check_method(_BRANCHY, layout=False)
    assert any(v.field == "traced_branch" and "res" in str(v.actual)
               for v in found)
    assert all(v.pass_name == "lint_methods" for v in found)


def test_traced_branch_also_breaks_layout_trace():
    # with the layout pass on, the same injection ALSO fails to trace under
    # eval_shape — both findings point at the same root cause
    fields = {v.field for v in check_method(_BRANCHY)}
    assert "traced_branch" in fields and "state_layout" in fields


def test_real_methods_lint_clean():
    assert check_methods(layout=False) == []


def test_real_methods_layout_clean():
    assert check_methods() == []


# -----------------------------------------------------------------------------
# injection 3: oversized kernel block -> the kernel lint flags it
# -----------------------------------------------------------------------------

def test_oversized_kernel_block_flagged():
    bad = KernelSpec("spmv", "stencil_spmv", "stencil_spmv_ref",
                     vmem_bytes=4 * VMEM_BUDGET_BYTES)
    found = check_kernels(table=(bad,))
    assert [v.field for v in found] == ["vmem_bytes"]
    assert found[0].pass_name == "lint_kernels"


def test_non_dividing_block_flagged():
    bad = KernelSpec("spmv", "stencil_spmv", "stencil_spmv_ref",
                     vmem_bytes=1024, block_z=7)
    found = check_kernels(table=(bad,))
    assert [v.field for v in found] == ["block_divisibility"]


def test_missing_oracle_flagged():
    bad = KernelSpec("spmv", "stencil_spmv", "no_such_ref_fn",
                     vmem_bytes=1024)
    found = check_kernels(table=(bad,))
    assert [v.field for v in found] == ["oracle"]


def test_real_kernel_table_clean():
    assert check_kernels() == []
    # and the real table stays under budget with honest margins
    for spec in KERNEL_TABLE:
        assert spec.vmem_bytes <= VMEM_BUDGET_BYTES, spec.name


# -----------------------------------------------------------------------------
# the real thing, end to end (subprocess, 8 host devices)
# -----------------------------------------------------------------------------

@pytest.mark.slow
def test_audit_subset_end_to_end():
    from repro.analysis.audit import run_measurements
    measured = run_measurements(["cg_merged"])
    assert measured["comms"]                      # incl. the pallas configs
    assert any("|pallas|" in k for k in measured["comms"])
    assert compare(measured) == []
