"""CG's ``p·Ap`` from the apply's own pass: ``Ops.matvec_dot``.

``Ops.matvec_dot(p)`` returns ``(A p, p·A p)``.  With the built-in box
apply (the 27-point stencil, ``Stencil.matvec_dot_padded``) the local
partial is computed in the pass that writes ``A p``: one
``optimization_barrier`` fences the pair, and on a mesh the partials take
the one psum ``dot`` took.  Every other path (the 7-point cross, a user
``matvec_padded``, the Pallas SpMV, a foreign ``SolverOptions.dot``) runs
``matvec`` and then ``dot``, as ``cg`` and ``pcg`` did before the hook.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import REPO_ROOT, run_multidevice

from repro.core.methods import Ops, get_method, run_method
from repro.core.operators import STENCIL_7PT, STENCIL_27PT
from repro.core.solvers import LocalOp
from repro.kernels.pallas_op import PallasOp

GRID = (8, 8, 16)
# |q| and |p·q| against their terms' magnitudes: a reordered sum
RTOL = {"float32": 1e-5, "float64": 1e-13}


def _vector(dtype: str, seed: int = 18) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(-1.0, 1.0, GRID).astype(dtype))


def _foreign_dot(a, b):
    return jnp.sum(a * b)


def _user_matvec_padded(xp):
    return STENCIL_27PT.matvec_padded(xp)


def _apart(ops: Ops, p: jax.Array) -> tuple:
    """What ``cg`` and ``pcg`` computed before the hook."""
    Ap = ops.matvec(p)
    return Ap, ops.dot(p, Ap)


def _operator(path: str):
    """``(A, dot)`` of one fallback path, on the 27-point operator unless
    the path is the 7-point one."""
    if path == "7pt":
        return LocalOp(STENCIL_7PT), None
    if path == "matvec_padded":
        return LocalOp(STENCIL_27PT, matvec_padded=_user_matvec_padded), None
    if path == "foreign_dot":
        return LocalOp(STENCIL_27PT), _foreign_dot
    assert path == "pallas"                  # interpret-mode kernels
    return PallasOp(LocalOp(STENCIL_27PT)), None


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_local_box_matvec_dot_matches_matvec_then_dot(dtype, f64):
    p = _vector(dtype)
    ops = Ops(LocalOp(STENCIL_27PT), p, norm_ref=1.0)
    q, pq = jax.jit(ops.matvec_dot)(p)
    q_ref = STENCIL_27PT.matvec(p)
    assert q.dtype == pq.dtype == p.dtype
    scale = float(jnp.max(jnp.abs(q_ref)))
    np.testing.assert_allclose(np.asarray(q), np.asarray(q_ref),
                               rtol=0, atol=RTOL[dtype] * scale)
    terms = np.abs(np.asarray(p, np.float64) * np.asarray(q_ref, np.float64))
    want = np.dot(np.asarray(p, np.float64).ravel(),
                  np.asarray(q_ref, np.float64).ravel())
    assert abs(float(pq) - want) <= RTOL[dtype] * terms.sum()


@pytest.mark.parametrize("path", ["7pt", "matvec_padded", "foreign_dot",
                                  "pallas"])
def test_local_fallbacks_are_matvec_then_dot_bit_for_bit(path):
    A, dot = _operator(path)
    p = _vector("float32")
    ops = Ops(A, p, dot=dot, norm_ref=1.0)
    got = jax.jit(ops.matvec_dot)(p)
    want = jax.jit(lambda v: _apart(ops, v))(p)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), path


# -- the CG program -----------------------------------------------------------

def _cg_step_apart(ops, state):
    """``cg``'s step as it was before the hook: ``matvec``, then ``dot``."""
    x, r, p, rr = state
    Ap, pAp = _apart(ops, p)
    alpha = rr / pAp
    x = x + alpha * p
    r = r - alpha * Ap
    rr_new = ops.dot(r, r)
    beta = rr_new / rr
    p = r + beta * p
    return (x, r, p, rr_new)


def _lowered_cg(A, dot, step=None) -> str:
    mdef = get_method("cg")
    if step is not None:
        mdef = dataclasses.replace(mdef, step=step)
    b = _vector("float32")

    def run(b, x0):
        ops = Ops(A, b, dot=dot, norm_ref=1.0)
        return run_method(mdef, ops, x0, tol=1e-6, maxiter=50)

    return jax.jit(run).lower(b, jnp.zeros_like(b)).as_text()


@pytest.mark.parametrize("path", ["7pt", "matvec_padded", "foreign_dot",
                                  "pallas"])
def test_cg_program_of_a_fallback_is_unchanged(path):
    """Where the hook does not engage, the lowered CG program is the one
    a step of ``matvec`` and then ``dot`` lowers to, letter for letter."""
    A, dot = _operator(path)
    assert _lowered_cg(A, dot) == _lowered_cg(A, dot, _cg_step_apart)


_BARRIER = re.compile(r"^\s*%(\S+?)(?::2)? = stablehlo\.optimization_barrier "
                      r"%(\S+), %(\S+) : tensor<[^>]*>, tensor<f32>", re.M)


def _defs(text: str) -> dict[str, str]:
    """``{ssa name: its defining line}`` of a lowered module."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^\s*%([\w#]+)(?::\d+)? = .*$", text, re.M)}


def test_box_cg_fences_pAp_with_the_apply():
    """In the 27-point CG loop, ``p·Ap``'s multiply-reduce is an operand of
    the barrier that also holds the apply's result, and the step's ``r``
    update reads the apply from that barrier."""
    text = _lowered_cg(LocalOp(STENCIL_27PT), None)
    assert text != _lowered_cg(LocalOp(STENCIL_27PT), None, _cg_step_apart)
    defs = _defs(text)
    pairs = []
    for m in _BARRIER.finditer(text):
        out, q, s = m.groups()
        red = defs[s]
        if "stablehlo.reduce(" not in red or "stablehlo.add" not in red:
            continue
        prod = defs[re.search(r"reduce\(%(\S+) init", red).group(1)]
        assert "stablehlo.multiply" in prod and f"%{q}" in prod, prod
        pairs.append(out)
    # one in the loop body (init applies A to x0 with no dot)
    assert len(pairs) == 1, pairs
    uses = re.findall(rf"%{re.escape(pairs[0])}#0\b", text)
    assert uses, "the apply's result is read from the barrier"


# -- the distributed operator -------------------------------------------------

_DISTRIBUTED = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
jax.config.update("jax_enable_x64", True)
from jax import lax
from repro.core.distributed import DistributedOp, GridLayout
from repro.core.methods import Ops
from repro.core.operators import STENCIL_7PT, STENCIL_27PT
from test_matvec_dot import GRID, _apart, _user_matvec_padded, _vector

def mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names,
                axis_types=(AxisType.Auto,) * len(names))

LAYOUTS = {
    "1d": GridLayout(mesh((4,), ("cells",)), (None, None, "cells")),
    "2d": GridLayout(mesh((2, 2), ("data", "model")), ("model", "data", None)),
    "3d": GridLayout(mesh((2, 2, 2), ("pod", "data", "model")),
                     ("model", "data", "pod")),
}

def run(layout, body, p):
    spec = layout.spec()
    fn = jax.jit(jax.shard_map(body, mesh=layout.mesh, in_specs=(spec,),
                               out_specs=(spec, P())))
    y, s = fn(jax.device_put(p, NamedSharding(layout.mesh, spec)))
    return np.asarray(y), np.asarray(s)

out = {}
for ltag, layout in LAYOUTS.items():
    for dtype in ("float32", "float64"):
        p = _vector(dtype)
        got = {}
        for mode in ("overlap", "concat", "scatter"):
            def body(v):
                A = DistributedOp(STENCIL_27PT, layout, halo_mode=mode)
                return Ops(A, v, norm_ref=1.0).matvec_dot(v)
            got[mode] = run(layout, body, p)
            q_ref = np.asarray(STENCIL_27PT.matvec(p), np.float64)
            pn = np.asarray(p, np.float64)
            y, s = got[mode]
            out[f"{ltag}-{mode}-{dtype}"] = dict(
                dtype=str(s.dtype),
                q_err=float(np.max(np.abs(y - q_ref)) / np.max(np.abs(q_ref))),
                dot_err=float(abs(float(s) - np.dot(pn.ravel(), q_ref.ravel()))
                              / np.sum(np.abs(pn * q_ref))),
            )
        out[f"{ltag}-{dtype}-modes_agree"] = all(
            np.array_equal(got[m][0], got["overlap"][0])
            and np.array_equal(got[m][1], got["overlap"][1])
            for m in ("concat", "scatter"))
    # the fallbacks: matvec and then dot, bit for bit
    p = _vector("float32")
    axes = layout.reduce_axes
    for path in ("7pt", "matvec_padded", "foreign_dot"):
        def ops_of(v):
            st = STENCIL_7PT if path == "7pt" else STENCIL_27PT
            mv = _user_matvec_padded if path == "matvec_padded" else None
            A = DistributedOp(st, layout, matvec_padded=mv,
                              halo_mode="overlap")
            dot = ((lambda a, b: lax.psum(jnp.sum(a * b), axes))
                   if path == "foreign_dot" else None)
            return Ops(A, v, dot=dot, norm_ref=1.0)
        a = run(layout, lambda v: ops_of(v).matvec_dot(v), p)
        b = run(layout, lambda v: _apart(ops_of(v), v), p)
        out[f"{ltag}-{path}"] = bool(np.array_equal(a[0], b[0])
                                     and np.array_equal(a[1], b[1]))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def distributed():
    """Every case of ``DistributedOp.matvec_dot`` in one interpreter on
    eight host devices (four for the 1-D and 2-D layouts)."""
    return run_multidevice(_DISTRIBUTED, devices=8,
                           env={"PYTHONPATH": os.pathsep.join(
                               [f"{REPO_ROOT}/src", f"{REPO_ROOT}/tests"])})


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("mode", ["overlap", "concat", "scatter"])
@pytest.mark.parametrize("layout", ["1d", "2d", "3d"])
def test_distributed_box_matvec_dot_matches_the_global_apply(
        distributed, layout, mode, dtype):
    r = distributed[f"{layout}-{mode}-{dtype}"]
    assert r["dtype"] == dtype
    assert r["q_err"] <= RTOL[dtype], r
    assert r["dot_err"] <= RTOL[dtype], r


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("layout", ["1d", "2d", "3d"])
def test_distributed_halo_modes_agree_bit_for_bit(distributed, layout, dtype):
    assert distributed[f"{layout}-{dtype}-modes_agree"]


@pytest.mark.parametrize("path", ["7pt", "matvec_padded", "foreign_dot"])
@pytest.mark.parametrize("layout", ["1d", "2d", "3d"])
def test_distributed_fallbacks_are_matvec_then_dot_bit_for_bit(
        distributed, layout, path):
    assert distributed[f"{layout}-{path}"]
