"""Every solver reduction is a multiply and a sum in the operands' dtype.

``core.methods.local_dot`` is the one local dot product: ``Ops.dot``,
``Ops.dotn`` and ``dot2`` reach it through ``LocalOp``, through the local
partials of ``DistributedOp`` (one ``psum``) and through ``PallasOp``'s
fallback.  Each must agree with a float64 ``numpy.dot`` of the same arrays,
and no ``jnp.vdot`` (a ``dot_general``, which takes XLA's multi-limb
emulation in float64 on a TPU) may come back into the solver's reductions.
"""

import ast
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import REPO_ROOT, run_multidevice

from repro.core.methods import Ops
from repro.core.operators import STENCIL_27PT
from repro.core.solvers import LocalOp
from repro.kernels.pallas_op import PallasOp

GRID = (8, 8, 16)
RTOL = {"float32": 1e-6, "float64": 1e-13}


def _vectors(dtype: str) -> list[np.ndarray]:
    # positive entries: no cancellation, so the relative error is the
    # summation's own
    rng = np.random.default_rng(14)
    return [rng.uniform(0.5, 1.5, GRID).astype(dtype) for _ in range(4)]


def _expected(vs) -> np.ndarray:
    """``[a·b, c·d, a·d, a·b, c·d]``: what ``dot(a, b)``,
    ``dotn((c, d), (a, d))`` and ``dot2(a, b, c, d)`` return, by a float64
    ``numpy.dot`` of the same arrays."""
    a, b, c, d = (v.ravel().astype(np.float64) for v in vs)
    ab, cd, ad = np.dot(a, b), np.dot(c, d), np.dot(a, d)
    return np.array([ab, cd, ad, ab, cd])


def _ops_dots(A, a, b, c, d) -> jax.Array:
    ops = Ops(A, a)
    return jnp.stack([ops.dot(a, b), *ops.dotn((c, d), (a, d)),
                      *ops.dot2(a, b, c, d)])


_DISTRIBUTED = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
jax.config.update("jax_enable_x64", True)
from repro.core.compat import make_mesh
from repro.core.distributed import DistributedOp, GridLayout
from repro.core.operators import STENCIL_27PT
from test_dots import _ops_dots, _vectors

mesh = make_mesh((1, 4), ("data", "model"))
layout = GridLayout(mesh, (None, "data", "model"))
A = DistributedOp(STENCIL_27PT, layout)
out = {}
for dtype in ("float32", "float64"):
    fn = jax.jit(jax.shard_map(lambda *v: _ops_dots(A, *v), mesh=mesh,
                               in_specs=(layout.spec(),) * 4, out_specs=P()))
    got = fn(*(jnp.asarray(v) for v in _vectors(dtype)))
    out[dtype] = {"dtype": str(got.dtype), "values": np.asarray(
        got, np.float64).tolist()}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def distributed_dots():
    """The dots of ``DistributedOp`` on four host devices (z split four
    ways), both dtypes in one interpreter."""
    return run_multidevice(_DISTRIBUTED, devices=4,
                           env={"PYTHONPATH": os.pathsep.join(
                               [f"{REPO_ROOT}/src", f"{REPO_ROOT}/tests"])})


@pytest.mark.parametrize("op", ["LocalOp", "DistributedOp", "PallasOp"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_solver_dots_match_numpy(op, dtype, f64, request):
    vs = _vectors(dtype)
    if op == "DistributedOp":
        res = request.getfixturevalue("distributed_dots")[dtype]
        got_dtype, got = res["dtype"], np.array(res["values"])
    else:
        A = LocalOp(STENCIL_27PT)
        if op == "PallasOp":
            A = PallasOp(A)       # interpret-mode kernels off a TPU
        out = jax.jit(lambda *v: _ops_dots(A, *v))(
            *(jnp.asarray(v) for v in vs))
        got_dtype, got = str(out.dtype), np.asarray(out, np.float64)
    assert got_dtype == dtype     # summed in the operands' dtype
    np.testing.assert_allclose(got, _expected(vs), rtol=RTOL[dtype], atol=0)


def _solver_sources() -> list[pathlib.Path]:
    src = pathlib.Path(REPO_ROOT) / "src" / "repro"
    return sorted((src / "core").glob("*.py")) + [
        src / "kernels" / "pallas_op.py"]


def test_no_vdot_in_solver_reductions():
    found = []
    for path in _solver_sources():
        for node in ast.walk(ast.parse(path.read_text())):
            name = (node.attr if isinstance(node, ast.Attribute)
                    else node.id if isinstance(node, ast.Name) else None)
            if name == "vdot":
                found.append(f"{path.name}:{node.lineno}")
    assert not found, found
