"""The distributed 27-point apply and CG against the global apply and the
plain reference, on four host devices (in a child interpreter: the main
pytest process keeps one device).

* ``DistributedOp.matvec`` with its default apply equals ``Stencil.matvec``
  on the global grid bit for bit, in every halo mode, on the 1-D z split
  and the 2x2 x/y split; and agrees with ``bench/reference.apply27``
  within float32 rounding.
* A four-device ``SolverSession`` CG on the ``("cells",)`` mesh, built as
  the benchmark builds it, takes the iterations of ``bench/reference.cg``
  (± 1) and reaches a true residual under its tolerance.
* The session's zero initial guess is made on the mesh, one block per
  device, and not as the whole grid on one of them.

Where the collectives and the stencil arithmetic land in the program's
scopes, on the same four-device mesh, is
``tests/test_obs.py::test_sharded_collectives_land_in_halo_and_reduce``.
"""

import numpy as np
import pytest
from conftest import run_multidevice

_SCRIPT = r"""
import json, sys
sys.path[:0] = ["src", "."]
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from bench import generator, harness, reference
from repro.api import SolverSession
from repro.core.compat import make_mesh
from repro.core.distributed import DistributedOp, make_layout
from repro.core.operators import STENCILS

out = {}

x = jax.random.normal(jax.random.key(15), (16, 16, 32), jnp.float32)
# sum of the 27 terms' magnitudes at each point, for the rounding bound
mag = np.asarray(27 * jnp.abs(x)
                 + reference.neighbour_sum(jnp.pad(jnp.abs(x), 1)), np.float64)
ref27 = np.asarray(jax.jit(reference.apply27)(x), np.float64)
meshes = {"1d": make_mesh((4,), ("cells",)),
          "2d": make_mesh((2, 2), ("data", "model"))}
for mtag, mesh in meshes.items():
    layout = make_layout(mesh)
    spec = layout.spec()
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    for st, stencil in STENCILS.items():
        glob = np.asarray(jax.jit(stencil.matvec)(x))
        for hm in ("concat", "overlap", "scatter"):
            op = DistributedOp(stencil, layout, halo_mode=hm)
            y = np.asarray(jax.jit(jax.shard_map(
                op.matvec, mesh=mesh, in_specs=spec, out_specs=spec))(xs))
            r = {"bitwise": bool(np.array_equal(y, glob)),
                 "max_abs": float(np.abs(y - glob).max())}
            if st == "27pt":
                r["ref_ratio"] = float(
                    (np.abs(y.astype(np.float64) - ref27) / mag).max())
            out[f"mv-{mtag}-{st}-{hm}"] = r

spec = harness.load_spec()
_, config, traffic = harness.load_cell(spec, "cg27-f32-512-x4")
config = dict(config, block_per_chip=[16, 16, 8])
base = harness.build_session(config, jax.devices()[:4])
x0 = base.problem.x0(base.backend.sharding())
out["x0"] = {"global": list(x0.shape),
             "shards": sorted({tuple(s.data.shape)
                               for s in x0.addressable_shards})}
for hm in ("auto", "concat", "scatter"):
    sess = base if hm == "auto" else SolverSession(
        method=config["method"], grid=base.problem.shape,
        stencil=config["operator"],
        options=base.options.replace(halo_mode=hm), mesh=base.backend.mesh)
    (b,) = generator.make_rhs(dict(traffic, rhs_pool=1), 2 ** 33 + 15,
                              sess.problem.shape, sess.problem.dtype,
                              sess.backend.sharding())
    res = sess.solve(b)
    x_ref, k_ref = reference.cg(jax.device_put(b, jax.devices()[0]),
                                config["tol"], config["maxiter"])
    jax.config.update("jax_enable_x64", True)
    true = reference.true_rel_residual(res.x, b)
    jax.config.update("jax_enable_x64", False)
    out[f"cg-{hm}"] = {
        "halo_mode": sess.halo_mode, "iters": int(res.iters),
        "status": int(res.status), "ref_iters": int(k_ref),
        "true_rel_residual": true, "tol": config["tol"]}
print(json.dumps(out))
"""

LAYOUTS = ["1d", "2d"]
MODES = ["concat", "overlap", "scatter"]
SESSION_MODES = ["auto", "concat", "scatter"]


@pytest.fixture(scope="module")
def results():
    return run_multidevice(_SCRIPT, devices=4, env={"JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("stencil", ["7pt", "27pt"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_distributed_matvec_equals_global_apply(results, layout, stencil,
                                                mode):
    r = results[f"mv-{layout}-{stencil}-{mode}"]
    assert r["bitwise"], r


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_distributed_matvec_agrees_with_reference(results, layout, mode):
    """The two applies sum the 27 terms in different orders: each sum's
    rounding error is at most 26 float32 epsilons times the sum of the
    terms' magnitudes, so the two differ by at most twice that (3.05e-5
    was seen at 27 N(0, 1) terms)."""
    r = results[f"mv-{layout}-27pt-{mode}"]
    assert r["ref_ratio"] <= 2 * 26 * np.finfo(np.float32).eps, r


@pytest.mark.parametrize("mode", SESSION_MODES)
def test_four_device_cg_matches_reference_cg(results, mode):
    r = results[f"cg-{mode}"]
    assert r["halo_mode"] == ("overlap" if mode == "auto" else mode)
    assert r["status"] == 0
    assert abs(r["iters"] - r["ref_iters"]) <= 1, r
    assert r["true_rel_residual"] < r["tol"], r


def test_initial_guess_is_made_one_block_per_device(results):
    assert results["x0"] == {"global": [16, 16, 32], "shards": [[16, 16, 8]]}
