"""The repro.precond subsystem.

Covers the ISSUE-3 acceptance criteria:
  * pcg with EACH of the four preconditioners converges on the 64^3 7pt
    problem in strictly fewer iterations than plain cg at the same
    tolerance (and the paper's absolute criterion);
  * every SPD-preserving preconditioner keeps pcg convergent on the
    7pt/27pt problems, to the dense-solve reference;
  * precond="jacobi" parity between the local and shard_map backends (the
    subprocess asserts the repo's established local-vs-distributed standard
    — identical iteration counts, 1e-9 solutions: even the RAW SpMV is not
    bitwise across worlds, the compiler contracts per shape — plus strict
    bit-for-bit identity where it is well-defined, facade-vs-direct within
    the shard_map world; halo modes agree to last-digit rounding, and the
    batched path matches single solves);
  * the Pallas Chebyshev/block-Jacobi kernels match their kernels/ref.py
    oracles to machine precision, and the use_pallas apply path matches the
    jnp path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_multidevice

from repro.api import REGISTRY, SolverOptions, SolverSession, solve
from repro.core.operators import STENCIL_7PT, STENCIL_27PT, build_dense_from_stencil
from repro.core.problems import make_problem
from repro.core.solvers import LocalOp, cg, pcg, bicgstab, pbicgstab
from repro.kernels import ops, ref
from repro.precond import (
    PRECONDITIONERS,
    BlockJacobi,
    Chebyshev,
    PointJacobi,
    SSOR,
    gershgorin_bounds,
    make_precond,
    precond_names,
)

pytestmark = pytest.mark.usefixtures("f64")

PRECONDS = ("jacobi", "block_jacobi", "ssor", "chebyshev")
SHAPE = (10, 10, 12)


@pytest.fixture(scope="module", params=["7pt", "27pt"])
def problem(request):
    prob = make_problem(SHAPE, request.param)
    A = LocalOp(prob.stencil)
    Ad = build_dense_from_stencil(prob.stencil, SHAPE)
    xref = np.linalg.solve(Ad, np.asarray(prob.b(), np.float64).reshape(-1))
    return prob, A, xref.reshape(SHAPE)


# -----------------------------------------------------------------------------
# protocol / registry / metadata
# -----------------------------------------------------------------------------

def test_registry_and_factory():
    assert set(PRECONDS) == set(PRECONDITIONERS)
    assert precond_names() == ("none", *sorted(PRECONDS))
    assert make_precond("none") is None
    assert make_precond(None) is None
    with pytest.raises(KeyError, match="unknown preconditioner"):
        make_precond("ilu")
    with pytest.raises(ValueError, match="params"):
        make_precond("none", sweeps=2)
    for name in PRECONDS:
        inst = make_precond(name)
        assert inst.name == name
        # the subsystem's design constraint: no new barriers, ever
        assert inst.extra_reductions_per_apply == 0, name
        assert inst.spd_preserving, name
        assert inst.touched_elements_per_apply(27) > 0, name
    # block-Jacobi is communication-free by construction
    assert make_precond("block_jacobi").halo_matvecs_per_apply == 0
    assert make_precond("jacobi", sweeps=3).halo_matvecs_per_apply == 2
    assert make_precond("ssor").halo_hide == "none"
    assert make_precond("chebyshev", degree=5).matvecs_per_apply == 4


def test_param_validation():
    with pytest.raises(ValueError, match="sweeps"):
        PointJacobi(sweeps=0)
    with pytest.raises(ValueError, match="omega"):
        BlockJacobi(omega=1.5)
    with pytest.raises(ValueError, match="omega"):
        SSOR(omega=2.0)
    with pytest.raises(ValueError, match="degree"):
        Chebyshev(degree=0)
    with pytest.raises(ValueError, match="bounds"):
        Chebyshev(bounds=(-1.0, 2.0)).setup(LocalOp(STENCIL_7PT))


def test_gershgorin_bounds():
    assert gershgorin_bounds(STENCIL_7PT) == (21.0, 33.0)
    assert gershgorin_bounds(STENCIL_27PT) == (1.0, 53.0)


def test_solver_registry_hooks():
    for m in ("pcg", "pbicgstab"):
        assert REGISTRY[m].accepts_precond
    assert REGISTRY["pcg"].precond_applies_per_iter == 1
    assert REGISTRY["pbicgstab"].precond_applies_per_iter == 2
    assert REGISTRY["pcg"].variant_of == "cg"
    assert REGISTRY["pbicgstab"].variant_of == "bicgstab"
    for m in ("cg", "cg_nb", "bicgstab", "bicgstab_b1", "jacobi"):
        assert not REGISTRY[m].accepts_precond


# -----------------------------------------------------------------------------
# convergence property: every SPD-preserving preconditioner keeps pcg
# convergent (to the dense reference) on both stencils
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("name", PRECONDS)
def test_pcg_converges_with_every_spd_preconditioner(problem, name):
    prob, A, xref = problem
    M = make_precond(name).bind(A)
    res = pcg(A, prob.b(), prob.x0(), tol=1e-8, maxiter=800, norm_ref=1.0,
              M=M)
    assert int(res.iters) < 800, name
    assert float(res.res_norm) < 1e-8, name
    # the reported residual is the TRUE residual (same contract as cg)
    true_r = float(jnp.linalg.norm((prob.b() - A.matvec(res.x)).reshape(-1)))
    assert abs(true_r - float(res.res_norm)) <= 1e-6 * max(true_r, 1.0)
    np.testing.assert_allclose(np.asarray(res.x), xref, atol=1e-7,
                               err_msg=name)


@pytest.mark.parametrize("name", PRECONDS)
def test_pbicgstab_converges_with_every_preconditioner(problem, name):
    prob, A, xref = problem
    M = make_precond(name).bind(A)
    res = pbicgstab(A, prob.b(), prob.x0(), tol=1e-8, maxiter=800,
                    norm_ref=1.0, M=M)
    assert int(res.iters) < 800, name
    assert float(res.res_norm) < 1e-8, name
    np.testing.assert_allclose(np.asarray(res.x), xref, atol=1e-6,
                               err_msg=name)


def test_pcg_identity_matches_cg_bitwise(problem):
    """With M=None the preconditioned forms ARE the classical methods."""
    prob, A, _ = problem
    r1 = cg(A, prob.b(), prob.x0(), tol=1e-8, maxiter=500, norm_ref=1.0)
    r2 = pcg(A, prob.b(), prob.x0(), tol=1e-8, maxiter=500, norm_ref=1.0)
    assert int(r1.iters) == int(r2.iters)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    b1 = bicgstab(A, prob.b(), prob.x0(), tol=1e-8, maxiter=500, norm_ref=1.0)
    b2 = pbicgstab(A, prob.b(), prob.x0(), tol=1e-8, maxiter=500,
                   norm_ref=1.0)
    assert int(b1.iters) == int(b2.iters)
    np.testing.assert_array_equal(np.asarray(b1.x), np.asarray(b2.x))


# -----------------------------------------------------------------------------
# the acceptance criterion: strictly fewer iterations than cg at 64^3 / 7pt
# -----------------------------------------------------------------------------

def test_pcg_strictly_beats_cg_on_64cubed_7pt():
    prob = make_problem((64, 64, 64), "7pt")
    A = LocalOp(prob.stencil)
    b, x0 = prob.b(), prob.x0()
    base = cg(A, b, x0, tol=1e-6, maxiter=700, norm_ref=1.0)
    assert int(base.iters) < 700
    for name in PRECONDS:
        res = pcg(A, b, x0, tol=1e-6, maxiter=700, norm_ref=1.0,
                  M=make_precond(name).bind(A))
        assert float(res.res_norm) < 1e-6, name
        assert int(res.iters) < int(base.iters), (
            name, int(res.iters), int(base.iters))


# -----------------------------------------------------------------------------
# facade plumbing
# -----------------------------------------------------------------------------

def test_facade_precond_options(problem):
    prob, A, _ = problem
    base = solve(prob, method="cg", tol=1e-8, maxiter=800)
    res = solve(prob, method="pcg", precond="chebyshev", tol=1e-8,
                maxiter=800)
    assert int(res.iters) < int(base.iters)
    # facade == direct (jitted) solver call, bit for bit — the zero-cost
    # contract; the facade jits the solve, so the reference must too (the
    # Chebyshev axpby chain fuses differently op-by-op)
    direct = jax.jit(
        lambda b, x0: pcg(A, b, x0, tol=1e-8, maxiter=800, norm_ref=1.0,
                          M=make_precond("chebyshev").bind(A))
    )(prob.b(), prob.x0())
    assert int(res.iters) == int(direct.iters)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(direct.x))
    # precond_params reach the constructor
    r6 = solve(prob, method="pcg", precond="chebyshev",
               precond_params={"degree": 6}, tol=1e-8, maxiter=800)
    assert int(r6.iters) <= int(res.iters)


def test_facade_precond_validation(problem):
    prob, _, _ = problem
    with pytest.raises(ValueError, match="precond"):
        SolverOptions(precond="ilu")
    with pytest.raises(ValueError, match="precond_params"):
        SolverOptions(precond_params={"sweeps": 2})
    with pytest.raises(ValueError, match="takes no preconditioner"):
        SolverSession(prob, method="cg",
                      options=SolverOptions(precond="jacobi"))
    sess = SolverSession(prob, method="pcg",
                         options=SolverOptions(precond="ssor"))
    assert "precond=ssor" in sess.describe()


def test_pcg_rejects_non_spd_preserving_precond(problem, monkeypatch):
    """spd_preserving gates pcg (CG's short recurrence silently breaks on a
    non-symmetric M); pbicgstab has no such requirement."""
    prob, _, _ = problem
    monkeypatch.setattr(PointJacobi, "spd_preserving", False)
    with pytest.raises(ValueError, match="SPD-preserving"):
        SolverSession(prob, method="pcg",
                      options=SolverOptions(precond="jacobi"))
    SolverSession(prob, method="pbicgstab",
                  options=SolverOptions(precond="jacobi"))


def test_batched_precond_matches_single(problem):
    prob, _, _ = problem
    sess = SolverSession(prob, method="pcg", options=SolverOptions(
        tol=1e-8, maxiter=400, norm_ref=None, precond="jacobi"))
    rng = np.random.default_rng(0)
    bs = jnp.asarray(rng.standard_normal((4, *SHAPE)))
    bres = sess.solve_batched(bs)
    for i in (0, 3):
        single = sess.solve(b=bs[i])
        assert int(bres.iters[i]) == int(single.iters), i
        np.testing.assert_allclose(np.asarray(bres.x[i]),
                                   np.asarray(single.x), atol=1e-12)


# -----------------------------------------------------------------------------
# Pallas kernels vs refs (machine precision) and the use_pallas apply path
# -----------------------------------------------------------------------------

KTOLS = {jnp.float32: dict(rtol=1e-4, atol=1e-5),
         jnp.float64: dict(rtol=1e-12, atol=1e-12)}


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.float64], ids=str)
def test_cheb_fused_step_kernel_matches_ref(stencil, dt):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    z = jax.random.normal(k1, (12, 10, 16), dt)
    r = jax.random.normal(k2, (12, 10, 16), dt)
    d = jax.random.normal(k3, (12, 10, 16), dt)
    zp = jnp.pad(z, 1)
    zn, dn = ops.cheb_step(zp, r, d, stencil, a=0.37, c=1.21)
    znr, dnr = ref.cheb_fused_step_ref(zp, r, d, stencil=stencil,
                                       a=0.37, c=1.21)
    np.testing.assert_allclose(np.asarray(zn), np.asarray(znr), **KTOLS[dt])
    np.testing.assert_allclose(np.asarray(dn), np.asarray(dnr), **KTOLS[dt])


@pytest.mark.parametrize("stencil", [STENCIL_7PT, STENCIL_27PT],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.float64], ids=str)
def test_block_jacobi_sweep_kernel_matches_ref(stencil, dt):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1), 2)
    z = jax.random.normal(k1, (12, 10, 16), dt)
    r = jax.random.normal(k2, (12, 10, 16), dt)
    zp = jnp.pad(z, 1)
    zs = ops.jacobi_sweep(zp, r, stencil, omega=0.9)
    zsr = ref.block_jacobi_sweep_ref(zp, r, stencil=stencil, omega=0.9)
    np.testing.assert_allclose(np.asarray(zs), np.asarray(zsr), **KTOLS[dt])


@pytest.mark.parametrize("cls", [Chebyshev, BlockJacobi],
                         ids=lambda c: c.name)
def test_use_pallas_apply_matches_jnp(cls):
    prob = make_problem((12, 12, 16), "27pt")
    A = LocalOp(prob.stencil)
    r = jax.random.normal(jax.random.PRNGKey(2), prob.shape, jnp.float64)
    z_jnp = cls().bind(A)(r)
    z_pal = cls(use_pallas=True).bind(A)(r)
    np.testing.assert_allclose(np.asarray(z_jnp), np.asarray(z_pal),
                               rtol=1e-12, atol=1e-12)


def test_pallas_flag_flows_into_precond(problem):
    prob, _, _ = problem
    sess = SolverSession(prob, method="pcg", options=SolverOptions(
        precond="chebyshev", pallas=True))
    assert sess.precond.use_pallas
    sess2 = SolverSession(prob, method="pcg", options=SolverOptions(
        precond="chebyshev", pallas=True,
        precond_params={"use_pallas": False}))
    assert not sess2.precond.use_pallas
    sess3 = SolverSession(prob, method="pcg", options=SolverOptions(
        precond="jacobi", pallas=True))     # no pallas kernel: flag ignored
    assert sess3.precond is not None


# -----------------------------------------------------------------------------
# local vs shard_map parity (subprocess: main process must keep 1 device)
# -----------------------------------------------------------------------------

_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.api import SolverOptions, SolverSession
from repro.core.distributed import DistributedOp, make_layout, solve_shardmap
from repro.core.problems import make_problem
from repro.core.solvers import LocalOp
from repro.launch.mesh import make_solver_mesh
from repro.precond import make_precond

prob = make_problem((12, 12, 16), "7pt")
mesh = make_solver_mesh(8)
layout = make_layout(mesh)
sh = NamedSharding(mesh, layout.spec())
out = {}

# 1) solve-level parity, local vs shard_map, pcg + jacobi (and chebyshev)
for pname in ("jacobi", "chebyshev"):
    opts = SolverOptions(tol=1e-6, maxiter=700, precond=pname)
    loc = SolverSession(prob, method="pcg",
                        options=opts.replace(layout="local")).solve()
    dist_sess = SolverSession(prob, method="pcg", options=opts, mesh=mesh)
    dist = dist_sess.solve()
    # facade vs direct shard_map build: bit for bit (zero-cost contract)
    fn, _ = solve_shardmap(prob, "pcg", mesh, tol=1e-6, maxiter=700,
                           halo_mode="overlap",
                           precond=make_precond(pname))
    direct = jax.jit(fn)(jax.device_put(prob.b(), sh),
                         jax.device_put(prob.x0(), sh))
    out[pname] = dict(
        loc_iters=int(loc.iters), dist_iters=int(dist.iters),
        max_dx=float(jnp.abs(loc.x - dist.x).max()),
        hist_close=bool(np.allclose(np.asarray(loc.history),
                                    np.asarray(dist.history),
                                    rtol=1e-9, equal_nan=True)),
        facade_bitwise=bool(np.array_equal(np.asarray(dist.x),
                                           np.asarray(direct.x))),
    )

# 2) halo-mode parity for the preconditioned solve: identical iteration
# counts and ulp-level solutions (the M apply's elementwise chain around
# the matvec fuses differently per mode — unlike plain cg, whose body
# stays bitwise — so strict bit equality is not well-defined here)
ref_x, iters = None, set()
mode_maxdiff = 0.0
for mode in ("concat", "scatter", "overlap"):
    fn, _ = solve_shardmap(prob, "pcg", mesh, tol=1e-6, maxiter=700,
                           halo_mode=mode, precond=make_precond("jacobi"))
    res = jax.jit(fn)(jax.device_put(prob.b(), sh),
                      jax.device_put(prob.x0(), sh))
    x = np.asarray(res.x)
    iters.add(int(res.iters))
    if ref_x is None:
        ref_x = x
    mode_maxdiff = max(mode_maxdiff, float(np.abs(ref_x - x).max()))
out["halo_modes_iters_agree"] = len(iters) == 1
out["halo_modes_maxdiff"] = mode_maxdiff

# 3) batched preconditioned solves on the mesh match single solves
sess = SolverSession(prob, method="pcg", mesh=mesh,
                     options=SolverOptions(tol=1e-6, maxiter=700,
                                           precond="jacobi"))
rng = np.random.default_rng(1)
bs = jnp.asarray(rng.standard_normal((4, 12, 12, 16)))
bres = sess.solve_batched(bs)
out["batched_max_dx"] = max(
    float(jnp.abs(bres.x[i] - sess.solve(b=bs[i]).x).max()) for i in (0, 3))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def parity_results():
    return run_multidevice(_PARITY_SCRIPT)


def test_local_vs_shardmap_parity(parity_results):
    """The repo's local-vs-distributed standard: identical iteration counts,
    1e-9-identical solutions and residual histories (the raw SpMV already
    differs in the last bits across worlds — per-shape compiler
    contraction), for the preconditioned solves too."""
    for pname in ("jacobi", "chebyshev"):
        r = parity_results[pname]
        assert r["loc_iters"] == r["dist_iters"], (pname, r)
        assert r["max_dx"] < 1e-9, (pname, r)
        assert r["hist_close"], pname
        assert r["facade_bitwise"], pname


def test_preconditioned_halo_modes_parity(parity_results):
    """All three halo modes agree on pcg+jacobi: same iteration counts,
    solutions equal to a couple of ulp (the preconditioner's elementwise
    chain fuses differently per mode, so — unlike plain cg — strict bit
    equality does not survive; 1e-13 pins last-digit rounding only)."""
    assert parity_results["halo_modes_iters_agree"]
    assert parity_results["halo_modes_maxdiff"] < 1e-13


def test_preconditioned_batched_parity(parity_results):
    assert parity_results["batched_max_dx"] < 1e-10
