"""Compile the main path's kernels and solve loop for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described and not attached.  What interpret-mode tests
cannot show — a block layout Mosaic refuses, a kernel over the scoped VMEM,
a float64 kernel — fails here, at the production size (128³).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.operators import STENCIL_27PT
from repro.core.solvers import SOLVERS, LocalOp
from repro.kernels import ops
from repro.kernels.cg_fused_update import fused_cg_body
from repro.kernels.rb_gs import rb_gs_half_sweep
from repro.kernels.spmv_dot import stencil_spmv_dots
from repro.kernels.stencil_spmv import stencil_spmv

N = 128


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the solvers run with x64 on; the kernels must compile under it
    jax.config.update("jax_enable_x64", True)
    yield topo.devices
    jax.config.update("jax_enable_x64", prev_x64)
    jax.config.update("jax_enable_compilation_cache", prev_cache)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    st = STENCIL_27PT
    padded, grid, scalar = (N + 2,) * 3, (N,) * 3, ()
    return {
        "stencil_spmv": (
            lambda xp: stencil_spmv(xp, stencil=st, interpret=False),
            (padded,)),
        "stencil_spmv_dots": (
            lambda xp: stencil_spmv_dots(xp, stencil=st, interpret=False),
            (padded,)),
        "rb_gs": (
            lambda xp, b: rb_gs_half_sweep(xp, b, stencil=st, colour=0,
                                           interpret=False),
            (padded, grid)),
        "fused_cg_body": (
            lambda *a: fused_cg_body(*a, interpret=False),
            (scalar, scalar) + (grid,) * 5),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_arg(s, jnp.float32, one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_cg_loop_compiles_in_f64_for_v5e(one_chip):
    cg = SOLVERS["cg"]
    op = LocalOp(STENCIL_27PT)
    b = _arg((N,) * 3, jnp.float64, one_chip)
    compiled = jax.jit(
        lambda b, x0: cg(op, b, x0, tol=1e-6, maxiter=600)).lower(b, b).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    # one while: the solve loop.  A float64 dot_general would add XLA's
    # multi-limb dot emulation loops (17 more in this loop)
    assert len(re.findall(r"\swhile\(", hlo)) == 1
    mem = compiled.memory_analysis()
    # x, r, p, Ap and b at 128³ in f64 fit a 16 GB chip many times over
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2e9
    # and the dots stage no vector-sized f32 pieces in temporaries
    assert mem.temp_size_in_bytes < 100e6


def test_27pt_apply_with_dot_at_512_compiles_for_v5e(one_chip):
    """The one-chip cell's apply, q = A p with p·q, at 512³ float32.  The
    separable box sum keeps the padded operand and two partial sums, each
    about half a gigabyte; a formulation that stages much more (a 3-D
    convolution staged 68.7 GB) fails here."""
    from repro.core.methods import local_dot

    op = LocalOp(STENCIL_27PT)

    def apply(p):
        q = op.matvec(p)
        return q, local_dot(p, q)

    p = _arg((512,) * 3, jnp.float32, one_chip)
    compiled = jax.jit(apply).lower(p).compile()
    assert "convolution" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9


def _pAp_fusions(hlo: str) -> list[tuple[str, int]]:
    """``(scope, slices)`` of each fusion that holds a reduce of CG's
    ``p·Ap`` (scoped ``repro.reduce`` inside ``Ops.matvec_dot``'s
    ``repro.matvec``): the scope ``op_scopes`` bills it to, and how many
    slices of the apply's operand it holds besides."""
    from repro.obs.scopes import op_scopes_from_text

    comps, comp = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$", line)
        if m:
            comp = comps.setdefault(m.group(1), [])
        elif comp is not None:
            comp.append(line)
    module = re.match(r"HloModule ([^\s,]+)", hlo).group(1)
    scopes = op_scopes_from_text(hlo)
    out = []
    for name, lines in comps.items():
        if not any(" reduce(" in ln and "repro.matvec/repro.reduce" in ln
                   for ln in lines):
            continue
        slices = sum(" slice(" in ln for ln in lines)
        for m in re.finditer(rf"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*"
                             rf"calls=%{re.escape(name)}\b", hlo, re.M):
            out.append((scopes[(module, m.group(1))], slices))
    return out


@pytest.mark.parametrize("n,dtype", [(512, jnp.float32), (128, jnp.float64)])
def test_cg_bills_pAp_where_it_is_reduced(one_chip, n, dtype):
    """The one-chip cells' CG loop: ``p·Ap`` is billed to ``repro.matvec``
    where XLA reduces it in the apply's last pass, and to ``repro.reduce``
    where it keeps a pass of its own.  In float32 at 512³ it rides the
    apply's pass, so ``reduce_ms`` no longer holds it."""
    x64 = dtype == jnp.float64
    jax.config.update("jax_enable_x64", x64)   # as the harness runs it
    try:
        b = _arg((n,) * 3, dtype, one_chip)
        op = LocalOp(STENCIL_27PT)
        hlo = jax.jit(lambda b, x0: SOLVERS["cg"](
            op, b, x0, tol=1e-6, maxiter=600)).lower(b, b).compile().as_text()
    finally:
        jax.config.update("jax_enable_x64", True)
    found = _pAp_fusions(hlo)
    assert len(found) == 1, found
    for scope, slices in found:
        assert scope == ("repro.matvec" if slices else "repro.reduce")
    if not x64:
        assert found[0][1] > 0, "p·Ap has a pass of its own"


def test_f64_pallas_request_raises_before_compiling(one_chip, monkeypatch):
    from repro.api import SolverOptions, SolverSession
    # steer the session as it is steered on a TPU backend
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="float32 or bfloat16"):
        SolverSession(method="cg_merged", grid=(N,) * 3, stencil="27pt",
                      options=SolverOptions(pallas=True))
    # float32 is accepted
    SolverSession(method="cg_merged", grid=(N,) * 3, stencil="27pt",
                  options=SolverOptions(pallas=True, f64=False))


def test_four_chip_cg_at_512_per_chip_compiles_for_v5e_2x2(v5e_2x2):
    """The benchmark's four-chip cell, built as the benchmark builds it:
    512³ float32 per chip, 512 x 512 x 2048 split along z.  Its stencil is
    the slice-add apply; a 3-D convolution there staged one 68.7 GB
    intermediate, four times a chip's memory."""
    import json
    import pathlib

    from bench import harness

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "bench/configs/hpcg27-cg-f32-b512-x4.json").read_text())
    jax.config.update("jax_enable_x64", False)   # as the harness runs float32
    try:
        sess = harness.build_session(config, list(v5e_2x2))
        assert sess.problem.shape == (512, 512, 2048)
        assert sess.halo_mode == "overlap"
        arg = jax.ShapeDtypeStruct(sess.problem.shape, sess.problem.dtype,
                                   sharding=sess.backend.sharding())
        compiled = sess._build_fn().lower(arg, arg).compile()
    finally:
        jax.config.update("jax_enable_x64", True)
    hlo = compiled.as_text()
    assert "convolution" not in hlo
    assert "collective-permute" in hlo and "all-reduce" in hlo
    # p·Ap's partials ride the passes of the interior's and the shell
    # slabs' apply, billed with them; its psum is the all-reduce above
    found = _pAp_fusions(hlo)
    assert found and all(sc == "repro.matvec" and sl for sc, sl in found)
    mem = compiled.memory_analysis()
    # per chip: b and x0 (1.07 GB) in arguments; x, r, p, q and the
    # padded operand's pieces in temporaries
    assert mem.argument_size_in_bytes == 2 * 4 * 512 ** 3
    assert mem.temp_size_in_bytes < 6e9
