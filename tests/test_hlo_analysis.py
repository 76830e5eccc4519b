"""HLO analysis layer: shape parsing, collective counting, overlap slack —
plus the PR-4 acceptance claim: merged/pipelined Krylov iteration bodies
compile to exactly ONE all-reduce on a real multi-device mesh, where the
classics emit 2–3."""

import jax
import jax.numpy as jnp
import pytest
from conftest import run_multidevice

from repro.analysis.hlo import (
    collective_bytes,
    count_collectives,
    overlap_slack,
    parse_computations,
    shape_bytes,
)


def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[4096]") == 8192
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("(f32[8], s32[4])") == 32 + 16


def test_parse_simple_program():
    txt = jax.jit(lambda a, b: a @ b + 1.0).lower(
        jnp.zeros((8, 8)), jnp.zeros((8, 8))).compile().as_text()
    comps = parse_computations(txt)
    assert comps
    ops = {i.opcode for c in comps for i in c.instructions}
    assert any("dot" in o or "fusion" in o or "custom-call" in o for o in ops)


def test_no_collectives_single_device():
    txt = jax.jit(lambda a: a * 2).lower(jnp.zeros((8,))).compile().as_text()
    assert count_collectives(txt) == {}
    assert collective_bytes(txt) == 0


def test_trip_count_scaling():
    hlo = """
HloModule m
%body.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %ar = f32[8] all-reduce(%p), to_apply=%add
}
ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  ROOT %ar2 = f32[8] all-reduce(%x), to_apply=%add
}
"""
    base = collective_bytes(hlo)
    scaled = collective_bytes(hlo, trip_counts={"body": 10})
    assert scaled == base + 9 * 32  # body's 32B counted 10x


def test_overlap_slack_structure():
    hlo = """
ENTRY %main (x: f32[64], y: f32[64]) -> f32[64] {
  %x = f32[64] parameter(0)
  %y = f32[64] parameter(1)
  %ar = f32[64] all-reduce(%x), to_apply=%add
  %big = f32[64] multiply(%y, %y)
  ROOT %out = f32[64] add(%ar, %big)
}
"""
    rep = overlap_slack(hlo)
    assert len(rep) == 1
    # %big is independent of the all-reduce -> hideable work exists
    assert rep[0]["slack_bytes"] >= 256


# -----------------------------------------------------------------------------
# Reduction counts of the compiled shard_map iteration bodies (PR 4).
# One step of each method is lowered on an 8-host-device 1-D mesh in a
# subprocess (the main pytest process must keep seeing 1 device) and its
# all-reduces counted: the merged/pipelined variants' entire scalar traffic
# must ride ONE stacked psum, the classics keep one per (paired) dot.
# -----------------------------------------------------------------------------

_COUNT_SCRIPT = r"""
import json
import jax
jax.config.update("jax_enable_x64", True)
from repro.core.compat import make_mesh
from repro.core.problems import make_problem
from repro.core.distributed import solve_step_shardmap, step_state_layout
from repro.analysis.hlo import count_collectives
from jax.sharding import NamedSharding

mesh = make_mesh((8,), ("cells",))
prob = make_problem((8, 8, 16), "27pt")
out = {}
for m in ("cg", "bicgstab", "pcg",
          "cg_merged", "cg_pipe", "pcg_merged", "pcg_pipe",
          "bicgstab_merged", "pbicgstab_merged"):
    fn, layout = solve_step_shardmap(prob, m, mesh)
    sh = NamedSharding(mesh, layout.spec())
    vecs, scals = step_state_layout(m)
    arr = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
    scal = jax.ShapeDtypeStruct((), prob.dtype)
    args = [arr] * (1 + len(vecs)) + [scal] * len(scals)
    txt = jax.jit(fn).lower(*args).compile().as_text()
    out[m] = count_collectives(txt).get("all-reduce", 0)
# cg and pcg take p·Ap from the apply's pass (Ops.matvec_dot) in every
# halo mode; the 7-point cross keeps its dot apart
for st in ("27pt", "7pt"):
    prob = make_problem((8, 8, 16), st)
    for m in ("cg", "pcg"):
        for hm in ("concat", "overlap"):
            fn, layout = solve_step_shardmap(prob, m, mesh, halo_mode=hm)
            sh = NamedSharding(mesh, layout.spec())
            vecs, scals = step_state_layout(m)
            arr = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
            scal = jax.ShapeDtypeStruct((), prob.dtype)
            args = [arr] * (1 + len(vecs)) + [scal] * len(scals)
            txt = jax.jit(fn).lower(*args).compile().as_text()
            out[f"{m}-{st}-{hm}"] = count_collectives(txt).get("all-reduce", 0)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def allreduce_counts():
    return run_multidevice(_COUNT_SCRIPT)


def test_classics_emit_multiple_allreduces(allreduce_counts):
    assert allreduce_counts["cg"] == 2
    assert allreduce_counts["bicgstab"] == 3
    assert allreduce_counts["pcg"] == 2      # p·Ap + the fused (r·z, r·r) pair


@pytest.mark.parametrize("halo_mode", ["concat", "overlap"])
@pytest.mark.parametrize("stencil", ["27pt", "7pt"])
@pytest.mark.parametrize("method", ["cg", "pcg"])
def test_matvec_dot_keeps_one_allreduce_per_dot(allreduce_counts, method,
                                                 stencil, halo_mode):
    """p·Ap from the apply's pass keeps its one psum: 2 per iteration."""
    assert allreduce_counts[f"{method}-{stencil}-{halo_mode}"] == 2


def test_merged_and_pipelined_emit_exactly_one_allreduce(allreduce_counts):
    """The tentpole claim, verified on compiled HLO: every reduction-hiding
    variant's iteration body contains exactly ONE all-reduce."""
    for m in ("cg_merged", "cg_pipe", "pcg_merged", "pcg_pipe",
              "bicgstab_merged", "pbicgstab_merged"):
        assert allreduce_counts[m] == 1, (m, allreduce_counts)
