"""repro.obs: tracing, scopes, convergence telemetry.

The load-bearing contracts:

  * telemetry OFF is a bitwise no-op — same ``SolveResult`` leaves as a
    solve that never heard of telemetry (``SolveResult.telemetry is None``
    keeps the pytree shape identical, so the lowered HLO is too — the
    ``make audit`` baseline pins that);
  * telemetry ON reports the truth — the buffered residual curve equals
    the driver's ``history`` and the final entry matches an *offline*
    ``||b - A x||`` recompute, for every registry method on the local and
    the shard_map backend;
  * the span stream round-trips — records written by an instrumented
    solve validate against the schema and aggregate through the CLI
    summarizer;
  * the scope map places each compiled op in the part of the solve that
    issued it — the stencil in ``repro.matvec``, dots and psums in
    ``repro.reduce``, ppermutes in ``repro.halo`` — locally and on a mesh;
  * the serve/monitor record unification keeps old readers working —
    pre-PR-8 heartbeat/metrics shapes still parse, and the committed
    PR-6-era ``BENCH_serve.json`` still passes its gate.
"""

import collections
import json
import os
import re
import time

import numpy as np
import pytest
from conftest import REPO_ROOT, run_multidevice

from repro.api import SolverOptions, SolverSession, solve
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS, LocalOp
from repro.obs import trace as obs
from repro.obs.convergence import (curve_record, effective_rows,
                                   residual_curve, scalar_history,
                                   telemetry_residuals, true_residual_norm)

pytestmark = pytest.mark.usefixtures("f64")

SHAPE = (10, 10, 12)


@pytest.fixture(scope="module")
def problem():
    return make_problem(SHAPE, "27pt")


@pytest.fixture()
def tracer_path(tmp_path):
    """An enabled tracer for the test body, torn down unconditionally so
    the module-global tracer never leaks into other tests."""
    path = str(tmp_path / "trace.jsonl")
    obs.enable(path)
    yield path
    obs.disable()


def _tele_opts(**kw):
    base = dict(tol=1e-8, maxiter=2000, telemetry=True,
                telemetry_buffer=4096)
    base.update(kw)
    return SolverOptions(**base)


# -----------------------------------------------------------------------------
# telemetry off == bitwise no-op
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["cg", "cg_merged", "bicgstab"])
def test_telemetry_off_is_bitwise_noop(problem, method):
    off = solve(problem, method=method, tol=1e-8, maxiter=2000)
    on = solve(problem, method=method, options=_tele_opts())
    assert off.telemetry is None
    assert on.telemetry is not None
    assert int(off.iters) == int(on.iters)
    assert float(off.res_norm) == float(on.res_norm)
    np.testing.assert_array_equal(np.asarray(off.x), np.asarray(on.x))
    np.testing.assert_array_equal(np.asarray(off.history),
                                  np.asarray(on.history))


def test_telemetry_off_matches_direct_solver_bitwise(problem):
    """The facade with telemetry off == the raw solver fn that never took
    a telemetry kwarg (the zero-cost-abstraction contract extended)."""
    res = solve(problem, method="cg", tol=1e-8, maxiter=2000)
    ref = SOLVERS["cg"](LocalOp(problem.stencil), problem.b(), problem.x0(),
                        tol=1e-8, maxiter=2000, norm_ref=1.0)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(ref.x))
    assert res.telemetry is None and ref.telemetry is None


# -----------------------------------------------------------------------------
# telemetry on: the curves are true
# -----------------------------------------------------------------------------

@pytest.mark.parametrize("method,precond", [
    ("cg", None), ("bicgstab", None), ("pcg", "jacobi"),
])
def test_telemetry_matches_offline_residual(problem, method, precond):
    """The buffered curve's final entry == an offline ||b - A x|| recompute
    (recurrence drift is O(eps * kappa) — loose relative tolerance)."""
    kw = {"precond": precond} if precond else {}
    res = solve(problem, method=method,
                options=_tele_opts(maxiter=400, **kw))
    tele_res = telemetry_residuals(res, method)
    true_res = true_residual_norm(LocalOp(problem.stencil), problem.b(),
                                  res.x)
    assert tele_res.shape == (int(res.iters) + 1,)
    assert float(tele_res[-1]) == pytest.approx(float(res.res_norm))
    assert float(tele_res[-1]) == pytest.approx(true_res, rel=1e-3,
                                                abs=1e-10)


@pytest.mark.parametrize("method", sorted(SOLVERS))
def test_telemetry_all_methods_local(problem, method):
    """Every registry method carries a telemetry buffer whose residual
    column reproduces the driver's history curve."""
    from repro.core.methods import get_method
    mdef = get_method(method)
    res = solve(problem, method=method, options=_tele_opts(maxiter=600))
    tele = np.asarray(res.telemetry)
    assert tele.shape == (601, len(mdef.scalars))
    rows = effective_rows(res)
    assert rows == int(res.iters) + 1
    np.testing.assert_allclose(telemetry_residuals(res, method),
                               np.asarray(res.history)[:rows],
                               rtol=1e-12, atol=0)
    hist = scalar_history(res, method)
    assert set(hist) == set(mdef.scalars)
    assert all(v.shape == (rows,) for v in hist.values())


def test_telemetry_buffer_overflow_keeps_final_state(problem):
    """A buffer smaller than the iteration count overwrites its last row:
    no NaNs, and the last row holds the *final* scalar state."""
    res = solve(problem, method="jacobi",
                options=SolverOptions(tol=1e-12, maxiter=50, telemetry=True,
                                      telemetry_buffer=4))
    tele = np.asarray(res.telemetry)
    assert tele.shape[0] == 4 and int(res.iters) > 4
    assert not np.isnan(tele).any()
    assert float(np.sqrt(tele[-1, 0])) == pytest.approx(float(res.res_norm))
    assert effective_rows(res) == 4


def test_curve_record_is_json_able(problem):
    res = solve(problem, method="cg", options=_tele_opts(maxiter=400))
    rec = curve_record(res, "cg", scalars=True)
    json.dumps(rec)                       # must round-trip
    assert rec["iters"] == int(res.iters)
    assert len(rec["residuals"]) == int(res.iters) + 1
    assert rec["telemetry_rows"] == int(res.iters) + 1
    assert rec["residuals"][-1] == pytest.approx(float(res.res_norm))
    np.testing.assert_allclose(rec["scalars"]["rr"],
                               np.asarray(res.history)[:int(res.iters) + 1]
                               ** 2, rtol=1e-12)
    # the residual curve helper agrees with the record
    np.testing.assert_allclose(residual_curve(res), rec["residuals"])


# -----------------------------------------------------------------------------
# shard_map backend: telemetry for every method (slow, 8-device subprocess)
# -----------------------------------------------------------------------------

_SHARD_TELE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.api import SolverOptions, solve
from repro.core.methods import get_method
from repro.core.problems import make_problem
from repro.core.solvers import SOLVERS
from repro.launch.mesh import make_solver_mesh

prob = make_problem((12, 12, 16), "27pt")
mesh = make_solver_mesh(8)
out = {}
for m in sorted(SOLVERS):
    off = solve(prob, method=m, mesh=mesh,
                options=SolverOptions(tol=1e-6, maxiter=600))
    on = solve(prob, method=m, mesh=mesh,
               options=SolverOptions(tol=1e-6, maxiter=600, telemetry=True,
                                     telemetry_buffer=601))
    rows = min(int(on.iters) + 1, np.asarray(on.telemetry).shape[-2])
    mdef = get_method(m)
    tele_res = np.sqrt(np.asarray(on.telemetry)[
        :rows, mdef.scalars.index(mdef.res_scalar)])
    out[m] = dict(
        off_none=off.telemetry is None,
        bitwise=bool(np.array_equal(np.asarray(off.x), np.asarray(on.x))),
        shape=list(np.asarray(on.telemetry).shape),
        n_scalars=len(mdef.scalars),
        curve_ok=bool(np.allclose(tele_res,
                                  np.asarray(on.history)[:rows])),
    )
print(json.dumps(out))
"""


@pytest.mark.slow
def test_telemetry_all_methods_shardmap():
    out = run_multidevice(_SHARD_TELE_SCRIPT)
    assert sorted(out) == sorted(SOLVERS)
    for m, r in out.items():
        assert r["off_none"], m
        assert r["bitwise"], m         # telemetry never perturbs the solve
        assert r["shape"] == [601, r["n_scalars"]], (m, r)
        assert r["curve_ok"], m


# -----------------------------------------------------------------------------
# the span stream: schema, nesting, CLI summarizer round-trip
# -----------------------------------------------------------------------------

def test_span_stream_roundtrip(problem, tracer_path, capsys):
    sess = SolverSession(problem, method="cg",
                         options=SolverOptions(tol=1e-8, maxiter=300))
    sess.solve()
    sess.solve()                       # second call: compile-cache hit
    obs.disable()

    assert obs.validate_stream(tracer_path) == []
    records = obs.read_trace(tracer_path)
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r)
    # lifecycle spans: resolve -> precond.setup -> compile -> solve/execute
    for name in ("resolve", "precond.setup", "compile", "solve", "execute"):
        assert name in by_name, name
    assert len(by_name["solve"]) == 2
    assert len(by_name["compile"]) == 1      # second solve reused the cache
    # nesting: execute's parent is its solve span
    solve_ids = {r["span_id"] for r in by_name["solve"]}
    assert all(r["parent_id"] in solve_ids for r in by_name["execute"])

    from repro.obs.__main__ import main as obs_main
    assert obs_main(["summarize", tracer_path, "--check", "--json"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["schema_errors"] == 0
    assert summary["spans"]["solve"]["count"] == 2
    assert summary["spans"]["execute"]["p50_s"] is not None


def test_summarize_check_fails_on_bad_record(tmp_path):
    path = tmp_path / "bad.jsonl"
    good = obs.make_event("ok")
    bad = {"schema": obs.SCHEMA, "kind": "span", "name": "x"}  # missing keys
    path.write_text(json.dumps(good) + "\n" + json.dumps(bad) + "\n")
    from repro.obs.__main__ import main as obs_main
    assert obs_main(["summarize", str(path), "--check"]) == 1
    assert obs_main(["summarize", str(path)]) == 0   # report-only mode


def test_tracer_disabled_is_noop(tmp_path):
    obs.disable()
    with obs.span("nothing") as sid:
        assert sid is None
    assert obs.event("nothing") is not None      # record built, not written
    assert not obs.active()


# -----------------------------------------------------------------------------
# serve metrics as views over the event stream (+ the unification bugfix)
# -----------------------------------------------------------------------------

def test_serve_metrics_views_and_schema():
    from repro.serve import ServeMetrics
    m = ServeMetrics()
    t0 = time.monotonic()
    m.record_submit(t0, bucket="b0", rid=1)
    m.record_submit(t0 + 0.1, bucket="b1", rid=2)
    m.record_queue_depth(2)
    m.record_completion("b0", 0.5, t0 + 0.5)
    m.record_completion("b1", 1.5, t0 + 1.6)
    m.record_preemption(3)
    m.rejected += 1

    assert m.completed == 2 and m.preemptions == 1 and m.requeued == 3
    for rec in m.events():
        assert obs.validate_record(rec) == [], rec
    snap = m.snapshot(queue_depth=0)
    assert snap["schema"] == obs.SCHEMA
    # the pre-PR-8 key set the bench/CI gate parses, still intact
    for k in ("completed", "preemptions", "requeued", "rejected", "qps",
              "queue_depth_max", "p50_s", "p95_s", "p99_s", "per_bucket"):
        assert k in snap, k
    assert snap["completed"] == 2 and snap["rejected"] == 1
    assert snap["p50_s"] == pytest.approx(1.0)
    assert snap["per_bucket"]["b0"]["served"] == 1
    assert snap["qps"] == pytest.approx(2 / 1.6, rel=1e-6)


def test_serve_metrics_forward_to_tracer(tracer_path):
    from repro.serve import ServeMetrics
    m = ServeMetrics()
    m.record_submit(time.monotonic(), bucket="b0", rid=7)
    m.record_completion("b0", 0.2, time.monotonic())
    obs.disable()
    recs = obs.read_trace(tracer_path)
    assert [r["name"] for r in recs] == ["serve.admit", "serve.complete"]
    assert recs[1]["attrs"]["latency_s"] == pytest.approx(0.2)


def test_heartbeat_reader_accepts_both_schemas(tmp_path):
    from repro.runtime.monitor import scan_hosts, write_host_heartbeat
    d = str(tmp_path)
    # new writer: a repro.obs/v1 metric record
    write_host_heartbeat(d, 0, step=12, step_time=0.5)
    # pre-PR-8 flat shape, as an old monitor directory would hold
    with open(os.path.join(d, "host_1.json"), "w") as f:
        json.dump({"host": 1, "step": 9, "t": time.time(),
                   "step_time": 0.4}, f)
    out = scan_hosts(d)
    assert out["alive"] == [0, 1]
    assert out["min_step"] == 9 and out["max_step"] == 12
    with open(os.path.join(d, "host_0.json")) as f:
        assert obs.validate_record(json.load(f)) == []


def test_scan_metrics_accepts_pre_schema_records(tmp_path):
    from repro.serve import ServeMetrics, scan_metrics
    from repro.serve.metrics import load_record
    d = str(tmp_path)
    ServeMetrics().write(d, name="new")
    old = {"t": 123.0, "completed": 4, "qps": 2.0}    # pre-PR-8, untagged
    with open(os.path.join(d, "metrics_old.json"), "w") as f:
        json.dump(old, f)
    out = scan_metrics(d)
    assert out["new"]["schema"] == obs.SCHEMA
    assert out["old"]["schema"] == f"{obs.SCHEMA}+legacy"
    assert out["old"]["t_wall"] == 123.0 and out["old"]["completed"] == 4
    assert load_record(out["new"]) == out["new"]      # tagged: pass-through


def test_committed_bench_serve_record_still_parses():
    """Regression gate for the record unification: the PR-6-era
    BENCH_serve.json committed at the repo root must still satisfy its own
    check (old snapshot key set intact under the new metrics store)."""
    from benchmarks.bench_serve import check_record
    rec = check_record(os.path.join(REPO_ROOT, "BENCH_serve.json"))
    assert rec["dropped"] == 0


# -----------------------------------------------------------------------------
# benchmark trajectories
# -----------------------------------------------------------------------------

def test_trajectory_rows_append(tmp_path):
    from benchmarks.common import trajectory_append, trajectory_row
    path = str(tmp_path / "hist.jsonl")
    row = trajectory_row("kernels", value=1.0)
    for k in ("bench", "t_wall", "git_sha", "device", "backend", "dtype"):
        assert k in row, k
    trajectory_append(path, row)
    trajectory_append(path, trajectory_row("kernels", value=2.0))
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2                 # appended, not overwritten
    assert [ln["value"] for ln in lines] == [1.0, 2.0]


# -----------------------------------------------------------------------------
# scopes: which compiled op belongs to which part of the solve
# -----------------------------------------------------------------------------

def _fusions(hlo_text: str) -> dict[str, collections.Counter]:
    """``{fusion instruction: Counter of its fused opcodes}``."""
    comps: dict[str, collections.Counter] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = re.match(r"^%([\w.\-]+) .*\{\s*$", line)
        if m:
            comp = comps.setdefault(m.group(1), collections.Counter())
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%[\w.\-]+ = .*? ([a-z\-]+)\(", line)
        if m and comp is not None:
            comp[m.group(1)] += 1
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*kind=k\w+, "
                         r"calls=%([\w.\-]+)", hlo_text, re.M):
        out[m.group(1)] = comps[m.group(2)]
    return out


def test_op_scopes_of_a_local_cg_session():
    sess = SolverSession(make_problem((16, 16, 16), "27pt"), method="cg",
                         options=SolverOptions(tol=1e-8, maxiter=300))
    assert sess.op_scopes() == {}            # nothing compiled yet
    sess.solve()
    scopes = sess.op_scopes()
    (ex,) = sess._executables.values()
    text = ex.as_text()
    module = re.match(r"HloModule ([^\s,]+)", text).group(1)
    assert {m for m, _ in scopes} == {module}
    by_op = {op: sc for (_, op), sc in scopes.items()}
    # the 27-point stencil: one fusion per apply (r0 = b - A x0 in init,
    # q = A p in each step) of the box sum's three 3-point slice sums,
    # placed by most of its fused instructions even where another scope's
    # op is its root (init's subtract)
    stencils = [f for f, ops in _fusions(text).items() if ops["slice"] >= 9]
    assert len(stencils) == 2
    assert all(by_op[f] == "repro.matvec" for f in stencils)
    # the dot products (a multiply and a sum): each a fusion of its own
    dots = [f for f, ops in _fusions(text).items() if ops["reduce"]]
    assert dots and all(by_op[d] == "repro.reduce" for d in dots)
    assert set(scopes.values()) == {"repro.loop", "repro.init", "repro.step",
                                    "repro.matvec", "repro.reduce"}
    whiles = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .* while\(", text,
                        re.M)
    assert whiles and all(by_op[w] == "repro.loop" for w in whiles)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/repro.loop/while/body/repro.step/repro.matvec/add",
     "repro.matvec"),
    ("jit(fn)/shard_map/repro.loop/while/body/repro.step/repro.matvec/"
     "repro.halo/ppermute", "repro.halo"),
    ("jit(run)/repro.loop/while/cond/lt", "repro.loop"),
    ("jit(run)/sqrt", None),
    ("jit(run)/repro.loop/repro.unknown/add", "repro.loop"),
])
def test_scope_of_takes_the_innermost_repro_scope(op_name, scope):
    from repro.obs.scopes import scope_of
    assert scope_of(op_name) == scope


def test_op_scopes_keys_ops_by_module():
    from repro.obs.scopes import op_scopes_from_text
    text = "\n".join([
        "HloModule jit_a, entry_computation_layout={()->f32[]}",
        "ENTRY %main.1 () -> f32[] {",
        '  ROOT %add.1 = f32[] add(%x, %y), metadata={op_name="jit(a)/'
        'repro.loop/while/body/repro.step/add"}',
        "}",
        "HloModule jit_b, entry_computation_layout={()->f32[]}",
        "ENTRY %main.1 () -> f32[] {",
        '  ROOT %add.1 = f32[] add(%x, %y), metadata={op_name="jit(b)/'
        'repro.reduce/add"}',
        "}",
    ])
    assert op_scopes_from_text(text) == {("jit_a", "add.1"): "repro.step",
                                         ("jit_b", "add.1"): "repro.reduce"}


_SCOPES_SCRIPT = r"""
import json, re
import jax
jax.config.update("jax_enable_x64", True)
from jax.sharding import Mesh
import numpy as np
from repro.api import SolverOptions, SolverSession
from repro.analysis.hlo import parse_computations
from repro.core.problems import make_problem

ARITH = ("add", "subtract", "multiply")
mesh = Mesh(np.array(jax.devices()[:4]), ("cells",))
out = {}
for method in ("cg", "cg_merged"):
    for mode in ("concat", "scatter", "overlap"):
        sess = SolverSession(make_problem((8, 8, 16), "27pt"), method=method,
                             options=SolverOptions(tol=1e-8, maxiter=200,
                                                   layout="1d",
                                                   halo_mode=mode),
                             mesh=mesh)
        res = sess.solve()
        (ex,) = sess._executables.values()
        text = ex.as_text()
        scopes = {op: sc for (_, op), sc in sess.op_scopes().items()}
        kinds = {}
        for m in re.finditer(
                r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*? "
                r"(collective-permute|all-reduce)(?:-start)?\(", text, re.M):
            kinds.setdefault(m.group(2), []).append(scopes.get(m.group(1)))
        comps = {c.name: c for c in parse_computations(text)}

        def arith(ins):
            if ins.opcode == "fusion":
                called = re.search(r"calls=%([\w.\-]+)", ins.raw).group(1)
                return any(i.opcode in ARITH
                           for i in comps[called].instructions)
            return ins.opcode in ARITH

        halo_arith, matvec_arith = [], 0
        for comp in comps.values():
            for ins in comp.instructions:
                if scopes.get(ins.name) == "repro.halo" and arith(ins):
                    halo_arith.append(ins.name)
                elif scopes.get(ins.name) == "repro.matvec" and arith(ins):
                    matvec_arith += 1
        out[f"{method}/{mode}"] = {"kinds": kinds, "iters": int(res.iters),
                                   "halo_arith": halo_arith,
                                   "matvec_arith": matvec_arith}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_scopes():
    return run_multidevice(_SCOPES_SCRIPT, devices=4)


@pytest.mark.parametrize("case", [f"{m}/{h}" for m in ("cg", "cg_merged")
                                  for h in ("concat", "scatter", "overlap")])
def test_sharded_collectives_land_in_halo_and_reduce(sharded_scopes, case):
    """Every halo permute in ``repro.halo``, every all-reduce in
    ``repro.reduce``, and the stencil arithmetic of the interior and the
    shell slabs in ``repro.matvec``, none of it in ``repro.halo``."""
    got = sharded_scopes[case]
    assert got["iters"] > 0
    permutes = got["kinds"]["collective-permute"]
    reduces = got["kinds"]["all-reduce"]
    assert permutes and set(permutes) == {"repro.halo"}
    assert reduces and set(reduces) == {"repro.reduce"}
    assert got["halo_arith"] == []
    assert got["matvec_arith"] > 0


def test_iteration_breakdown_is_iteration_time():
    from benchmarks.scaling_model import iteration_breakdown, iteration_time
    bd = iteration_breakdown("cg", 27, (16, 16, 64), 8)
    assert bd["total"] == pytest.approx(
        bd["t_mem"] + bd["t_halo"] + bd["t_precond"] + bd["t_reduce"])
    assert iteration_time("cg", 27, (16, 16, 64), 8) == bd["total"]
