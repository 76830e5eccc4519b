"""Device time by the program's scopes and the device's idle time inside
the program's solve spans: the reduction on hand-made traces, the program's
spans on a trace recorded on the CPU, and the four readers."""

import jax
import pytest

from bench import harness, scopes, xplane
from bench.tests.test_bench_run import CELLS, run, tiny

READERS = ["matvec_ms", "reduce_ms", "update_ms", "host_gap_ms"]


@pytest.fixture(autouse=True)
def keep_x64():
    was = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", was)

SCOPE_MAP = {("jit_run", "while.1"): "repro.loop",
             ("jit_run", "fusion.1"): "repro.matvec",
             ("jit_run", "fusion.2"): "repro.step",
             ("jit_run", "all-reduce.1"): "repro.reduce",
             ("jit_other", "fusion.1"): "repro.init"}


def _trace(module):
    """Two chips, two solves; chip 1 runs 2 ns of each op less.  The
    ``module`` of every op is ``module`` (``None``: the trace names none)."""
    def ops(cut):
        return [(module, "while.1", 10, 90),
                (module, "fusion.1", 12, 40 - cut),
                (module, "all-reduce.1", 40, 45),
                (module, "fusion.2", 50, 80 - cut),
                (module, "copy.1", 90, 95),
                (module, "fusion.2", 120, 150)]
    host = [("bench.solve", 0, 100), ("repro.solve", 2, 14),
            ("repro.inputs", 2, 8), ("repro.execute", 8, 14),
            ("bench.solve", 100, 160), ("repro.solve", 100, 125)]
    return scopes.ScopeTrace(ops={0: ops(0), 1: ops(2)}, host=host)


@pytest.mark.parametrize("module", ["jit_run", None])
def test_summarize_hand_made_trace_with_a_scope_map(module):
    tr = _trace(module)
    solves = xplane.spans(tr.host, "bench.solve")
    busy, idle = scopes.summarize(tr, solves,
                                  xplane.spans(tr.host, "repro.solve"),
                                  SCOPE_MAP)
    # every busy ns inside the solves lands in one scope or unscoped
    assert sum(busy.values()) == pytest.approx((85 + 30) * 1e-9)
    if module is None:        # an op is matched by its module and name
        assert set(busy) == {"unscoped"}
    else:
        # the while's self time is its own 80 ns less the ops nested in
        # it; copy.1 is in no scope; fusion.1 of another module is init
        assert busy == pytest.approx({
            "repro.matvec": (28 - 1) * 1e-9,   # chip 0: 28 ns, chip 1: 26
            "repro.reduce": 5e-9,
            "repro.step": (30 - 1 + 30) * 1e-9,
            "repro.loop": (80 - 27 - 5 - 29) * 1e-9,
            "unscoped": 5e-9})
    # idle inside repro.solve: [2, 10] in the first, [100, 120] in the
    # second on both chips
    assert idle == pytest.approx((8 + 20) * 1e-9)


def test_program_spans_nest_in_bench_solve_on_a_cpu_trace(tmp_path, spec):
    from repro.obs import trace as obs
    obs.disable()                       # the JSON-lines sink is off
    config = tiny(spec, "cg27-f32-512")
    sess = harness.build_session(config, jax.devices()[:1])
    b = sess.problem.b()
    jax.block_until_ready(sess.solve(b))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation(harness.SOLVE_SPAN):
            jax.block_until_ready(sess.solve(b))
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(str(tmp_path)), cpu_ops=True)

    def inside(name, outer):
        return [(s, e) for s, e in xplane.spans(tr.host, name)
                if any(lo <= s <= e <= hi for lo, hi in outer)]

    bench = xplane.spans(tr.host, harness.SOLVE_SPAN)
    solve = inside("repro.solve", bench)
    assert len(bench) == len(solve) == 2
    for name in ("repro.inputs", "repro.execute"):
        assert len(inside(name, solve)) == 2, name


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def _run(trace):
    return harness.Run(config={}, chips=1, points_per_chip=0, peaks=None,
                       setup_s=0.0, compile_s=0.0, durations=[1.0],
                       window_s=1.0, iters=[1], trace=trace)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_a_trace(name):
    assert harness.load_reader(name)(_run(None)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_a_scope_map(name, spec, monkeypatch):
    from repro.api import SolverSession
    monkeypatch.setattr(SolverSession, "op_scopes", lambda self: {})
    run = _run(object())
    run.config = tiny(spec, "cg27-f32-512")
    assert harness.load_reader(name)(run) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_a_program_without_scopes(name, monkeypatch):
    from repro.api import SolverSession
    monkeypatch.delattr(SolverSession, "op_scopes")
    assert harness.load_reader(name)(_run(object())) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_scope_metrics(spec, cell):
    r = run(spec, cell, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert set(READERS) <= set(m)
    assert all(m[k]["value"] >= 0 and m[k]["unit"] == "ms" for k in READERS)
    assert m["matvec_ms"]["value"] > 0 and m["update_ms"]["value"] > 0
    t = scopes._last[1]
    assert t.solves == scopes.SOLVES and t.iters > 0
    assert set(t.scope_busy_s) <= {"repro.loop", "repro.init", "repro.step",
                                   "repro.matvec", "repro.reduce",
                                   scopes.UNSCOPED}
    # the measurement is made once per run and shared by the readers
    assert scopes.measure(scopes._last[0]) is t
