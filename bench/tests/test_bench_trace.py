"""The reduction from a profiler trace to busy time, idle gaps, collective
time and per-annotation device time: on hand-made intervals, and on a
small trace recorded on the CPU."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import xplane


def test_union_covered_and_gaps():
    merged = xplane.union([(5, 8), (0, 2), (1, 3), (8, 9), (12, 15)])
    assert merged == [(0, 3), (5, 9), (12, 15)]
    assert xplane.covered(merged, 0, 15) == 10
    assert xplane.covered(merged, 2, 13) == 1 + 4 + 1
    assert xplane.gaps(merged, 0, 15) == [(3, 5), (9, 12)]
    assert xplane.gaps(merged, -1, 20) == [(-1, 0), (3, 5), (9, 12),
                                           (15, 20)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


@pytest.mark.parametrize("name,coll", [
    ("%all-reduce.3 = f32[] all-reduce(f32[] %x)", True),
    ("all-reduce-start.1", True),
    ("%collective-permute-done.2 = f32[1,512,512] done(...)", True),
    ("collective-permute.7", True),
    ("all-gather.1", True),
    ("%multiply_reduce_fusion.6 = (f32[], f32[8]) fusion(...)", False),
    ("%while.4 = (f32[8]) while(...)", False),
    ("copy.3", False),
])
def test_collective_classification(name, coll):
    assert xplane.is_collective(name) is coll


def test_short_names():
    assert xplane.short_name("%fusion.3 = f32[2] fusion(%a)") == "fusion.3"
    assert xplane.short_name("copy.1") == "copy.1"


def test_self_times_subtract_nested_operations():
    evs = [("%while.1 = (f32[]) while()", 0, 100),
           ("%fusion.1 = f32[] fusion()", 10, 40),
           ("fusion.2", 50, 90),
           ("copy.1", 100, 110)]
    st = xplane.self_times(evs, 0, 200)
    assert st == {"while.1": 30, "fusion.1": 30, "fusion.2": 40, "copy.1": 10}


def test_summarize_hand_made_trace():
    # two chips; two solves in a window [0, 100]
    ops = {
        0: [("fusion.1", 10, 30), ("all-reduce.1", 30, 35),
            ("fusion.2", 60, 80)],
        1: [("fusion.1", 12, 30), ("collective-permute-done.1", 30, 40),
            ("fusion.2", 60, 80)],
    }
    host = [("bench.window", 0, 100), ("bench.solve", 5, 45),
            ("bench.solve", 55, 95), ("PjitFunction(run)", 45, 58)]
    tr = xplane.Trace(ops=ops, host=host)
    s = xplane.summarize(tr, (0, 100), xplane.spans(host, "bench.solve"))
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((45 + 48) / 2 * 1e-9)
    assert s.solve_busy_s == pytest.approx(s.busy_s)
    assert s.collective_s == pytest.approx((5 + 10) / 2 * 1e-9)
    assert s.top_ops[:2] == [["fusion.2", pytest.approx(20e-9)],
                             ["fusion.1", pytest.approx(19e-9)]]
    # chip 0's gaps, longest first, labelled by what the host was doing
    assert s.idle_gaps == [
        ["bench.window > PjitFunction(run)", pytest.approx(25e-9)],
        ["bench.solve", pytest.approx(20e-9)],
        ["bench.solve", pytest.approx(10e-9)]]


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready(f(x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.solve"):
                jax.block_until_ready(f(x))
            time.sleep(0.05)
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(str(tmp_path)), cpu_ops=True)
    (window,) = xplane.spans(tr.host, "bench.window")
    solves = xplane.spans(tr.host, "bench.solve")
    assert len(solves) == 3
    assert all(window[0] <= s <= e <= window[1] for s, e in solves)
    s = xplane.summarize(tr, window, solves)
    assert s.n_devices == 1
    assert 0 < s.solve_busy_s <= s.busy_s < s.window_s
    assert s.solve_busy_s <= sum(e - s_ for s_, e in solves) * 1e-9
    assert s.collective_s == 0
    # the host slept 50 ms between solves: gaps the device could not fill
    assert s.idle_gaps and s.idle_gaps[0][1] >= 0.04
    assert s.idle_gaps[0][0].startswith("bench.window")
    assert any(name.startswith(("dot", "fusion")) or "dot" in name
               for name, _ in s.top_ops)
