"""A whole run at a tiny size on the CPU, without the look for a chip: sound
runs come out correct, and runs with the timed path broken underneath, or
with the lower-precision control in the program's place, do not."""

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ["cg27-f32-512", "cg27-f64-128"]
SEED = 2 ** 31 + 12345


@pytest.fixture(autouse=True)
def keep_x64():
    was = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", was)


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def tiny(spec, cell):
    _, config, _ = harness.load_cell(spec, cell, ROOT)
    return dict(config, block_per_chip=[16, 16, 16])


def run(spec, cell, trace=False, seconds=0.3):
    return harness.run_cell(spec, cell, SEED, seconds, trace,
                            t_proc0=time.perf_counter(), root=ROOT,
                            require_tpu=False, config=tiny(spec, cell))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(spec, cell):
    r = run(spec, cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metrics_for(spec, cell, False)}
    assert r["metrics"]["solve_s"]["value"] > 0
    assert r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["unconverged_solves"]["value"] == 0
    assert r["checks"]["true_rel_residual"]["value"] <= \
        r["checks"]["true_rel_residual"]["limit"]


def test_traced_run_reads_the_per_layer_metrics(spec):
    r = run(spec, "cg27-f32-512", trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert {"compile_s", "iters", "iter_ms", "idle_share"} <= set(m)
    # no peaks for a CPU: the roofline reader finds nothing, and says so
    assert "hbm_roofline" not in m and "collective_share" not in m
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and len(
        r["breakdown"]["device_ops"]) <= 10


def _step_unchanged(monkeypatch):
    import repro.core.solvers as solvers
    real = solvers.run_method

    def broken(mdef, *a, **kw):
        return real(dataclasses.replace(mdef, step=lambda ops, st: st),
                    *a, **kw)

    monkeypatch.setattr(solvers, "run_method", broken)


def _answer_altered(monkeypatch):
    from repro.api.session import SolverSession
    real = SolverSession.solve

    def broken(self, b=None, x0=None):
        res = real(self, b, x0)
        return res._replace(x=res.x.at[3, 4, 5].add(1.0))

    monkeypatch.setattr(SolverSession, "solve", broken)


def _control_in_place(monkeypatch):
    from repro.api.session import SolverSession
    real = SolverSession.solve

    def lower(self, b=None, x0=None):
        res = real(self, b, x0)
        low = jnp.dtype(control.LOWER[jnp.dtype(b.dtype).name])
        x, _ = reference.cg(b.astype(low), self.options.tol,
                            self.options.maxiter)
        return res._replace(x=x.astype(b.dtype))

    monkeypatch.setattr(SolverSession, "solve", lower)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_step_unchanged, _answer_altered,
                                   _control_in_place])
def test_broken_timed_path_is_not_correct(spec, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = run(spec, cell)
    assert r["correct"] is False and r["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_readings_fail_the_limit(spec, cell):
    r = control.control_readings(spec, cell, SEED, require_tpu=False,
                                 config=tiny(spec, cell))
    assert r["fails"] is True
    assert min(r["true_rel_residuals"]) > 3 * r["limit"]


def _bench_cmd(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cg27-f32-512",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_exits_nonzero_without_a_result():
    proc = _bench_cmd(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_a_directory_with_only_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = _bench_cmd(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())
    json.loads((tmp_path / "BENCHMARK.json").read_text())
