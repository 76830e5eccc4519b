"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the problem, method, precision, block
  per chip, decomposition and the check's limit;
* ``bench/traffic/<mix>.json``: the parameters ``bench/generator.py`` reads;
* ``bench/metrics/<metric>.py``: a ``read(run)`` that returns the metric's
  value from a :class:`Run`, or ``None`` where it finds nothing to read.

The window drives ``SolverSession.solve(b)``, the program's own entry,
back to back for ``seconds``, each call ending in ``block_until_ready``.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import pathlib
import random
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / "bench" / ".cache" / "jax"

#: annotation names in the trace (bench/xplane.py reads them back)
WINDOW_SPAN = "bench.window"
SOLVE_SPAN = "bench.solve"


class NoChip(RuntimeError):
    """The devices JAX finds cannot run this cell."""


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


# -- finding a cell's files ---------------------------------------------------

def load_spec(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(spec: dict, name: str, root: pathlib.Path = ROOT
              ) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of the workload ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_for(spec: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports: end-to-end ones in a plain run,
    per-layer ones in a traced run."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(name: str, root: pathlib.Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- devices, compile cache, problem ------------------------------------------

def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cell_devices(chips: int, require_tpu: bool = True) -> list:
    """The first ``chips`` devices; raises :class:`NoChip` unless they are
    TPUs of a kind ``bench/peaks.py`` knows."""
    import jax
    from bench.peaks import PEAKS

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
        if devs[0].device_kind not in PEAKS:
            raise NoChip(f"no peaks for device kind {devs[0].device_kind!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def global_grid(config: dict, chips: int) -> tuple[int, int, int]:
    """The whole grid: one block per chip, stacked along the split dim (the
    paper's 1-D z decomposition, ``"split": "z"``, is the one supported)."""
    if config["split"] != "z":
        raise ValueError(f"unknown split {config['split']!r}")
    bx, by, bz = config["block_per_chip"]
    return (bx, by, bz * chips)


def build_session(config: dict, devices: list):
    """The program's solver session for this configuration, on exactly
    ``devices``: one chip runs the local path, several the configuration's
    decomposition over a mesh of those chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.api import SolverOptions, SolverSession

    f64 = config["precision"] == "float64"
    grid = global_grid(config, len(devices))
    mesh = None
    if len(devices) > 1:
        # the mesh layout="auto" builds over all of a host's chips
        mesh = Mesh(np.array(devices), ("cells",),
                    axis_types=(AxisType.Auto,))
    opts = SolverOptions(
        tol=config["tol"], maxiter=config["maxiter"], f64=f64,
        norm_ref=config["norm_ref"], pallas=config["pallas"],
        layout="local" if mesh is None else "auto")
    return SolverSession(method=config["method"], grid=grid,
                         stencil=config["operator"], options=opts, mesh=mesh)


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers here."""

    config: dict
    chips: int
    points_per_chip: int
    peaks: dict | None
    setup_s: float              # process start to window start, less the
                                # TPU runtime's start
    compile_s: float
    durations: list[float]      # seconds of each solve of the window
    window_s: float             # first solve's start to last one's end
    iters: list[int]            # iterations of each solve
    trace: object | None = None  # bench.xplane.Summary of a traced run


class _CompileCounter:
    """Counts compiles and persistent-cache loads while it is armed."""

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, *args, **kwargs):
        if self.armed and event.endswith("backend_compile_duration"):
            self.count += 1

    def _event(self, event, *args, **kwargs):
        if self.armed and event.endswith("/compilation_cache/cache_hits"):
            self.count += 1

    def close(self):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


def _x64(on: bool) -> bool:
    import jax
    was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", on)
    return was


def _window(sess, pool, seconds: float, sample: int, seed: int):
    """Solve back to back for ``seconds``, cycling through ``pool``.

    Returns each solve's (start, end, iters, status) and a
    sample of ``sample`` solutions, drawn from the seed (reservoir
    sampling over the solves as they finish), as ``(solve index, rhs
    index, x, the solver's own final residual norm)``.
    """
    import jax

    rng = random.Random(seed)
    records, kept = [], []
    deadline = time.perf_counter() + seconds
    i = 0
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            k = i % len(pool)
            with jax.profiler.TraceAnnotation(SOLVE_SPAN):
                t0 = time.perf_counter()
                res = jax.block_until_ready(sess.solve(pool[k]))
                t1 = time.perf_counter()
            records.append((t0, t1, res.iters, res.status))
            if len(kept) < sample:
                kept.append((i, k, res.x, res.res_norm))
            else:
                j = rng.randrange(i + 1)
                if j < sample:
                    kept[j] = (i, k, res.x, res.res_norm)
            del res
            i += 1
            if t1 >= deadline:
                break
    return records, kept


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_proc0: float, root: pathlib.Path = ROOT,
             require_tpu: bool = True, config: dict | None = None,
             traffic: dict | None = None) -> dict:
    """One run; returns the result line as a dict.  ``config`` and
    ``traffic`` replace the cell's files (tests run tiny sizes so)."""
    import jax
    import numpy as np

    from bench import generator, reference, xplane
    from bench.peaks import PEAKS

    cell, cfg, mix = load_cell(spec, cell_name, root)
    config = config or cfg
    traffic = traffic or mix
    chips = int(cell["chips"])
    phases = {"imports": time.perf_counter() - t_proc0}
    # the TPU runtime's start: before anything of the program is imported,
    # so that no work of the program can move into it; it is left out of
    # setup_s (it is the environment's, 6 to 13 s and unsteady on a v5e)
    t = time.perf_counter()
    devices = cell_devices(chips, require_tpu)
    kind = devices[0].device_kind
    phases["runtime_start"] = time.perf_counter() - t

    t = time.perf_counter()
    from repro.core.methods import STATUS_CONVERGED
    f64 = config["precision"] == "float64"
    _x64(f64)
    phases["program_import"] = time.perf_counter() - t
    counter = _CompileCounter()
    t = time.perf_counter()
    sess = build_session(config, devices)
    dtype = sess.problem.dtype
    phases["session"] = time.perf_counter() - t
    t = time.perf_counter()
    pool = generator.make_rhs(traffic, seed, sess.problem.shape, dtype,
                              sess.backend.sharding())
    phases["rhs"] = time.perf_counter() - t
    t = time.perf_counter()
    # warm-up: the window's own call, once per right-hand side shape (one)
    jax.block_until_ready(sess.solve(pool[0]))
    phases["warmup_solve"] = time.perf_counter() - t
    compile_s = sum(v["compile_s"] for v in sess.cache_stats().values())

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    counter.armed = True
    t_window = time.perf_counter()
    records, kept = _window(sess, pool, seconds,
                            int(traffic["check_sample"]), seed)
    counter.armed = False
    counter.close()
    if trace:
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    durations = [t1 - t0 for t0, t1, _, _ in records]
    window_s = records[-1][1] - records[0][0]
    iters = [int(r[2]) for r in records]
    statuses = [int(r[3]) for r in records]
    del records
    keep_rhs = {k for _, k, _, _ in kept}
    pool = {k: b for k, b in enumerate(pool) if k in keep_rhs}
    del sess

    summary = None
    if trace:
        tr = xplane.load(xplane.find_xplane(log_dir),
                         devices=[d.id for d in devices],
                         cpu_ops=not require_tpu)
        shutil.rmtree(log_dir, ignore_errors=True)
        (window,) = xplane.spans(tr.host, WINDOW_SPAN) or [(0.0, 0.0)]
        summary = xplane.summarize(tr, window, xplane.spans(tr.host,
                                                            SOLVE_SPAN))

    # the check: every solve converged; the sampled solutions' true
    # residuals, by the plain reference in float64 (the solver's own final
    # residual beside each, for the gap between the two)
    t_check = time.perf_counter()
    was = _x64(True)
    try:
        norms = [reference.residual_norms(x, pool[k]) for _, k, x, _ in kept]
    finally:
        _x64(was)
    residuals = [r / bn for r, bn in norms]
    recurrence = [float(rn) / bn for (_, _, _, rn), (_, bn) in zip(kept, norms)]
    check_s = time.perf_counter() - t_check
    limit = float(config["check"]["residual_limit"])
    worst = (max(residuals) if residuals and all(map(math.isfinite, residuals))
             else None)
    bad = {i for i, s in enumerate(statuses) if s != STATUS_CONVERGED}
    bad |= {i for (i, _, _, _), r in zip(kept, residuals) if not r <= limit}
    unconverged = sum(s != STATUS_CONVERGED for s in statuses)
    correct = worst is not None and worst <= limit and unconverged == 0

    run = Run(config=config, chips=chips,
              points_per_chip=int(np.prod(config["block_per_chip"])),
              peaks=PEAKS.get(kind),
              setup_s=t_window - t_proc0 - phases["runtime_start"],
              compile_s=compile_s, durations=durations, window_s=window_s,
              iters=iters, trace=summary)
    metrics = {}
    for m in metrics_for(spec, cell_name, trace):
        value = load_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(statuses),
              "failed": len(bad), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
        log(f"trace: {summary.n_devices} devices, window {summary.window_s} s,"
            f" busy {summary.busy_s} s, in solves {summary.solve_busy_s} s,"
            f" collectives {summary.collective_s} s")
    result["setup_phases_s"] = phases
    result["window_compiles"] = counter.count
    result["check_s"] = check_s
    result["solves"] = {"iters": dict(sorted(collections.Counter(iters).items())),
                        "durations_s": durations,
                        "rhs_checked": [k for _, k, _, _ in kept],
                        "true_rel_residuals": [r if math.isfinite(r) else None
                                               for r in residuals],
                        "recurrence_rel_residuals": [
                            r if math.isfinite(r) else None
                            for r in recurrence]}
    result["checks"] = {
        "true_rel_residual": {"value": worst, "limit": limit},
        "unconverged_solves": {"value": unconverged, "limit": 0},
    }
    return result
