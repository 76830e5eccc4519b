"""Device time by the program's own scopes, and the device's idle time
inside the program's solve spans.

The program names the parts of its solve loop with ``jax.named_scope``
(``repro.obs.scopes``: ``repro.matvec``, ``repro.halo``, ``repro.reduce``,
``repro.step``, ...) and opens a profiler annotation for each of its host
spans (``repro.solve`` holding ``repro.inputs`` and ``repro.execute``).
A metric reader is handed the window's summary, not its trace, so
:func:`measure` makes :data:`SOLVES` more solves of the run's
configuration on the run's chips after the window, each under the
benchmark's own ``bench.solve`` annotation as in the window, traces them,
and reduces that trace with the session's scope map
(``SolverSession.op_scopes()``).  The readers of one run share one
measurement.  A program without the scope map gives ``None``.
"""

from __future__ import annotations

import dataclasses
import re
import shutil
import tempfile

from bench import xplane

#: solves traced after the window, each as the window makes it
SOLVES = 3
#: the right-hand side: b = A x*, x* drawn as the seq-rhs4 mix draws it
MIX = {"rhs_pool": 1, "x_star": {"kind": "uniform", "low": 0.5, "high": 1.5}}
SEED = 1
#: the program's solve span, as the profiler sees it
PROGRAM_SOLVE = "repro.solve"
#: device time outside every repro.* scope
UNSCOPED = "unscoped"

_MODULE_RUN = re.compile(r"\(.*\)$")


@dataclasses.dataclass
class ScopeTrace:
    """Device operations per chip as ``(module, op, start_ns, end_ns)``,
    and the host spans of the thread that made the solves."""

    ops: dict[int, list[tuple[str | None, str, float, float]]]
    host: list[tuple[str, float, float]]


@dataclasses.dataclass
class ScopeTimes:
    """What :func:`measure` read; times are averaged over chips."""

    solves: int
    iters: int
    scope_busy_s: dict[str, float]  # self time inside the solve spans, by
    #                                 innermost repro.* scope or UNSCOPED
    host_gap_s: float               # device idle inside the repro.solve spans


def load(path: str, devices: list[int] | None = None, *,
         cpu_ops: bool = False) -> ScopeTrace:
    """Read a trace as :func:`bench.xplane.load` does, keeping each
    operation's HLO module: the ``XLA Modules`` line's run that holds it
    on a TPU, its ``hlo_module`` stat on the CPU (``cpu_ops``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = xplane._TPU_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            lines = {line.name: line for line in plane.lines}
            runs = sorted((e.start_ns, e.end_ns,
                           _MODULE_RUN.sub("", e.name))
                          for e in getattr(lines.get("XLA Modules"),
                                           "events", ()))
            evs = ops.setdefault(dev, [])
            i = 0
            for e in sorted(getattr(lines.get("XLA Ops"), "events", ()),
                            key=lambda e: e.start_ns):
                while i < len(runs) and runs[i][1] <= e.start_ns:
                    i += 1
                module = (runs[i][2] if i < len(runs)
                          and runs[i][0] <= e.start_ns else None)
                evs.append((module, xplane.short_name(e.name), e.start_ns,
                            e.end_ns))
            continue
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name.startswith(xplane.ANNOTATION_PREFIX)
                   for e in events):
                host.extend((e.name, e.start_ns, e.end_ns) for e in events)
            if cpu_ops and line.name.startswith("tf_XLA"):
                ops.setdefault(0, []).extend(
                    (dict(e.stats).get("hlo_module"), e.name, e.start_ns,
                     e.end_ns)
                    for e in events if e.end_ns > e.start_ns
                    and not e.name.startswith((*xplane._CPU_NON_OPS,
                                               "end: ")))
    return ScopeTrace(ops={d: sorted(v, key=lambda e: e[2])
                           for d, v in ops.items()},
                      host=sorted(host, key=lambda e: e[1]))


def self_times(evs, lo: float, hi: float) -> dict[tuple, float]:
    """:func:`bench.xplane.self_times`, keyed by ``(module, op)``."""
    out: dict[tuple, float] = {}
    stack: list[tuple[tuple, float]] = []
    for module, op, s, e in sorted(evs, key=lambda ev: (ev[2], -ev[3])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        d = max(0.0, min(e, hi) - max(s, lo))
        key = (module, op)
        out[key] = out.get(key, 0.0) + d
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - d
        stack.append((key, e))
    return out


def summarize(trace: ScopeTrace, solve_spans, program_spans,
              scope_map: dict[tuple[str, str], str]
              ) -> tuple[dict[str, float], float]:
    """``(scope_busy_s, host_gap_s)``: each chip's self time inside
    ``solve_spans`` by the scope ``scope_map`` gives its ``(module, op)``,
    and the device's idle time inside ``program_spans``, both averaged
    over chips, in seconds."""
    n = max(1, len(trace.ops))
    busy: dict[str, float] = {}
    idle = 0.0
    for evs in trace.ops.values():
        for lo, hi in solve_spans:
            for (module, op), d in self_times(evs, lo, hi).items():
                scope = scope_map.get((module, op), UNSCOPED)
                busy[scope] = busy.get(scope, 0.0) + d
        merged = xplane.union((s, e) for _, _, s, e in evs)
        for lo, hi in program_spans:
            idle += sum(e - s for s, e in xplane.gaps(merged, lo, hi))
    return ({k: v / n * 1e-9 for k, v in sorted(busy.items())},
            idle / n * 1e-9)


_last: tuple[object, ScopeTimes | None] | None = None


def measure(run) -> ScopeTimes | None:
    """The scope times of a traced run (``run.trace`` set), measured once
    per run; ``None`` for an untraced run or a program without scopes."""
    global _last
    if _last is None or _last[0] is not run:
        _last = (run, _measure(run) if run.trace is not None else None)
    return _last[1]


def _measure(run) -> ScopeTimes | None:
    import jax
    from repro.api import SolverSession

    from bench import generator, harness

    if not hasattr(SolverSession, "op_scopes"):
        return None
    devices = jax.devices()[:run.chips]
    sess = harness.build_session(run.config, devices)
    (b,) = generator.make_rhs(MIX, SEED, sess.problem.shape,
                              sess.problem.dtype, sess.backend.sharding())
    jax.block_until_ready(sess.solve(b))
    scope_map = sess.op_scopes()
    if not scope_map:
        return None
    log_dir = tempfile.mkdtemp(prefix="bench-scopes-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    iters = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for _ in range(SOLVES):
            with jax.profiler.TraceAnnotation(harness.SOLVE_SPAN):
                iters += int(jax.block_until_ready(sess.solve(b)).iters)
    finally:
        jax.profiler.stop_trace()
    try:
        tr = load(xplane.find_xplane(log_dir), [d.id for d in devices],
                  cpu_ops=devices[0].platform == "cpu")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    busy, idle = summarize(tr, xplane.spans(tr.host, harness.SOLVE_SPAN),
                           xplane.spans(tr.host, PROGRAM_SOLVE), scope_map)
    return ScopeTimes(solves=SOLVES, iters=iters, scope_busy_s=busy,
                      host_gap_s=idle)
