"""From a profiler trace to device busy time, idle gaps and collective time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things: the device operations of each chip (the ``XLA Ops`` line of
each ``/device:TPU:<id>`` plane) and the host spans of the thread that
holds the benchmark's own annotations.  Everything else here is plain
interval arithmetic on those lists, so it can be checked on any trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: prefix of the benchmark's own host annotations
ANNOTATION_PREFIX = "bench."

#: HLO opcodes that move data between chips
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv|ragged-all-to-all)")

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")

#: host-side events on the XLA CPU threads that are not operations
_CPU_NON_OPS = ("ThreadpoolListener", "ThunkExecutor")


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark thread's host spans, as
    ``(name, start_ns, end_ns)`` tuples."""

    ops: dict[int, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, devices: list[int] | None = None, *,
         cpu_ops: bool = False) -> Trace:
    """Read a trace.  ``devices`` keeps only those TPU ids.  ``cpu_ops``
    takes the operations that XLA's CPU backend ran on its own threads as
    device 0's; it exists for checking this module on a trace recorded on
    the CPU, and a TPU run never sets it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
            continue
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if any(n.startswith(ANNOTATION_PREFIX) for n, _, _ in events):
                host.extend(events)
            if cpu_ops and line.name.startswith("tf_XLA"):
                ops.setdefault(0, []).extend(
                    ev for ev in events
                    if ev[2] > ev[1] and not ev[0].startswith(_CPU_NON_OPS))
    return Trace(ops={d: sorted(v, key=lambda e: e[1])
                      for d, v in ops.items()},
                 host=sorted(host, key=lambda e: e[1]))


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the disjoint ``merged`` intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def short_name(op: str) -> str:
    """``fusion.3`` from the TPU's ``%fusion.3 = f32[...] fusion(...)``."""
    return op.split(" = ", 1)[0].lstrip("%")


def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.match(short_name(op_name)))


def self_times(evs, lo: float, hi: float) -> dict[str, float]:
    """Each operation's time inside ``[lo, hi]`` less that of the
    operations nested in it (a ``while`` holds its body's fusions), summed
    by short name."""
    out: dict[str, float] = {}
    stack: list[tuple[str, float]] = []   # (name, end) of open operations
    for name, s, e in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        d = max(0.0, min(e, hi) - max(s, lo))
        name = short_name(name)
        out[name] = out.get(name, 0.0) + d
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - d
        stack.append((name, e))
    return out


def host_label(host, t: float) -> str:
    """What the host was doing at ``t``: the innermost benchmark annotation
    open then, and the innermost other host span inside it."""
    open_ = [(e - s, n) for n, s, e in host if s <= t < e]
    bench = sorted(x for x in open_ if x[1].startswith(ANNOTATION_PREFIX))
    other = sorted(x for x in open_ if not x[1].startswith(ANNOTATION_PREFIX))
    label = bench[0][1] if bench else "outside"
    if other:
        label += " > " + other[0][1]
    return label


def spans(host, name: str) -> list[tuple[float, float]]:
    """Start and end of every host span called ``name``."""
    return [(s, e) for n, s, e in host if n == name]


@dataclasses.dataclass
class Summary:
    """Device time of one traced window, each time averaged over chips."""

    n_devices: int
    window_s: float       # length of the window
    busy_s: float         # union of device operations inside the window
    #                       (a while loop counts as running from its start
    #                       to its end, its own control flow included)
    solve_busy_s: float   # ... inside the per-solve annotations
    collective_s: float   # union of collective operations inside them
    top_ops: list         # [[op name, self seconds], ...] longest first
    idle_gaps: list       # [[host label, seconds], ...] longest first


def summarize(trace: Trace, window: tuple[float, float],
              solve_spans: list[tuple[float, float]], top: int = 10
              ) -> Summary:
    """Reduce ``trace`` over ``window`` (ns) and the per-solve spans."""
    lo, hi = window
    n = max(1, len(trace.ops))
    busy = solve_busy = coll = 0.0
    by_op: dict[str, float] = {}
    all_gaps: list[tuple[float, str]] = []
    for dev in sorted(trace.ops):
        evs = trace.ops[dev]
        merged = union((s, e) for _, s, e in evs)
        busy += covered(merged, lo, hi)
        cmerged = union((s, e) for name, s, e in evs if is_collective(name))
        for s, e in solve_spans:
            solve_busy += covered(merged, s, e)
            coll += covered(cmerged, s, e)
        for name, d in self_times(evs, lo, hi).items():
            by_op[name] = by_op.get(name, 0.0) + d
        if dev == min(trace.ops):
            longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
            all_gaps = [(e - s, host_label(trace.host, (s + e) / 2))
                        for s, e in longest[:top]]
    ops_sorted = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_sorted = all_gaps
    return Summary(
        n_devices=len(trace.ops),
        window_s=(hi - lo) * 1e-9,
        busy_s=busy / n * 1e-9,
        solve_busy_s=solve_busy / n * 1e-9,
        collective_s=coll / n * 1e-9,
        top_ops=[[k, v / n * 1e-9] for k, v in ops_sorted],
        idle_gaps=[[label, d * 1e-9] for d, label in gaps_sorted],
    )
