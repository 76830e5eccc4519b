"""Structural analysis of lowered/compiled HLO.

This is the "profiler" of the dry-run methodology (no real TPU): we parse the
HLO text to (i) count collectives, (ii) sum collective operand bytes for the
roofline's collective term, and (iii) measure *overlap slack* — how much
independent compute the schedule could run concurrently with each collective.

Overlap slack is the TPU-side evidence for the paper's Fig. 1: in classical
CG both all-reduces have ~zero independent work available (blocking barriers),
while in CG-NB each reduction has a full SpMV / vector-update's worth of
independent ops — the dependence-graph property that lets XLA's latency-hiding
scheduler overlap them.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# e.g. ``f32[128,256]{1,0}`` or ``bf16[4096]`` or ``pred[]``
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?")
# instruction line: ``  %name = <shape or tuple> opcode(...operands...)``,
# optionally prefixed with ROOT.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s*([\w\-]+)\((.*)$"
)
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes appearing in ``shape_str``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    result_bytes: int
    operand_names: list[str]
    operand_bytes: int
    raw: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: list[Instruction]

    def by_name(self) -> dict[str, int]:
        return {ins.name: i for i, ins in enumerate(self.instructions)}


def parse_computations(hlo_text: str) -> list[Computation]:
    comps: list[Computation] = []
    cur: Computation | None = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("(" in stripped or stripped.startswith("ENTRY")):
            header = stripped.split("(")[0].strip().lstrip("%")
            cur = Computation(name=header or "entry", instructions=[])
            continue
        if stripped == "}":
            if cur is not None:
                comps.append(cur)
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_str, opcode, rest = m.groups()
        # operand region: up to the matching close paren — approximate by
        # cutting at ``), `` attribute separators; operands are %refs anyway.
        operand_names = _OPERAND_RE.findall(rest.split("),")[0])
        cur.instructions.append(
            Instruction(
                name=name,
                opcode=opcode,
                result_bytes=shape_bytes(shape_str),
                operand_names=operand_names,
                operand_bytes=0,  # filled below
                raw=stripped,
            )
        )
    # resolve operand bytes from producer result sizes
    for comp in comps:
        idx = comp.by_name()
        for ins in comp.instructions:
            b = 0
            for on in ins.operand_names:
                j = idx.get(on)
                if j is not None:
                    b += comp.instructions[j].result_bytes
            ins.operand_bytes = b
    return comps


def is_collective(opcode: str) -> bool:
    base = opcode.replace("-start", "").replace("-done", "")
    return base in COLLECTIVE_OPS


def count_collectives(hlo_text: str) -> dict[str, int]:
    counts: dict[str, int] = defaultdict(int)
    for comp in parse_computations(hlo_text):
        for ins in comp.instructions:
            if is_collective(ins.opcode) and not ins.opcode.endswith("-done"):
                base = ins.opcode.replace("-start", "")
                counts[base] += 1
    return dict(counts)


def collective_stats(hlo_text: str) -> dict[str, dict[str, int]]:
    """Per-opcode ``{"count": n, "bytes": b}`` over every collective.

    The audit's drift detector: counts are checked against the registry's
    communication metadata, bytes against the committed AUDIT.json baseline
    (a byte change with stable counts means the *payload* structure moved —
    e.g. a psum pair silently unfusing into two half-size reductions would
    keep total bytes but change counts, while a state-layout change keeps
    counts but moves bytes).  ``-start``/``-done`` pairs count once, like
    :func:`count_collectives`.
    """
    stats: dict[str, dict[str, int]] = {}
    for comp in parse_computations(hlo_text):
        for ins in comp.instructions:
            if is_collective(ins.opcode) and not ins.opcode.endswith("-done"):
                base = ins.opcode.replace("-start", "")
                rec = stats.setdefault(base, {"count": 0, "bytes": 0})
                rec["count"] += 1
                rec["bytes"] += ins.operand_bytes or ins.result_bytes
    return stats


#: the donation annotations jax leaves in lowered text: ``tf.aliasing_output``
#: on unsharded lowerings, ``jax.buffer_donor`` once shardings are attached.
_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def donation_markers(lowered_text: str) -> int:
    """Number of donated arguments visible in *lowered* (StableHLO) text.

    Counts both spellings: a lowering with concrete/unsharded arguments
    annotates ``tf.aliasing_output = N``, one with shardings attached emits
    ``jax.buffer_donor = true`` — either way, one marker per donated
    argument.  ``SolverOptions.donate`` donates exactly x0, so the audit
    expects 1 with donation on and 0 with it off.
    """
    return sum(lowered_text.count(m) for m in _DONATION_MARKERS)


_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+)\s*,")


def input_output_aliases(compiled_text: str) -> list[int]:
    """Parameter numbers aliased to outputs in a *compiled* HloModule.

    Parses the ``input_output_alias={ {0}: (1, {}, may-alias) }`` header
    attribute — the form XLA actually acts on (the lowered markers above are
    requests; this is the grant).  Returns one entry per aliased output,
    e.g. ``[1]`` when output 0 reuses parameter 1's buffer.
    """
    out: list[int] = []
    for line in compiled_text.splitlines():
        if "input_output_alias={" not in line:
            continue
        body = line.split("input_output_alias={", 1)[1]
        depth = 1
        end = 0
        for i, ch in enumerate(body):
            depth += (ch == "{") - (ch == "}")
            if depth == 0:
                end = i
                break
        out.extend(int(p) for p in _ALIAS_ENTRY_RE.findall(body[:end]))
    return out


def collective_bytes(hlo_text: str, trip_counts: dict[str, int] | None = None) -> int:
    """Sum of operand bytes over every collective op.

    ``trip_counts`` maps computation-name substrings to a multiplier (used to
    scale while-loop bodies by their trip count, since a loop body appears
    once in the HLO but executes many times).
    """
    total = 0
    for comp in parse_computations(hlo_text):
        mult = 1
        if trip_counts:
            for frag, m in trip_counts.items():
                if frag in comp.name:
                    mult = m
                    break
        for ins in comp.instructions:
            if is_collective(ins.opcode) and not ins.opcode.endswith("-done"):
                # operand bytes == per-device send volume (all-gather sends the
                # shard, all-reduce ~the buffer (ring ~2x, ignored), ppermute
                # the slab).  Result bytes would overcount gathers n-fold.
                total += mult * (ins.operand_bytes or ins.result_bytes)
    return total


def _reachable(adj: list[list[int]], start: int) -> set[int]:
    seen = {start}
    stack = [start]
    while stack:
        u = stack.pop()
        for v in adj[u]:
            if v not in seen:
                seen.add(v)
                stack.append(v)
    return seen


# Opcodes that represent no real work (layout plumbing / constants); excluded
# from the overlap-slack work accounting.
_TRIVIAL_OPS = {
    "parameter", "constant", "iota", "broadcast", "copy", "bitcast",
    "bitcast-convert", "tuple", "get-tuple-element", "reshape", "convert",
    "transpose", "copy-start", "copy-done", "after-all", "partition-id",
}

# XLA's CPU backend wraps a lone op in a fusion called ``wrapped_<op>`` even
# with its fusion passes off (``wrapped_broadcast.1``)
_WRAPPED_RE = re.compile(r"wrapped_([a-z_\-]+?)(?:\.\d+)?$")


def _work_opcode(ins) -> str:
    """The opcode whose work ``ins`` does: the wrapped op's, for a
    single-op wrapper fusion."""
    m = _WRAPPED_RE.match(ins.name) if ins.opcode == "fusion" else None
    return m.group(1).replace("_", "-") if m else ins.opcode


def overlap_slack(hlo_text: str, computation_filter: str | None = None,
                  ops: tuple[str, ...] | None = None):
    """For each collective: how much work is *hideable behind it* — ops that
    are neither ancestors (already done when the collective issues) nor
    descendants (waiting on it) in the dependence graph.

    ``ops`` restricts the report to the named collective base opcodes (e.g.
    ``("collective-permute",)`` for the halo traffic, ``("all-reduce",)`` for
    the global reductions); default is every collective.

    Work proxy: result bytes of non-trivial ops (solver bodies are
    elementwise/stencil-dominated so byte traffic tracks FLOPs).  Reported
    both as absolute ``slack_bytes`` and as a fraction of the computation's
    total work.  A reduction is a *blocking barrier* in the paper's sense when
    its slack is below ~one vector's worth of traffic — see
    ``repro.core.overlap.blocking_reductions``.
    """
    out = []
    for comp in parse_computations(hlo_text):
        if computation_filter and computation_filter not in comp.name:
            continue
        n = len(comp.instructions)
        idx = comp.by_name()
        fwd: list[list[int]] = [[] for _ in range(n)]   # producer -> consumer
        bwd: list[list[int]] = [[] for _ in range(n)]
        for i, ins in enumerate(comp.instructions):
            for on in ins.operand_names:
                j = idx.get(on)
                if j is not None and j != i:
                    fwd[j].append(i)
                    bwd[i].append(j)
        weights = np.array(
            [
                0.0 if _work_opcode(ins) in _TRIVIAL_OPS
                else float(ins.result_bytes)
                for ins in comp.instructions
            ]
        )
        total_w = weights.sum() or 1.0
        for i, ins in enumerate(comp.instructions):
            if not is_collective(ins.opcode) or ins.opcode.endswith("-done"):
                continue
            if ops is not None and ins.opcode.replace("-start", "") not in ops:
                continue
            dependent = _reachable(fwd, i) | _reachable(bwd, i)
            indep_w = total_w - weights[list(dependent)].sum()
            out.append(
                dict(
                    computation=comp.name,
                    op=ins.opcode,
                    name=ins.name,
                    bytes=max(ins.operand_bytes, ins.result_bytes),
                    slack_bytes=float(indep_w),
                    slack_fraction=float(indep_w / total_w),
                )
            )
    return out


def while_loop_bodies(hlo_text: str) -> list[str]:
    """Names of computations that look like while-loop bodies."""
    return [
        c.name
        for c in parse_computations(hlo_text)
        if "body" in c.name or "while" in c.name
    ]
