"""Which operation of a compiled solve belongs to which part of the program.

The solve names its parts with ``jax.named_scope`` at the boundaries every
method goes through (:data:`SCOPES`; docs/API.md §Observability).  XLA
keeps the scope path in each instruction's ``op_name`` metadata, so the
text of a compiled executable says which ``repro.*`` scope each device
operation of a profiler trace ran in.

A fusion's own metadata is its root's, and the root is often the cheap
part: a stencil fusion of ``q = A p`` with ``p·q`` fused in carries the
dot's ``repro.reduce``, and one of ``r = b - A x`` the subtract's scope.
So a fusion is placed in the scope that holds most of its fused
instructions, and in its own scope on a tie.  The map is computed only
when asked for; nothing here runs at compile time.
"""

from __future__ import annotations

import collections
import re

#: the scope names, outermost first; a refactor keeps them
SCOPES = ("repro.loop", "repro.init", "repro.step", "repro.matvec",
          "repro.halo", "repro.reduce", "repro.precond")

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_FUSION = re.compile(r"\bkind=k\w+, calls=%([\w.\-]+)")
_SCOPE = re.compile(r"repro\.[a-z]+")


def scope_of(op_name: str) -> str | None:
    """The innermost ``repro.*`` scope in an ``op_name`` path, e.g.
    ``repro.matvec`` for ``jit(run)/repro.loop/while/body/repro.step/
    repro.matvec/add``; ``None`` outside every scope."""
    found = [s for s in _SCOPE.findall(op_name) if s in SCOPES]
    return found[-1] if found else None


def _parse(hlo_text: str):
    """Per module: ``{instruction: (own scope, fused computation)}`` and
    ``{computation: Counter of its instructions' scopes}``."""
    modules: dict[str, tuple[dict, dict]] = {}
    instrs = comps = None
    comp = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            instrs, comps = modules.setdefault(m.group(1), ({}, {}))
            continue
        if instrs is None:
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = comps.setdefault(m.group(1), collections.Counter())
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        fused = _FUSION.search(line)
        instrs[m.group(1)] = (scope, fused.group(1) if fused else None)
        if scope is not None and comp is not None:
            comp[scope] += 1
    return modules


def op_scopes_from_text(hlo_text: str) -> dict[tuple[str, str], str]:
    """``{(HLO module, instruction name): repro.* scope}`` for every
    instruction of ``hlo_text`` (one or more ``HloModule`` dumps) that a
    scope holds: its own innermost scope, or for a fusion the scope of
    most of its fused instructions."""
    out: dict[tuple[str, str], str] = {}
    for module, (instrs, comps) in _parse(hlo_text).items():
        for name, (own, fused) in instrs.items():
            counts = comps.get(fused) if fused else None
            if counts:
                own = max(counts, key=lambda s: (counts[s], s == own))
            if own is not None:
                out[(module, name)] = own
    return out


def op_scopes(compiled) -> dict[tuple[str, str], str]:
    """:func:`op_scopes_from_text` of a ``jax.stages.Compiled``."""
    return op_scopes_from_text(compiled.as_text())
