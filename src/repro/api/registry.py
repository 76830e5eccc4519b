"""Solver registry: a read-only view over ``repro.core.methods.METHODS``.

Each method is declared once, as a ``MethodDef`` passed to
``register_method``; that declaration carries the per-iteration
communication structure (reductions, how each one hides, SpMV and halo
counts) that drives the scaling model and the barrier-structure reporting,
plus the solver-selection facts (SPD requirement, stationary vs Krylov,
which classical method a variant descends from).  Every driver — launch,
benchmarks, examples, the dry-run — reads it through these helpers.
"""

from __future__ import annotations

from repro.core.methods import METHODS, MethodDef

#: name -> MethodDef; the same dict object as ``core.methods.METHODS``
REGISTRY: dict[str, MethodDef] = METHODS


def get_solver(name: str) -> MethodDef:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown method {name!r}; options: {sorted(REGISTRY)}") from None


def solver_names() -> list[str]:
    return sorted(REGISTRY)


def variant_pairs() -> list[tuple[str, str]]:
    """(classical, variant) pairs — the paper's side-by-side comparisons."""
    return sorted((s.variant_of, s.name) for s in REGISTRY.values()
                  if s.variant_of is not None)


def fallback_chain(name: str) -> list[str]:
    """``[name, its variant_of, ...]`` down to the classical method — the
    robustness ladder ``on_breakdown="fallback"`` walks (repro.resilience):
    each rung trades back a communication-hiding rearrangement for the
    numerically plainer recurrence it was derived from.  Cycle-safe (a
    malformed registry cannot loop) and always at least ``[name]``."""
    chain, seen = [name], {name}
    cur = get_solver(name)
    while cur.variant_of is not None and cur.variant_of not in seen:
        chain.append(cur.variant_of)
        seen.add(cur.variant_of)
        cur = get_solver(cur.variant_of)
    return chain


def fused_solver_names() -> list[str]:
    """Methods whose MethodDef declares a fused Pallas iteration body — the
    capability query behind the facade's ``pallas=True`` routing."""
    return sorted(n for n, s in REGISTRY.items() if s.has_fused_body)
