"""Typed solver options — the one configuration object the facade accepts.

Replaces the ad-hoc kwargs previously threaded through ``launch/solve.py``,
``configs/hpcg.py`` and every benchmark driver.  Everything the seven solvers
and the two execution worlds (local / shard_map) understand is named here;
call sites stop inventing their own flag spellings.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

#: accepted ``halo_mode`` values, owned by the operator that implements them
#: (see backend.resolve_halo_mode for how "auto" resolves)
from repro.core.distributed import HALO_MODES

#: accepted ``precond`` values ("none" + the repro.precond registry)
from repro.precond import precond_names

from repro.core.methods import GuardSpec

#: accepted ``layout`` values and what they resolve to (see backend.py)
LAYOUTS = ("auto", "local", "1d", "2d", "3d")

#: accepted ``on_breakdown`` recovery policies (repro.resilience):
#: "raise"    — raise SolveBreakdown on an abnormal guarded exit
#: "none"     — return the typed SolveResult.status untouched
#: "restart"  — re-solve from the last finite iterate, up to max_restarts
#: "fallback" — retry down the robustness ladder: pallas→XLA first, then
#:              variant_of back to the classical method
ON_BREAKDOWN = ("raise", "none", "restart", "fallback")


@dataclasses.dataclass(frozen=True)
class SolverOptions:
    """Everything that parameterises a solve, minus the problem itself.

    Attributes
    ----------
    tol:          convergence tolerance (relative to ``norm_ref``).
    maxiter:      iteration cap.
    f64:          solve in double precision (the paper's setting).  The
                  facade never flips the process-global ``jax_enable_x64``
                  flag itself: building an f64 problem requires the caller
                  to have run ``repro.core.problems.enable_f64()`` at
                  process start (drivers do), and a pre-built ``problem``
                  whose dtype contradicts this flag raises instead of being
                  silently accepted.
    layout:       device decomposition: ``"auto"`` (local on 1 device, else
                  the paper-faithful 1-D z split), ``"local"``, ``"1d"``,
                  ``"2d"`` (near-square data×model mesh), ``"3d"``
                  (pod×data×model).
    pallas:       back the local stencil SpMV with the Pallas kernel
                  (float32/bfloat16 on a TPU: a float64 problem raises).
                  ``None`` = "auto": ``kernels.autotune`` decides per
                  (stencil, grid, dtype, device_kind) — the persisted tune
                  cache when one exists, else the default table (TPU, not
                  float64, and grid volume >= 24³).  Resolved to a concrete bool at
                  session construction.
    norm_ref:     residual normalisation; ``1.0`` = the paper's absolute
                  HPCCG criterion, ``None`` = relative to ``||b||``.
    dot:          override the reduction used by the solver (local path
                  only; the distributed path always uses the layout's psum).
    halo_mode:    halo-exchange strategy for the distributed operator
                  (``"auto"`` | ``"concat"`` | ``"scatter"`` |
                  ``"overlap"``).  ``"overlap"`` splits the SpMV into an
                  interior part computed while the ppermutes are in flight
                  and a boundary shell finished from the received planes;
                  all modes produce bit-for-bit identical results.
                  ``"auto"`` resolves to ``"overlap"`` for the built-in
                  stencil formulations, ``"concat"`` under a custom
                  ``matvec_padded``/Pallas kernel (see
                  ``backend.resolve_halo_mode``).
    matvec_padded: override the padded-operand SpMV (wins over ``pallas``).
    dims_map:     explicit grid-dim -> mesh-axis mapping (advanced; wins
                  over ``layout`` when a mesh is supplied).
    precond:      preconditioner for the methods that take one (``pcg`` /
                  ``pbicgstab``): ``"none"`` | ``"jacobi"`` |
                  ``"block_jacobi"`` | ``"ssor"`` | ``"chebyshev"``
                  (the ``repro.precond`` registry).  Resolved by
                  ``backend.resolve_precond``; requesting one with a
                  method that has no ``M=`` hook raises.
    precond_params: constructor knobs for the chosen preconditioner
                  (``{"sweeps": 3}``, ``{"degree": 5}``,
                  ``{"omega": 1.2}``, ...); ``options.pallas`` flows into
                  the preconditioners that have fused Pallas kernels
                  unless ``use_pallas`` is pinned here.
    donate:       donate the ``x0`` buffer of ``solve``/``solve_batched``
                  to the compiled call (``jax.jit`` ``donate_argnums``), so
                  the x/r/p iterate buffers reuse it instead of allocating
                  a fresh output each solve — the serving hot path.
                  Caveat: donation is live on EVERY backend (CPU included):
                  a caller-supplied ``x0`` array is INVALIDATED by the call
                  (reusing it raises a deleted-buffer error); pass
                  ``donate=False`` to keep reusing your own ``x0`` buffer.
                  The ``timed_*`` paths always compile an undonated variant
                  (they re-call with the same buffers).
    telemetry:    opt-in per-iteration convergence telemetry (repro.obs):
                  thread a bounded scalar-history buffer through the
                  solver's while-loop carry and return it as
                  ``SolveResult.telemetry`` — a
                  ``(min(telemetry_buffer, maxiter+1), n_scalars)`` array
                  whose row k holds every declared loop-carry scalar after
                  iteration k (row 0 = the initial state; NaN-padded past
                  convergence; iterations beyond the buffer overwrite its
                  last row).  Works on every backend (the buffer is part of
                  the MethodDef driver's carry) and is donation-safe
                  (fixed-size, created inside the jitted solve).  Disabled
                  (the default) the solve is a bitwise no-op vs the
                  pre-telemetry facade: ``SolveResult.telemetry`` is
                  ``None`` — an empty pytree subtree — and the lowered HLO
                  is unchanged.  Enabled it adds one (cheap, fused)
                  buffer write per iteration to the compiled loop.
    telemetry_buffer: row bound of the telemetry buffer (clamped to
                  ``maxiter + 1``); only read when ``telemetry=True``.
    guards:       arm the per-iteration breakdown guards (repro.resilience):
                  NaN scalars, divergence, the method's ρ-underflow /
                  negative-curvature guard and optional stagnation
                  detection, all riding scalars the loop already carries
                  (zero extra collectives).  OFF by default — with guards
                  off and ``on_breakdown="raise"`` the compiled solve is
                  bitwise the pre-resilience one except for the always-on
                  typed ``SolveResult.status``.
    on_breakdown: what ``SolverSession.solve`` does when a GUARDED solve
                  exits with status breakdown/diverged/stagnated (see
                  ``ON_BREAKDOWN``).  Any value other than "raise"/"none"
                  implies ``guards``.  Applies to single-RHS ``solve``
                  only; ``solve_batched`` always returns per-lane statuses.
    max_restarts: attempt budget for the "restart"/"fallback" policies.
    residual_replacement: every N > 0 iterations, re-derive the TRUE
                  residual (and the recurrence images) from the iterate —
                  the drift mitigation for the merged/pipelined variants
                  (methods whose MethodDef declares a ``refresh`` hook).
                  Cost: ``refresh_spmvs`` SpMV-equivalents per refresh,
                  priced by the scaling model's ``t_rr`` term.  0 = off.
    breakdown_eps / divergence_factor / stagnation_window / stagnation_rtol:
                  GuardSpec thresholds (see ``core.methods.GuardSpec``);
                  read only when guards are armed.
    """

    tol: float = 1e-6
    maxiter: int = 600
    f64: bool = True
    layout: str = "auto"
    pallas: bool | None = False
    norm_ref: float | None = 1.0
    dot: Callable | None = None
    halo_mode: str = "auto"
    matvec_padded: Callable | None = None
    dims_map: dict[str, str | None] | None = None
    precond: str = "none"
    precond_params: dict | None = None
    donate: bool = True
    telemetry: bool = False
    telemetry_buffer: int = 256
    guards: bool = False
    on_breakdown: str = "raise"
    max_restarts: int = 2
    residual_replacement: int = 0
    breakdown_eps: float = 1e-12
    divergence_factor: float = 1e8
    stagnation_window: int = 0
    stagnation_rtol: float = 1.0

    def guards_armed(self) -> bool:
        """Whether the breakdown guards compile into the loop cond: armed
        explicitly (``guards=True``) or implied by an active recovery
        policy (restart/fallback need the typed early exit to act on)."""
        return self.guards or self.on_breakdown in ("restart", "fallback")

    def guard_spec(self) -> GuardSpec | None:
        """The GuardSpec the MethodDef driver takes; None when disarmed."""
        if not self.guards_armed():
            return None
        return GuardSpec(
            breakdown_eps=self.breakdown_eps,
            divergence_factor=self.divergence_factor,
            stagnation_window=self.stagnation_window,
            stagnation_rtol=self.stagnation_rtol)

    def telemetry_rows(self) -> int:
        """Effective telemetry buffer rows: 0 when disabled, else the
        declared bound clamped to ``maxiter + 1`` (the most rows a solve
        can produce).  This is the ``telemetry=`` integer the MethodDef
        driver and ``solve_shardmap`` take."""
        if not self.telemetry:
            return 0
        return min(self.telemetry_buffer, self.maxiter + 1)

    def __post_init__(self):
        if self.precond not in precond_names():
            raise ValueError(
                f"unknown precond {self.precond!r}; "
                f"options: {precond_names()}")
        if self.precond_params and self.precond == "none":
            raise ValueError("precond_params given but precond='none'")
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"unknown layout {self.layout!r}; options: {LAYOUTS}")
        if self.halo_mode not in HALO_MODES:
            raise ValueError(
                f"unknown halo_mode {self.halo_mode!r}; options: {HALO_MODES}")
        if self.maxiter < 0:
            raise ValueError(f"maxiter must be >= 0, got {self.maxiter}")
        if self.telemetry_buffer < 1:
            raise ValueError(
                f"telemetry_buffer must be >= 1, got {self.telemetry_buffer}")
        if self.on_breakdown not in ON_BREAKDOWN:
            raise ValueError(
                f"unknown on_breakdown {self.on_breakdown!r}; "
                f"options: {ON_BREAKDOWN}")
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0, got {self.max_restarts}")
        if self.residual_replacement < 0:
            raise ValueError(
                f"residual_replacement must be >= 0 (0 disables), got "
                f"{self.residual_replacement}")
        if self.guards_armed():
            self.guard_spec()   # validates the GuardSpec thresholds

    def replace(self, **kw) -> "SolverOptions":
        return dataclasses.replace(self, **kw)

    def solver_kwargs(self) -> dict:
        """The kwargs every solver in the registry accepts."""
        return dict(tol=self.tol, maxiter=self.maxiter,
                    norm_ref=self.norm_ref)
