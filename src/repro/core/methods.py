"""Single-source method definitions: each iterative method defined ONCE.

This module is the paper's central design point made literal.  The paper's
claim is that the *same* numerical method can be re-expressed across parallel
execution models (MPI-only, fork-join, task-based) and compared fairly; the
repo's analogue is that ONE :class:`MethodDef` per algorithm drives

  * the local single-device ``solve()`` path          (``LocalOp``),
  * the whole-solve distributed path                  (``DistributedOp``
    inside ``shard_map`` — ``core.distributed.solve_shardmap``),
  * the one-iteration analysis hook                   (``solve_step_shardmap``,
    what the dry-run/roofline lowers for exact cost analysis), and
  * the fused Pallas execution of the methods that declare fused kernels
    (``kernels.pallas_op.PallasOp``) — single-device AND inside shard_map.

A ``MethodDef`` is three pure functions plus a declared state layout:

  ``init(ops, x0) -> state``      the loop carry at iteration 0
  ``step(ops, state) -> state``   ONE iteration (== one while_loop body)
  ``finalize(ops, x0, state)``    exit correction (optional; default state[0])

``state`` is a flat tuple: the declared ``vectors`` (local-grid arrays, the
iterate first) followed by the declared ``scalars``.  ``res_scalar`` names the
scalar slot carrying the method's squared-residual estimate — the generic
driver's convergence check, residual history and reported ``res_norm`` all
read exactly that slot, which is what keeps iteration counts comparable
across methods and backends.

``ops`` is an :class:`Ops` context: the operator ``A`` (anything satisfying
the ``LocalOp`` protocol — ``matvec``/``pad_exchange``/``diag``/``dotn``),
the right-hand side ``b``, the bound preconditioner apply ``M`` (identity
when absent), and the reduction hooks ``dot``/``dot2``/``dotn``.  On a
single device the reductions are :func:`local_dot` (a multiply and a sum);
inside ``shard_map`` they are its local partials under the layout's
``psum`` — the method definition cannot tell, which is the whole point (the
paper's write-once/parallelise-underneath rule).

Barrier structure reproduced from the paper (§3.1, Fig. 1):

  * ``cg``            — 2 blocking reductions / iteration.
  * ``cg_nb``         — Alg. 1: the SpMV is applied to ``r`` so ``A·p`` becomes a
                        vector update; both reductions leave the critical path
                        (the ``r·r`` reduction overlaps the SpMV, the ``Ap·p``
                        reduction overlaps the lagged ``x`` update).  NOTE:
                        Alg. 1 line 9 is implemented with the sign convention
                        that keeps ``x_j = x_{j-1} + α_{j-1} p_{j-1}`` (the
                        printed minus sign is a typo — with it the recursion
                        contradicts line 4).  Equivalence with classical CG is
                        asserted by tests/test_solvers.py.
  * ``bicgstab``      — 3 blocking reductions / iteration.
  * ``bicgstab_b1``   — Alg. 2: ω's reductions overlap the ``x_{j+1/2}`` update,
                        the ``α_n``/``β`` reductions overlap the ``p_{j+1/2}``
                        update; one blocking reduction (``α_d``) remains.
                        Includes the restart procedure (lines 13-15).
  * ``jacobi``        — 1 reduction (the residual norm).
  * ``gauss_seidel``  — the paper's *relaxed* tasked GS adapted to TPU:
                        GS-fresh across z-planes inside a block, stale across
                        blocks (the role the benign data races play in the
                        paper's Code 4).
  * ``gauss_seidel_rb`` — red-black coloured symmetric GS (§3.4).

Beyond the paper: the preconditioned forms (``pcg``/``pbicgstab`` + merged/
pipelined composites, PR 3) and the reduction-hiding restructurings
(``*_merged``/``*_pipe``, PR 4 — Chronopoulos–Gear, Cools–Vanroose,
Ghysels–Vanroose).  Numerical caveat: the merged/pipelined forms replace
``p·Ap`` (and, for BiCGStab, ‖r‖²) with recurrences; rounding makes them
drift from the classics by O(ε·κ) per iteration and puts an O(ε·κ·‖b‖)
floor on the attainable residual — solve in f64 (the paper's setting) for
tight absolute tolerances.  The reported ``res_norm`` is each method's own
estimate, like the classics'.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


#: typed loop-exit statuses (``SolveResult.status``, repro.resilience).
#: Always computed — classification is a handful of scalar ``where``s on
#: values the loop already carries, so it adds no collectives and no cost.
STATUS_CONVERGED = 0    # res_scalar dropped below (tol * norm_ref)^2
STATUS_MAXITER = 1      # iteration budget exhausted, residual still finite
STATUS_BREAKDOWN = 2    # NaN scalars or a method guard fired (rho/omega
#                         underflow, negative curvature on a non-SPD operator)
STATUS_DIVERGED = 3     # residual blew past divergence_factor^2 * ||r0||^2
STATUS_STAGNATED = 4    # no relative progress for stagnation_window iters

STATUS_NAMES = ("converged", "maxiter", "breakdown", "diverged", "stagnated")


def status_name(code) -> str:
    """Human name for a ``SolveResult.status`` code (host-side helper)."""
    return STATUS_NAMES[int(code)]


@dataclasses.dataclass(frozen=True)
class GuardSpec:
    """Breakdown-guard thresholds for the resilient driver (opt-in).

    Every check reads scalars the while-loop already carries (post-psum,
    hence replicated under shard_map) — enabling guards changes neither the
    collective count nor the reduction schedule, which is what the
    ``repro.analysis`` guard-invariance audit asserts.

    ``breakdown_eps``      ρ-underflow threshold for the BiCGStab family
                           (fires when ρ² < ε²·‖r₀‖²·‖r‖²).  Conservative
                           default: only a genuine orthogonality collapse
                           trips it.
    ``divergence_factor``  exit with ``diverged`` once the squared residual
                           exceeds ``factor² · max(‖r₀‖², thresh²)``.
    ``stagnation_window``  0 disables; N > 0 exits with ``stagnated`` after
                           N consecutive iterations without the squared
                           residual improving below ``stagnation_rtol`` ×
                           the best seen so far.
    """

    breakdown_eps: float = 1e-12
    divergence_factor: float = 1e8
    stagnation_window: int = 0
    stagnation_rtol: float = 1.0

    def __post_init__(self):
        if self.breakdown_eps < 0 or self.divergence_factor <= 1:
            raise ValueError(
                f"GuardSpec: breakdown_eps must be >= 0 and "
                f"divergence_factor > 1, got {self.breakdown_eps!r}/"
                f"{self.divergence_factor!r}")
        if self.stagnation_window < 0 or not 0 < self.stagnation_rtol <= 1:
            raise ValueError(
                f"GuardSpec: stagnation_window >= 0 and 0 < stagnation_rtol "
                f"<= 1 required, got {self.stagnation_window!r}/"
                f"{self.stagnation_rtol!r}")


class SolveBreakdown(RuntimeError):
    """A guarded solve exited abnormally under ``on_breakdown="raise"``.

    Carries the method name and the full :class:`SolveResult` (``.method``,
    ``.result``) so callers can inspect the typed status, the iterate and
    the residual history of the failed attempt.
    """

    def __init__(self, method: str, result: "SolveResult"):
        self.method = method
        self.result = result
        super().__init__(
            f"{method}: solve exited with status="
            f"{status_name(result.status)!r} after {int(result.iters)} "
            f"iterations (res_norm={float(result.res_norm):.3e})")


class SolveResult(NamedTuple):
    x: jax.Array
    iters: jax.Array          # number of completed iterations
    res_norm: jax.Array       # final ||r||_2 (method's own residual estimate)
    history: jax.Array        # (maxiter+1,) residual-norm history, NaN-padded
    #: opt-in per-iteration scalar-state telemetry (repro.obs): a bounded
    #: (buffer, len(mdef.scalars)) NaN-padded buffer of the method's declared
    #: loop-carry scalars, row k = the state after iteration k (row 0 = the
    #: initial state; overflow past the buffer overwrites the last row).
    #: ``None`` when disabled — an EMPTY pytree subtree, so the result tree,
    #: the lowered HLO and every shard_map out_spec are bit-for-bit the
    #: pre-telemetry ones.
    telemetry: jax.Array | None = None
    #: typed loop-exit status (int32, one of the ``STATUS_*`` codes above).
    #: ``run_method`` always fills it; the ``None`` default only keeps
    #: hand-built results (tests, out_spec templates) constructible.
    status: jax.Array | None = None


def local_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """The local dot product of every solver reduction: an elementwise
    product summed in the operands' dtype.

    The operators are real and symmetric, so there is no conjugation to
    keep.  Not ``jnp.vdot``: a float64 ``dot_general`` on a TPU takes XLA's
    multi-limb dot emulation (``while`` loops over f32 pieces staged in
    vector-sized buffers), where a float64 multiply and ``reduce`` take the
    same float64 emulation as the vector updates.  A float32 vector
    ``dot_general`` already compiles to this multiply-reduce.
    """
    return jnp.sum(a * b)


def _identity(v: jax.Array) -> jax.Array:
    return v


def in_scope(name: str) -> Callable:
    """Decorator: every operation the function traces goes under
    ``jax.named_scope(name)``.  HLO metadata only, so the compiled program
    is unchanged; docs/API.md §Observability lists the scope names."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def _own_dot(A, dot) -> bool:
    """Whether ``dot`` is the operator's own, or none was given: only then
    may the operator's fused reductions (``dotn``, ``matvec_dot``) stand in
    for it."""
    return dot is None or getattr(dot, "__self__", None) is A


def _stacked_dot(A, dot):
    """The fused-reduction hook of the merged/pipelined variants.

    Returns ``dotn(*pairs) -> tuple`` computing every pair in ONE global
    reduction.  When the caller passes the operator's own ``dot`` (or none),
    the operator's ``dotn`` is used — ``DistributedOp.dotn`` stacks the
    partials into a single ``psum``, which is the whole point of the merged
    variants.  A foreign ``dot`` override (``SolverOptions.dot``) falls back
    to per-pair calls, preserving its semantics at the cost of the fusion.
    """
    if _own_dot(A, dot):
        dn = getattr(A, "dotn", None)
        if dn is not None:
            return dn
    d = dot or local_dot

    def dotn(*pairs):
        return tuple(d(a, b) for a, b in pairs)

    return dotn


def _hist_init(maxiter: int, v0, dtype) -> jax.Array:
    h = jnp.full((maxiter + 1,), jnp.nan, dtype=dtype)
    return h.at[0].set(v0.astype(dtype))


#: the FULL surface a MethodDef body may touch on its ``ops`` context — the
#: write-once/parallelise-underneath contract, stated once so the AST lint
#: (``repro.analysis.lint_methods``) and the humans reading this file agree.
#: A method body calling anything else is coupling itself to one backend.
OPS_PROTOCOL = frozenset({
    "A", "b", "M", "dot", "dot2", "dotn", "matvec", "matvec_dot", "diag",
    "norm_ref", "params",
})

#: what a MethodDef may touch on the operator itself (``ops.A`` — the
#: LocalOp/DistributedOp/PallasOp protocol).  ``base`` unwraps a PallasOp to
#: its inner operator; everything from ``spmv_dots`` on is a fused-kernel
#: hook a ``fused_step`` body targets (``PallasOp`` supplies them — one per
#: single-pass Pallas kernel of the reduction-hiding family).
OPERATOR_PROTOCOL = frozenset({
    "matvec", "matvec_dot", "matvec_local", "pad_exchange", "diag",
    "stencil", "dot", "dot2", "dotn", "sum_partials", "split_dims", "base",
    "spmv_dots",
    "cg_body", "spmv_dots3", "fused_dots", "pipe_body", "pcg_body",
    "ppipe_body", "bicgstab_spmv_dots", "bicgstab_update1",
    "bicgstab_spmv_update",
})


class Ops:
    """The execution context a :class:`MethodDef` runs against.

    Bundles the operator, the right-hand side, the bound preconditioner
    apply and the reduction hooks.  ``dot`` defaults to the operator's own
    global reduction (``DistributedOp.dot`` = one psum) when it has one,
    else :func:`local_dot`; ``dotn`` stacks any number of dot products into
    ONE collective where the operator supports it (see :func:`_stacked_dot`).
    ``matvec_dot`` is ``(A p, p·A p)`` from the operator's own hook under
    the same rule: a foreign ``dot`` gets ``matvec`` and then ``dot``.
    ``norm_ref=None`` resolves to ``||b||`` via ``dot`` (the relative
    criterion); the paper's absolute HPCCG criterion is ``norm_ref=1.0``.
    """

    __slots__ = ("A", "b", "M", "dot", "dotn", "norm_ref", "params",
                 "_matvec_dot")

    def __init__(self, A, b, *, M=None, dot=None, norm_ref=None,
                 params: dict | None = None):
        self.A = A
        self.b = b
        self.M = in_scope("repro.precond")(M) if M is not None else _identity
        own = getattr(A, "dot", None)
        reduce = in_scope("repro.reduce")
        self.dot = reduce(dot if dot is not None else (own or local_dot))
        self.dotn = reduce(_stacked_dot(A, dot))
        self._matvec_dot = (getattr(A, "matvec_dot", None)
                            if _own_dot(A, dot) else None)
        self.params = params or {}
        if norm_ref is None:
            norm_ref = jnp.sqrt(self.dot(b, b))
        self.norm_ref = norm_ref

    def matvec(self, x: jax.Array) -> jax.Array:
        with jax.named_scope("repro.matvec"):
            return self.A.matvec(x)

    def matvec_dot(self, p: jax.Array) -> tuple:
        """``(A p, p·A p)``: the apply and the global dot of its operand
        with the result, for CG's step.  The operator's hook fences the
        local partial with the apply, so XLA can reduce it in the apply's
        pass; the dot's own operations and its psum stay ``repro.reduce``'s
        (a fusion of the two is billed to ``repro.matvec``, by most of its
        instructions)."""
        if self._matvec_dot is None:
            Ap = self.matvec(p)
            return Ap, self.dot(p, Ap)
        with jax.named_scope("repro.matvec"):
            return self._matvec_dot(p)

    def dot2(self, a, b, c, d) -> tuple:
        """Two dot products in ONE collective (the paper fuses scalar pairs
        into a single MPI_Allreduce)."""
        return self.dotn((a, b), (c, d))

    @property
    def diag(self):
        return self.A.diag


#: how a reduction's latency is hidden (the scaling model's terms):
#: "none" = blocking barrier, "vec" = overlapped with one vector update,
#: "spmv" = overlapped with the SpMV, "pipe" = a pipelined stacked
#: reduction overlapped with the NEXT SpMV (+ preconditioner apply) —
#: the Ghysels–Vanroose window, priced by scaling_model's t_reduce term.
HideKind = str

#: accepted ``MethodDef.reduce_hide`` values — the variant's reduction
#: *scheduling strategy* (orthogonal to the per-reduction hide kinds):
#: "none"      = one psum per dot product (the classics + the paper's
#:               nonblocking variants),
#: "merged"    = every dot of the iteration stacked into ONE psum
#:               (Chronopoulos–Gear CG, single-reduction BiCGStab),
#: "pipelined" = the ONE stacked psum additionally overlapped with the
#:               body's SpMV (Ghysels–Vanroose).
REDUCE_HIDES = ("none", "merged", "pipelined")

#: how a SpMV's halo exchange hides (one entry per SpMV per iteration):
#: "interior" = the ppermutes ride behind the interior stencil apply
#: (halo_mode="overlap"), "none" = the consumer needs the halos immediately
#: (the Gauss-Seidel sweeps: the very first plane/colour reads them).
HaloHideKind = str


@dataclasses.dataclass(frozen=True)
class MethodDef:
    """One iterative method, defined once, executed by pluggable runtimes.

    ``vectors``/``scalars`` declare the loop-carry layout (the step-state
    signature of ``solve_step_shardmap`` and the dry-run is derived from
    them mechanically); ``res_scalar`` names the scalar slot the generic
    driver's convergence check and history read.  ``fused_init``/
    ``fused_step`` (present iff ``fused_kernels`` is non-empty) are the
    same iteration expressed against the fused-kernel hooks of
    ``kernels.pallas_op.PallasOp`` — the capability the facade's Pallas
    routing queries.  The per-iteration communication structure
    (``reduction_hides`` … ``halo_exchanges_per_iter``) is what the scaling
    model, the barrier-structure reporting and the HLO audit read.
    """

    name: str
    vectors: tuple[str, ...]          # loop-carried grid arrays; [0] = iterate
    scalars: tuple[str, ...]          # loop-carried scalars
    res_scalar: str                   # scalar slot holding ||r||^2 (estimate)
    init: Callable                    # (ops, x0) -> state
    step: Callable                    # (ops, state) -> state
    reduction_hides: tuple[HideKind, ...]   # per logical reduction
    spmvs_per_iter: int
    finalize: Callable | None = None  # (ops, x0, state) -> x
    variant_of: str | None = None     # classical baseline this method refines
    accepts_precond: bool = False     # init/step consult ops.M
    stationary: bool = False          # Jacobi/GS family (vs Krylov)
    reduce_hide: str = "none"         # reduction scheduling (REDUCE_HIDES)
    params: tuple[str, ...] = ()      # tuning knobs read from ops.params
    default_maxiter: int = 500
    fused_kernels: tuple[str, ...] = ()   # PallasOp hooks the fused body uses
    fused_init: Callable | None = None
    fused_step: Callable | None = None
    #: optional breakdown guard ``(ops, state, rr0, eps) -> bool``: True
    #: means the next step would amplify a numerical breakdown (ρ/ω
    #: underflow, negative curvature).  Evaluated on carried post-psum
    #: scalars only — it must add no reductions.  None = generic NaN/
    #: divergence guards only.
    guard: Callable | None = None
    #: optional residual replacement ``(ops, x0, state) -> state``:
    #: recompute the TRUE residual (and the recurrence images derived from
    #: it) from the current iterate, bounding the O(ε·κ) per-iteration
    #: recurrence drift of the merged/pipelined variants.  Applied every
    #: ``refresh_every`` iterations by the resilient driver.
    refresh: Callable | None = None
    #: SpMV-equivalents one refresh costs (scaling-model price of the
    #: residual-replacement cadence); required iff ``refresh`` is set.
    refresh_spmvs: int = 0
    halo_hides: tuple[HaloHideKind, ...] = ()   # defaults to all-"interior"
    spd_required: bool = False
    precond_applies_per_iter: int = 0  # M^{-1} applications per iteration
    #: COMPILED all-reduces per iteration body — what `python -m
    #: repro.analysis` asserts on the HLO of every mesh shape.  Defaults to
    #: ``reductions_per_iter``; set explicitly where the implementation fuses
    #: logical reductions into one collective (pcg rides r·z and r·r on a
    #: single psum pair, so 3 logical reductions compile to 2 all-reduces).
    allreduces_per_iter: int | None = None
    #: halo exchanges (``pad_exchange`` calls) per iteration body; each one
    #: compiles to ``2 × n_split_dims`` collective-permutes.  Defaults to
    #: ``spmvs_per_iter``; the Gauss-Seidel sweeps exchange per plane/colour
    #: half-sweep plus once for the residual matvec, so they set it higher.
    halo_exchanges_per_iter: int | None = None
    description: str = ""

    def __post_init__(self):
        if self.res_scalar not in self.scalars:
            raise ValueError(
                f"{self.name!r}: res_scalar {self.res_scalar!r} not in "
                f"declared scalars {self.scalars}")
        if bool(self.fused_kernels) != (self.fused_step is not None):
            raise ValueError(
                f"{self.name!r}: fused_kernels and fused_step must be "
                f"declared together")
        if self.fused_step is not None and self.fused_init is None:
            raise ValueError(f"{self.name!r}: fused_step without fused_init")
        if (self.refresh is None) != (self.refresh_spmvs == 0):
            raise ValueError(
                f"{self.name!r}: refresh and refresh_spmvs must be declared "
                f"together (the scaling model prices every refresh hook)")
        if not self.halo_hides:
            object.__setattr__(
                self, "halo_hides", ("interior",) * self.spmvs_per_iter)
        if len(self.halo_hides) != self.spmvs_per_iter:
            raise ValueError(
                f"{self.name!r}: halo_hides needs one entry per SpMV "
                f"({len(self.halo_hides)} != {self.spmvs_per_iter})")
        if self.precond_applies_per_iter and not self.accepts_precond:
            raise ValueError(
                f"{self.name!r}: precond_applies_per_iter without "
                f"accepts_precond")
        if self.reduce_hide not in REDUCE_HIDES:
            raise ValueError(
                f"{self.name!r}: unknown reduce_hide {self.reduce_hide!r}; "
                f"options: {REDUCE_HIDES}")
        if self.reduce_hide != "none" and len(self.reduction_hides) != 1:
            raise ValueError(
                f"{self.name!r}: reduce_hide={self.reduce_hide!r} means ONE "
                f"stacked reduction per iteration, but reduction_hides has "
                f"{len(self.reduction_hides)} entries")
        if self.reduce_hide == "pipelined" and self.reduction_hides != ("pipe",):
            raise ValueError(
                f"{self.name!r}: a pipelined variant's single reduction "
                f"hides behind the next SpMV — reduction_hides must be "
                f"('pipe',)")
        if self.allreduces_per_iter is None:
            object.__setattr__(
                self, "allreduces_per_iter", self.reductions_per_iter)
        if self.halo_exchanges_per_iter is None:
            object.__setattr__(
                self, "halo_exchanges_per_iter", self.spmvs_per_iter)
        if self.reduce_hide != "none" and self.allreduces_per_iter != 1:
            raise ValueError(
                f"{self.name!r}: reduce_hide={self.reduce_hide!r} claims ONE "
                f"stacked reduction but allreduces_per_iter="
                f"{self.allreduces_per_iter}")
        if self.allreduces_per_iter > self.reductions_per_iter:
            raise ValueError(
                f"{self.name!r}: allreduces_per_iter "
                f"({self.allreduces_per_iter}) exceeds the declared logical "
                f"reductions ({self.reductions_per_iter}) — fusing can only "
                f"reduce the collective count")
        if self.halo_exchanges_per_iter < self.spmvs_per_iter:
            raise ValueError(
                f"{self.name!r}: halo_exchanges_per_iter "
                f"({self.halo_exchanges_per_iter}) below spmvs_per_iter "
                f"({self.spmvs_per_iter}) — every SpMV needs its halos")

    @property
    def res_index(self) -> int:
        """Flat state index of the ``res_scalar`` slot."""
        return len(self.vectors) + self.scalars.index(self.res_scalar)

    @property
    def reductions_per_iter(self) -> int:
        return len(self.reduction_hides)

    @property
    def blocking_reductions(self) -> int:
        """Reductions with no overlap window (the paper's hard barriers)."""
        return sum(1 for h in self.reduction_hides if h == "none")

    @property
    def hidden_halos(self) -> int:
        """SpMVs whose halo exchange overlaps interior compute."""
        return sum(1 for h in self.halo_hides if h == "interior")

    @property
    def has_fused_body(self) -> bool:
        return self.fused_step is not None

    @property
    def has_refresh(self) -> bool:
        """Whether the method declares a residual-replacement hook — the
        capability ``SolverOptions.residual_replacement`` queries."""
        return self.refresh is not None


METHODS: dict[str, MethodDef] = {}


def register_method(mdef: MethodDef) -> MethodDef:
    if mdef.name in METHODS:
        raise ValueError(f"method {mdef.name!r} already defined")
    if mdef.variant_of is not None and mdef.variant_of not in METHODS:
        raise ValueError(
            f"{mdef.name!r}: unknown baseline {mdef.variant_of!r} "
            f"(define the classical method first)")
    METHODS[mdef.name] = mdef
    return mdef


def get_method(name: str) -> MethodDef:
    """Look up a MethodDef; unknown names raise a ValueError that lists the
    known methods (the silent-fallthrough regression fixed in PR 5)."""
    try:
        return METHODS[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; known methods: "
            f"{sorted(METHODS)}") from None


def method_names() -> list[str]:
    return sorted(METHODS)


# =============================================================================
# The generic driver: MethodDef + Ops -> a whole solve
# =============================================================================

def _status_basic(res2, thresh2):
    """Loop-exit classification from the residual scalar alone.

    The plain driver's cond (``res2 >= thresh2``) already exits on NaN
    (every comparison with NaN is False) — this names WHY the loop exited
    instead of letting a NaN ``res_norm`` masquerade as convergence.
    """
    status = jnp.where(res2 < thresh2, STATUS_CONVERGED, STATUS_MAXITER)
    status = jnp.where(jnp.isinf(res2), STATUS_DIVERGED, status)
    status = jnp.where(jnp.isnan(res2), STATUS_BREAKDOWN, status)
    return status.astype(jnp.int32)


@in_scope("repro.loop")
def run_method(mdef: MethodDef, ops: Ops, x0: jax.Array, *,
               tol: float = 1e-6, maxiter: int | None = None,
               fused: bool = False, telemetry: int = 0,
               guard_spec: GuardSpec | None = None,
               refresh_every: int = 0) -> SolveResult:
    """Run ``mdef`` to convergence: ``lax.while_loop`` around its ``step``.

    The convergence check, the residual history and the reported
    ``res_norm`` all read the method's declared ``res_scalar`` slot, so
    every backend (local, shard_map, fused Pallas) stops on identical
    criteria.  ``fused=True`` selects the fused-kernel body (``ops.A`` must
    then be a ``PallasOp``).

    ``telemetry=N`` (repro.obs) additionally threads a bounded
    ``(min(N, maxiter+1), len(mdef.scalars))`` scalar-history buffer
    through the while-loop carry: row k holds every declared loop-carry
    scalar after iteration k (row 0 = the initial state; iterations past
    the buffer overwrite its last row — fixed-size, so the carry stays
    donation-safe).  ``telemetry=0`` (the default) takes a code path
    byte-identical to the pre-telemetry driver and returns
    ``SolveResult.telemetry = None``.

    Resilience (repro.resilience):

    * ``SolveResult.status`` is ALWAYS filled with a typed exit code —
      with everything below disabled it is classified post-loop from the
      residual scalar alone (:func:`_status_basic`), so the loop, its
      carry and its collectives are untouched.
    * ``guard_spec=GuardSpec(...)`` arms per-iteration breakdown guards in
      the loop cond: NaN in any carried scalar, divergence past
      ``divergence_factor``, the method's own ``guard`` hook (ρ-underflow,
      negative curvature) and optional stagnation detection.  A fired
      guard exits BEFORE the poisoning step runs, preserving the last
      finite iterate.  Guards read carried post-psum scalars only — zero
      extra collectives (audited by ``repro.analysis``).
    * ``refresh_every=N`` applies the method's residual-replacement hook
      every N iterations (methods with ``refresh`` declared — the
      merged/pipelined variants), bounding recurrence drift at a priced
      cost of ``refresh_spmvs`` SpMV-equivalents per refresh.

    Scopes (repro.obs): the driver traces under ``repro.loop``, the
    method's init under ``repro.init`` and each step under ``repro.step``;
    ``Ops`` puts its matvec, reductions and preconditioner inside those.
    """
    if maxiter is None:
        maxiter = mdef.default_maxiter
    if fused and not mdef.has_fused_body:
        raise ValueError(f"{mdef.name!r} declares no fused kernels")
    if refresh_every < 0:
        raise ValueError(f"refresh_every must be >= 0, got {refresh_every}")
    if refresh_every and mdef.refresh is None:
        raise ValueError(
            f"{mdef.name!r} declares no residual-replacement hook; "
            f"refresh_every applies only to methods with one "
            f"(the merged/pipelined variants)")
    init = mdef.fused_init if fused else mdef.init
    step = in_scope("repro.step")(mdef.fused_step if fused else mdef.step)
    thresh2 = (tol * ops.norm_ref) ** 2
    ridx = mdef.res_index
    with jax.named_scope("repro.init"):
        state = tuple(init(ops, x0))
    hist = _hist_init(maxiter, jnp.sqrt(state[ridx]), ops.b.dtype)

    if guard_spec is not None or refresh_every:
        return _run_resilient(mdef, ops, x0, step, state, hist,
                              thresh2=thresh2, maxiter=maxiter,
                              telemetry=telemetry, guard_spec=guard_spec,
                              refresh_every=refresh_every)

    if not telemetry:
        def cond(c):
            state, k, _ = c
            return (state[ridx] >= thresh2) & (k < maxiter)

        def body(c):
            state, k, hist = c
            state = tuple(step(ops, state))
            hist = hist.at[k + 1].set(jnp.sqrt(state[ridx]).astype(hist.dtype))
            return (state, k + 1, hist)

        state, k, hist = lax.while_loop(cond, body, (state, 0, hist))
        x = mdef.finalize(ops, x0, state) if mdef.finalize else state[0]
        return SolveResult(x=x, iters=k, res_norm=jnp.sqrt(state[ridx]),
                           history=hist,
                           status=_status_basic(state[ridx], thresh2))

    cap = min(int(telemetry), maxiter + 1)
    nvec = len(mdef.vectors)
    dt = hist.dtype

    def _scal_row(state):
        return jnp.stack([jnp.asarray(s).astype(dt) for s in state[nvec:]])

    tele = jnp.full((cap, len(mdef.scalars)), jnp.nan, dt)
    tele = tele.at[0].set(_scal_row(state))

    def cond(c):
        state, k, _, _ = c
        return (state[ridx] >= thresh2) & (k < maxiter)

    def body(c):
        state, k, hist, tele = c
        state = tuple(step(ops, state))
        hist = hist.at[k + 1].set(jnp.sqrt(state[ridx]).astype(hist.dtype))
        tele = tele.at[jnp.minimum(k + 1, cap - 1)].set(_scal_row(state))
        return (state, k + 1, hist, tele)

    state, k, hist, tele = lax.while_loop(cond, body, (state, 0, hist, tele))
    x = mdef.finalize(ops, x0, state) if mdef.finalize else state[0]
    return SolveResult(x=x, iters=k, res_norm=jnp.sqrt(state[ridx]),
                       history=hist, telemetry=tele,
                       status=_status_basic(state[ridx], thresh2))


def _run_resilient(mdef: MethodDef, ops: Ops, x0, step, state, hist, *,
                   thresh2, maxiter: int, telemetry: int,
                   guard_spec: GuardSpec | None,
                   refresh_every: int) -> SolveResult:
    """The guarded/refreshing driver loop (run_method's opt-in slow path).

    Carries a dict pytree so the optional extras (telemetry rows,
    stagnation counters) ride along only when requested.  All guard terms
    are elementwise ops on carried post-psum scalars — under shard_map they
    are replicated, so every shard takes the same branch and no collective
    is added (the invariant ``repro.analysis`` audits).
    """
    guards_on = guard_spec is not None
    gs = guard_spec if guards_on else GuardSpec()
    ridx = mdef.res_index
    nvec = len(mdef.vectors)
    dt = hist.dtype
    window = gs.stagnation_window if guards_on else 0
    rr0 = state[ridx]
    # divergence ceiling relative to the larger of ||r0||^2 and the stop
    # threshold, so near-converged starts don't trip it on noise
    div2 = (gs.divergence_factor ** 2) * jnp.maximum(
        rr0, jnp.asarray(thresh2, dtype=jnp.asarray(rr0).dtype))

    def _nan_scalars(state):
        bad = jnp.isnan(state[ridx])
        for s in state[nvec:]:
            bad = bad | jnp.isnan(s)
        return bad

    def _guard_fired(state):
        if mdef.guard is None:
            return jnp.asarray(False)
        return mdef.guard(ops, state, rr0, gs.breakdown_eps)

    def _scal_row(state):
        return jnp.stack([jnp.asarray(s).astype(dt) for s in state[nvec:]])

    carry = {"state": state, "k": 0, "hist": hist}
    if telemetry:
        cap = min(int(telemetry), maxiter + 1)
        tele = jnp.full((cap, len(mdef.scalars)), jnp.nan, dt)
        carry["tele"] = tele.at[0].set(_scal_row(state))
    if window:
        carry["best2"] = rr0
        carry["since"] = 0

    def cond(c):
        state, k = c["state"], c["k"]
        go = (state[ridx] >= thresh2) & (k < maxiter)
        if guards_on:
            # pre-step guards: a firing exits with the LAST FINITE iterate
            bad = _nan_scalars(state) | _guard_fired(state) \
                | (state[ridx] > div2)
            if window:
                bad = bad | (c["since"] >= window)
            go = go & ~bad
        return go

    def body(c):
        k = c["k"]
        state = tuple(step(ops, c["state"]))
        if refresh_every:
            state = lax.cond(
                (k + 1) % refresh_every == 0,
                lambda s: tuple(mdef.refresh(ops, x0, s)),
                lambda s: s, state)
        out = {"state": state, "k": k + 1,
               "hist": c["hist"].at[k + 1].set(
                   jnp.sqrt(state[ridx]).astype(dt))}
        if telemetry:
            cap = c["tele"].shape[0]
            out["tele"] = c["tele"].at[jnp.minimum(k + 1, cap - 1)].set(
                _scal_row(state))
        if window:
            res2 = state[ridx]
            improved = res2 < gs.stagnation_rtol * c["best2"]
            out["best2"] = jnp.minimum(res2, c["best2"])
            out["since"] = jnp.where(improved, 0, c["since"] + 1)
        return out

    fc = lax.while_loop(cond, body, carry)
    state, k, hist = fc["state"], fc["k"], fc["hist"]
    x = mdef.finalize(ops, x0, state) if mdef.finalize else state[0]
    res2 = state[ridx]
    nan_bad = _nan_scalars(state)
    i32 = jnp.int32
    status = jnp.asarray(STATUS_MAXITER, i32)
    if window:
        status = jnp.where(fc["since"] >= window,
                           jnp.asarray(STATUS_STAGNATED, i32), status)
    diverged = jnp.isinf(res2)
    if guards_on:
        diverged = diverged | (res2 > div2)
    status = jnp.where(diverged, jnp.asarray(STATUS_DIVERGED, i32), status)
    broke = nan_bad if not guards_on else (nan_bad | _guard_fired(state))
    status = jnp.where(broke, jnp.asarray(STATUS_BREAKDOWN, i32), status)
    status = jnp.where((res2 < thresh2) & ~nan_bad,
                       jnp.asarray(STATUS_CONVERGED, i32), status)
    return SolveResult(x=x, iters=k, res_norm=jnp.sqrt(res2), history=hist,
                       telemetry=fc.get("tele"), status=status)


# =============================================================================
# Krylov methods — conjugate gradients
# =============================================================================

def _rho_underflow_guard(rho_idx: int, rr_idx: int):
    """BiCGStab-family breakdown guard: ρ = (r̂, r) collapsing relative to
    ‖r̂‖‖r‖ ≈ ‖r₀‖‖r‖ means the shadow residual has become numerically
    orthogonal — the next β/α division amplifies noise into the iterate.
    Reads only carried post-psum scalars (flat-state indices are pinned by
    the declared vectors/scalars layouts)."""
    def guard(ops, state, rr0, eps):
        rho, rr = state[rho_idx], state[rr_idx]
        return rho * rho < (eps * eps) * rr0 * rr
    return guard


def _nonpositive_guard(idx: int):
    """Negative-curvature/indefiniteness guard for the CG family: the
    carried inner product at ``idx`` (p·Ap, r·z, w·r, ...) must stay
    positive on an SPD operator — a non-positive value means A (or M) is
    not SPD and the α division is about to change sign or blow up."""
    def guard(ops, state, rr0, eps):
        return state[idx] <= 0.0
    return guard


def _cg_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    rr = ops.dot(r, r)
    return (x0, r, r, rr)


def _cg_step(ops, state):
    """Classical CG (HPCCG reference): 2 blocking reductions."""
    x, r, p, rr = state
    Ap, pAp = ops.matvec_dot(p)       # blocking: feeds alpha immediately
    alpha = rr / pAp
    x = x + alpha * p
    r = r - alpha * Ap
    rr_new = ops.dot(r, r)            # blocking: feeds beta before next SpMV
    beta = rr_new / rr
    p = r + beta * p
    return (x, r, p, rr_new)


register_method(MethodDef(
    name="cg", vectors=("x", "r", "p"), scalars=("rr",), res_scalar="rr",
    init=_cg_init, step=_cg_step,
    reduction_hides=("none", "vec"), spmvs_per_iter=1, spd_required=True,
    description="classical conjugate gradient (2 blocking reductions)"))


def _cg_nb_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    Ap = ops.matvec(r)                # p_0 = r_0
    an = ops.dot(r, r)
    ad = ops.dot(Ap, r)
    return (x0, r, r, Ap, an, ad)


def _cg_nb_step(ops, state):
    """Nonblocking CG (paper Alg. 1): the SpMV is applied to ``r_j``;
    ``A·p_j`` is reconstructed as a vector update (line 6).  Both reductions
    are off the critical path: the dataflow successor of ``α_n = r·r`` is
    line 6 which *follows* the SpMV, and the successor of ``α_d`` is the
    *next* iteration's ``α``, past the lagged ``x`` update (line 9)."""
    x, r, p, Ap, an, ad = state
    alpha = an / ad                       # α_{j-1}
    r_new = r - alpha * Ap                # Tk 0 (line 4)
    an_new = ops.dot(r_new, r_new)        # Tk 0 (line 5) — reduction in flight...
    Ar = ops.matvec(r_new)                # ...overlapped with this SpMV
    beta = an_new / an
    Ap_new = Ar + beta * Ap               # Tk 1 & 2 (line 6) — no SpMV on p!
    p_new = r_new + beta * p              # Tk 2 (line 7)
    ad_new = ops.dot(Ap_new, p_new)       # Tk 2 (line 8) — overlapped with...
    x = x + alpha * p                     # Tk 3 (line 9, sign-fixed; uses OLD p)
    return (x, r_new, p_new, Ap_new, an_new, ad_new)


def _cg_nb_finalize(ops, x0, state):
    # the x update lags one iteration; apply the final correction term
    x, r, p, Ap, an, ad = state
    return x + (an / ad) * p


register_method(MethodDef(
    name="cg_nb", vectors=("x", "r", "p", "Ap"), scalars=("an", "ad"),
    res_scalar="an", init=_cg_nb_init, step=_cg_nb_step,
    finalize=_cg_nb_finalize, variant_of="cg",
    guard=_nonpositive_guard(5),        # ad = p·Ap: negative curvature
    reduction_hides=("spmv", "vec"), spmvs_per_iter=1, spd_required=True,
    description="nonblocking CG (Alg. 1): both reductions off the critical path"))


def _pcg_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    z = ops.M(r)
    rz = ops.dot(r, z)
    rr = ops.dot(r, r)
    return (x0, r, z, rz, rr)


def _pcg_step(ops, state):
    """Preconditioned CG; ``M`` must be SPD-preserving.  ``p·Ap`` and
    ``r·z`` block (the latter pair-fused with the check-only ``r·r``);
    the convergence check stays on the TRUE residual ``||r||``, so
    iteration counts are comparable with ``cg`` at the same tolerance.
    With ``M = I`` this is arithmetically identical to ``cg``."""
    x, r, p, rz, rr = state
    Ap, pAp = ops.matvec_dot(p)       # blocking: feeds alpha immediately
    alpha = rz / pAp
    x = x + alpha * p
    r = r - alpha * Ap
    z = ops.M(r)
    rz_new, rr_new = ops.dot2(r, z, r, r)   # blocking pair (r·r: check only)
    beta = rz_new / rz
    p = z + beta * p
    return (x, r, p, rz_new, rr_new)


register_method(MethodDef(
    name="pcg", vectors=("x", "r", "p"), scalars=("rz", "rr"),
    res_scalar="rr", init=_pcg_init, step=_pcg_step,
    variant_of="cg", accepts_precond=True,
    guard=_nonpositive_guard(3),        # rz = r·M⁻¹r: M or A not SPD
    reduction_hides=("none", "none", "vec"), spmvs_per_iter=1,
    spd_required=True,
    allreduces_per_iter=2,       # the (r·z, r·r) pair rides ONE psum (dot2)
    precond_applies_per_iter=1,
    description="preconditioned CG (repro.precond): p·Ap and r·z block, "
                "r·r feeds only the check; +0 reductions from the "
                "built-in preconditioners"))


def _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev):
    """β and the Saad-recurrence α of merged/pipelined CG.

    ``α = γ/(δ − βγ/α_prev)`` equals classical CG's ``γ/(p·Ap)`` in exact
    arithmetic; seeding ``γ_prev = inf, α_prev = 1`` makes the first pass
    degenerate to ``β = 0, α = γ/δ`` without a cond.
    """
    beta = gamma / gamma_prev
    alpha = gamma / (delta - beta * gamma / alpha_prev)
    return alpha, beta


def _merged_seed(ref):
    inf = jnp.asarray(jnp.inf, ref.dtype)
    one = jnp.asarray(1.0, ref.dtype)
    return inf, one


def _cg_merged_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    w = ops.matvec(r)
    gamma, delta = ops.dotn((r, r), (w, r))
    zero = jnp.zeros_like(ops.b)
    inf, one = _merged_seed(gamma)
    return (x0, r, zero, zero, w, gamma, delta, inf, one)


def _cg_merged_step(ops, state):
    """Merged-reduction CG (Chronopoulos–Gear): the SpMV is applied to ``r``
    (``w = A r``) and both scalars the iteration needs — ``γ = r·r`` and
    ``δ = w·r`` — come out of a single stacked reduction; ``p·Ap`` is
    recovered by the Saad recurrence.  ONE psum per iteration; one extra
    vector recurrence (``s = A p``) of memory traffic."""
    x, r, p, s, w, gamma, delta, gamma_prev, alpha_prev = state
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    p = r + beta * p
    s = w + beta * s                  # s = A p by recurrence — no SpMV on p
    x = x + alpha * p
    r = r - alpha * s
    w = ops.matvec(r)
    gamma_new, delta_new = ops.dotn((r, r), (w, r))   # the ONE reduction
    return (x, r, p, s, w, gamma_new, delta_new, gamma, alpha)


def _cg_merged_fused_init(ops, x0):
    # the initial residual uses the wrapped operator's (jnp) matvec — the
    # fused kernels take over from the first spmv_dots pass onward
    r = ops.b - ops.A.base.matvec(x0)
    w, delta, gamma = ops.A.spmv_dots(r)
    zero = jnp.zeros_like(ops.b)
    inf, one = _merged_seed(gamma)
    return (x0, r, zero, zero, w, gamma, delta, inf, one)


def _cg_merged_fused_step(ops, state):
    """The merged-CG iteration as TWO fused HBM passes (``ops.A`` is a
    ``PallasOp``): all four vector updates in one VMEM pass
    (``fused_cg_body``), then the SpMV + BOTH dot partials in another
    (``spmv_dots``; the partials ride one stacked psum under shard_map).
    Identical recurrence to :func:`_cg_merged_step` — iterates agree to
    machine precision (slab-ordered dot accumulation), pinned by
    tests/test_reduction_hiding.py."""
    x, r, p, s, w, gamma, delta, gamma_prev, alpha_prev = state
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    x, r, p, s = ops.A.cg_body(alpha, beta, x, r, p, s, w)     # pass 1
    w, delta_new, gamma_new = ops.A.spmv_dots(r)               # pass 2
    return (x, r, p, s, w, gamma_new, delta_new, gamma, alpha)


def _cg_merged_refresh(ops, x0, state):
    """Residual replacement (van der Vorst–Ye / Cools): recompute the TRUE
    residual from the iterate and rebuild every recurrence image (``s = A
    p``, ``w = A r``) and scalar from it, discarding accumulated drift.
    One stacked reduction, same shape as the step's own."""
    x, r, p, s, w, gamma, delta, gamma_prev, alpha_prev = state
    r = ops.b - ops.matvec(x)
    s = ops.matvec(p)
    w = ops.matvec(r)
    gamma, delta = ops.dotn((r, r), (w, r))
    return (x, r, p, s, w, gamma, delta, gamma_prev, alpha_prev)


register_method(MethodDef(
    name="cg_merged", vectors=("x", "r", "p", "s", "w"),
    scalars=("gamma", "delta", "gamma_prev", "alpha_prev"),
    res_scalar="gamma", init=_cg_merged_init, step=_cg_merged_step,
    variant_of="cg", reduce_hide="merged",
    fused_kernels=("cg_body", "spmv_dots"),
    fused_init=_cg_merged_fused_init, fused_step=_cg_merged_fused_step,
    guard=_nonpositive_guard(6),        # delta = r·Ar: A not SPD
    refresh=_cg_merged_refresh, refresh_spmvs=3,
    reduction_hides=("none",), spmvs_per_iter=1, spd_required=True,
    description="Chronopoulos–Gear CG: all dots in ONE stacked psum "
                "(Saad recurrence for p·Ap)"))


def _pcg_merged_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    u = ops.M(r)
    w = ops.matvec(u)
    gamma, delta, rr = ops.dotn((r, u), (w, u), (r, r))
    zero = jnp.zeros_like(ops.b)
    inf, one = _merged_seed(gamma)
    return (x0, r, u, zero, zero, w, gamma, delta, rr, inf, one)


def _pcg_merged_step(ops, state):
    """Merged-reduction PCG (Chronopoulos–Gear with ``u = M⁻¹r``); the
    TRUE-residual ``r·r`` rides in the same stacked reduction (3 scalars,
    ONE psum), so stopping matches ``pcg``.  ``M`` must be SPD-preserving."""
    x, r, u, p, s, w, gamma, delta, rr, gamma_prev, alpha_prev = state
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    p = u + beta * p
    s = w + beta * s
    x = x + alpha * p
    r = r - alpha * s
    u = ops.M(r)
    w = ops.matvec(u)
    gamma_new, delta_new, rr_new = ops.dotn((r, u), (w, u), (r, r))
    return (x, r, u, p, s, w, gamma_new, delta_new, rr_new, gamma, alpha)


def _pcg_merged_guard(ops, state, rr0, eps):
    # gamma = r·u (the M-inner product) and delta = u·Au must both stay
    # positive when A and M are SPD
    return (state[6] <= 0.0) | (state[7] <= 0.0)


def _pcg_merged_refresh(ops, x0, state):
    """Residual replacement for merged PCG: true r, fresh ``u = M⁻¹r`` and
    recurrence images, all scalars from one stacked reduction."""
    x, r, u, p, s, w, gamma, delta, rr, gamma_prev, alpha_prev = state
    r = ops.b - ops.matvec(x)
    u = ops.M(r)
    w = ops.matvec(u)
    s = ops.matvec(p)
    gamma, delta, rr = ops.dotn((r, u), (w, u), (r, r))
    return (x, r, u, p, s, w, gamma, delta, rr, gamma_prev, alpha_prev)


def _pcg_merged_fused_step(ops, state):
    """Merged PCG as fused HBM passes: all four vector updates in one VMEM
    pass (``pcg_body``), the preconditioner apply on its own (Pallas)
    kernels via ``ops.M``, then SpMV + the full reduction triple
    (``γ = r·u``, ``δ = w·u``, true ``r·r``) in one more pass
    (``spmv_dots3``, partials on one stacked psum).  Same recurrence as
    :func:`_pcg_merged_step`."""
    x, r, u, p, s, w, gamma, delta, rr, gamma_prev, alpha_prev = state
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    x, r, p, s = ops.A.pcg_body(alpha, beta, x, r, u, p, s, w)   # pass 1
    u = ops.M(r)                                   # precond (own kernels)
    w, delta_new, gamma_new, rr_new = ops.A.spmv_dots3(u, r)     # pass 2
    return (x, r, u, p, s, w, gamma_new, delta_new, rr_new, gamma, alpha)


register_method(MethodDef(
    name="pcg_merged", vectors=("x", "r", "u", "p", "s", "w"),
    scalars=("gamma", "delta", "rr", "gamma_prev", "alpha_prev"),
    res_scalar="rr", init=_pcg_merged_init, step=_pcg_merged_step,
    variant_of="pcg", reduce_hide="merged", accepts_precond=True,
    fused_kernels=("pcg_body", "spmv_dots3"),
    fused_init=_pcg_merged_init, fused_step=_pcg_merged_fused_step,
    guard=_pcg_merged_guard,
    refresh=_pcg_merged_refresh, refresh_spmvs=3,
    reduction_hides=("none",), spmvs_per_iter=1, spd_required=True,
    precond_applies_per_iter=1,
    description="merged-reduction PCG (Chronopoulos–Gear with M)"))


def _cg_pipe_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    w = ops.matvec(r)
    (rr0,) = ops.dotn((r, r))
    zero = jnp.zeros_like(ops.b)
    inf, one = _merged_seed(rr0)
    return (x0, r, w, zero, zero, zero, inf, one, rr0)


def _cg_pipe_step(ops, state):
    """Pipelined CG (Ghysels–Vanroose): the ONE stacked reduction is issued
    at the top of the body and the body's SpMV (``n = A w``, on carried
    state) is dataflow-independent of it — the latency-hiding scheduler
    runs the SpMV while the psum is in flight.  The ``optimization_barrier``
    pins the SpMV as its own schedulable task (the ``bicgstab_b1`` idiom).
    The freshest residual norm available to the check is the previous
    body's, so the method typically reports one more iteration than ``cg``;
    two extra vector recurrences (``s = A p``, ``z = A s``) pay for the
    hiding."""
    x, r, w, p, s, z, gamma_prev, alpha_prev, rr = state
    gamma, delta = ops.dotn((r, r), (w, r))           # issued...
    n = lax.optimization_barrier(ops.matvec(w))       # ...hidden behind this
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    z = n + beta * z                  # z = A s by recurrence
    s = w + beta * s                  # s = A p by recurrence
    p = r + beta * p
    x = x + alpha * p
    r = r - alpha * s
    w = w - alpha * z                 # w = A r by recurrence
    return (x, r, w, p, s, z, gamma, alpha, gamma)


def _cg_pipe_refresh(ops, x0, state):
    """Residual replacement for pipelined CG: the three recurrence chains
    (``w = A r``, ``s = A p``, ``z = A s``) all restart from the true
    residual; one extra SpMV each plus the lagged ``rr`` recomputed."""
    x, r, w, p, s, z, gamma_prev, alpha_prev, rr = state
    r = ops.b - ops.matvec(x)
    w = ops.matvec(r)
    s = ops.matvec(p)
    z = ops.matvec(s)
    (rr,) = ops.dotn((r, r))
    return (x, r, w, p, s, z, gamma_prev, alpha_prev, rr)


def _cg_pipe_fused_step(ops, state):
    """Pipelined CG as TWO fused HBM passes: the body's SpMV (``n = A w``)
    and BOTH reduction partials come out of one slab sweep
    (``spmv_dots3`` with ``x = w`` — its first partial ``(A w)·w`` is
    unused), then all six vector recurrences in one VMEM pass
    (``pipe_body``).  The latency overlap the unfused form schedules
    explicitly happens *inside* the sweep: partials accumulate while the
    stencil streams, and the stacked psum rides the kernel boundary."""
    x, r, w, p, s, z, gamma_prev, alpha_prev, rr = state
    n, _nw, delta, gamma = ops.A.spmv_dots3(w, r)                # pass 1
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    x, r, w, p, s, z = ops.A.pipe_body(
        alpha, beta, x, r, w, p, s, z, n)                        # pass 2
    return (x, r, w, p, s, z, gamma, alpha, gamma)


register_method(MethodDef(
    name="cg_pipe", vectors=("x", "r", "w", "p", "s", "z"),
    scalars=("gamma_prev", "alpha_prev", "rr"), res_scalar="rr",
    init=_cg_pipe_init, step=_cg_pipe_step,
    variant_of="cg", reduce_hide="pipelined",
    fused_kernels=("spmv_dots3", "pipe_body"),
    fused_init=_cg_pipe_init, fused_step=_cg_pipe_fused_step,
    refresh=_cg_pipe_refresh, refresh_spmvs=4,
    reduction_hides=("pipe",), spmvs_per_iter=1, spd_required=True,
    description="Ghysels–Vanroose pipelined CG: the ONE stacked psum "
                "overlaps the SpMV"))


def _pcg_pipe_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    u = ops.M(r)
    w = ops.matvec(u)
    (rr0,) = ops.dotn((r, r))
    zero = jnp.zeros_like(ops.b)
    inf, one = _merged_seed(rr0)
    return (x0, r, u, w, zero, zero, zero, zero, inf, one, rr0)


def _pcg_pipe_step(ops, state):
    """Pipelined PCG (Ghysels–Vanroose Alg. 3): the stacked reduction
    (``γ = r·u``, ``δ = w·u``, TRUE ``r·r`` — ONE psum) overlaps both the
    preconditioner apply ``m = M⁻¹w`` and the SpMV ``n = A m``.  Four extra
    recurrences (``s, q, z, u``); stopping lags one iteration like the
    unpreconditioned pipeline."""
    x, r, u, w, p, s, q, z, gamma_prev, alpha_prev, rr = state
    gamma, delta, rr_new = ops.dotn((r, u), (w, u), (r, r))   # issued...
    m = ops.M(w)                                  # ...hidden behind the
    n = lax.optimization_barrier(ops.matvec(m))   # apply and the SpMV
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    z = n + beta * z                  # z = A q by recurrence
    q = m + beta * q                  # q = M⁻¹ s by recurrence
    s = w + beta * s                  # s = A p by recurrence
    p = u + beta * p
    x = x + alpha * p
    r = r - alpha * s
    u = u - alpha * q                 # u = M⁻¹ r by recurrence
    w = w - alpha * z                 # w = A u by recurrence
    return (x, r, u, w, p, s, q, z, gamma, alpha, rr_new)


def _pcg_pipe_refresh(ops, x0, state):
    """Residual replacement for pipelined PCG: true r, fresh preconditioned
    images ``u = M⁻¹r``/``q = M⁻¹s`` and SpMV images rebuilt from them."""
    x, r, u, w, p, s, q, z, gamma_prev, alpha_prev, rr = state
    r = ops.b - ops.matvec(x)
    u = ops.M(r)
    w = ops.matvec(u)
    s = ops.matvec(p)
    q = ops.M(s)
    z = ops.matvec(q)
    (rr,) = ops.dotn((r, r))
    return (x, r, u, w, p, s, q, z, gamma_prev, alpha_prev, rr)


def _pcg_pipe_fused_step(ops, state):
    """Pipelined PCG as fused HBM passes: the reduction triple on carried
    state in one read pass (``fused_dots``), the preconditioner apply and
    SpMV on their own kernels, then all eight vector recurrences in one
    VMEM pass (``ppipe_body``).  Same recurrence as
    :func:`_pcg_pipe_step`."""
    x, r, u, w, p, s, q, z, gamma_prev, alpha_prev, rr = state
    gamma, delta, rr_new = ops.A.fused_dots(r, u, w)             # pass 1
    m = ops.M(w)                                   # precond (own kernels)
    n = ops.matvec(m)                                            # SpMV
    alpha, beta = _cg_merged_scalars(gamma, delta, gamma_prev, alpha_prev)
    x, r, u, w, p, s, q, z = ops.A.ppipe_body(
        alpha, beta, x, r, u, w, p, s, q, z, m, n)               # pass 2
    return (x, r, u, w, p, s, q, z, gamma, alpha, rr_new)


register_method(MethodDef(
    name="pcg_pipe", vectors=("x", "r", "u", "w", "p", "s", "q", "z"),
    scalars=("gamma_prev", "alpha_prev", "rr"), res_scalar="rr",
    init=_pcg_pipe_init, step=_pcg_pipe_step,
    variant_of="pcg", reduce_hide="pipelined", accepts_precond=True,
    fused_kernels=("fused_dots", "ppipe_body"),
    fused_init=_pcg_pipe_init, fused_step=_pcg_pipe_fused_step,
    refresh=_pcg_pipe_refresh, refresh_spmvs=4,
    reduction_hides=("pipe",), spmvs_per_iter=1, spd_required=True,
    precond_applies_per_iter=1,
    description="pipelined PCG: the stacked psum overlaps M-apply + SpMV"))


# =============================================================================
# Krylov methods — BiCGStab family
# =============================================================================

def _bicgstab_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    rho = ops.dot(r, r)               # r̂ = r_0 ⇒ ρ_0 = (r̂,r_0) = ‖r_0‖²
    return (x0, r, r, r, rho, rho)


def _bicgstab_step(ops, state):
    """Classical BiCGStab: 3 blocking reduction points per iteration (the
    ω pair and the ρ/‖r‖² pair each fused into one collective)."""
    x, r, rhat, p, rho, rr = state
    v = ops.matvec(p)
    rhat_v = ops.dot(rhat, v)             # barrier 1
    alpha = rho / rhat_v
    s = r - alpha * v
    t = ops.matvec(s)
    ts, tt = ops.dot2(t, s, t, t)         # barrier 2 (fused pair of dots)
    omega = ts / tt
    x = x + alpha * p + omega * s
    r = s - omega * t
    rho_new, rr_new = ops.dot2(rhat, r, r, r)   # barrier 3 (fused pair)
    beta = (rho_new / rho) * (alpha / omega)
    p = r + beta * (p - omega * v)
    return (x, r, rhat, p, rho_new, rr_new)


register_method(MethodDef(
    name="bicgstab", vectors=("x", "r", "rhat", "p"),
    scalars=("rho", "rr"), res_scalar="rr",
    init=_bicgstab_init, step=_bicgstab_step,
    guard=_rho_underflow_guard(4, 5),
    reduction_hides=("none", "none", "vec"), spmvs_per_iter=2,
    description="classical BiCGStab (3 blocking reductions)"))


def _pbicgstab_step(ops, state):
    """Right-preconditioned BiCGStab (``A M⁻¹ y = b``, ``x = M⁻¹ y``).
    Right preconditioning keeps ``r`` the TRUE residual, so stopping and
    iteration counts are directly comparable with ``bicgstab``; ``M`` need
    not be SPD-preserving.  Barrier structure unchanged (3 blocking
    reduction points) — the two ``M`` applies add stencil sweeps but no
    reductions for the built-in preconditioners."""
    x, r, rhat, p, rho, rr = state
    phat = ops.M(p)
    v = ops.matvec(phat)
    rhat_v = ops.dot(rhat, v)             # barrier 1
    alpha = rho / rhat_v
    s = r - alpha * v
    shat = ops.M(s)
    t = ops.matvec(shat)
    ts, tt = ops.dot2(t, s, t, t)         # barrier 2 (fused pair of dots)
    omega = ts / tt
    x = x + alpha * phat + omega * shat
    r = s - omega * t
    rho_new, rr_new = ops.dot2(rhat, r, r, r)   # barrier 3 (fused pair)
    beta = (rho_new / rho) * (alpha / omega)
    p = r + beta * (p - omega * v)
    return (x, r, rhat, p, rho_new, rr_new)


register_method(MethodDef(
    name="pbicgstab", vectors=("x", "r", "rhat", "p"),
    scalars=("rho", "rr"), res_scalar="rr",
    init=_bicgstab_init, step=_pbicgstab_step,
    variant_of="bicgstab", accepts_precond=True,
    guard=_rho_underflow_guard(4, 5),
    reduction_hides=("none", "none", "vec"), spmvs_per_iter=2,
    precond_applies_per_iter=2,
    description="right-preconditioned BiCGStab (true-residual stopping)"))


def _bicgstab_b1_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    beta_rr = ops.dot(r, r)                    # β_0 = r_0·r_0
    rhat = r / jnp.sqrt(beta_rr)               # r'
    an = ops.dot(r, rhat)                      # α_{n,0} = sqrt(β_0)
    return (x0, r, r, rhat, an, beta_rr)


def _bicgstab_b1_step(ops, state):
    """BiCGStab one-blocking (paper Alg. 2) with the restart procedure.

    Only ``α_d = (A·p)·r'`` blocks; ω's pair of reductions overlaps the
    ``x_{j+1/2}`` update (Tk 3) and the ``α_n``/``β`` pair overlaps the
    ``p_{j+1/2}`` update (Tk 5).  Restart (lines 13-15) triggers on
    ``sqrt(|α_n|) < ε_restart·||b||`` and re-orthogonalises ``r'``,
    eliminating the near-breakdown amplification (and, in the paper's task
    world, accumulated nondeterministic rounding).  ``ε_restart`` comes
    from ``ops.params`` (default 1e-5, paper §4.1)."""
    x, r, p, rhat, an, beta_rr = state
    restart_thresh = ops.params.get("eps_restart", 1e-5) * ops.norm_ref
    Ap = ops.matvec(p)
    ad = ops.dot(Ap, rhat)                # Tk 0 (line 3) — the ONE blocking reduction
    alpha = an / ad
    s = r - alpha * Ap                    # Tk 1 (line 4)
    As = ops.matvec(s)
    ts, tt = ops.dot2(As, s, As, As)      # Tk 2 (line 5) — overlapped with...
    # optimization_barrier = the Tk-3-is-its-own-task constraint: without
    # it XLA fuses this update into the omega-dependent x_{j+1} and the
    # overlap window vanishes (measured: slack 4096 -> 0 bytes)
    x_half = lax.optimization_barrier(x + alpha * p)   # ...Tk 3 (line 6)
    omega = ts / tt
    x_new = x_half + omega * s            # Tk 4 (line 8; == line 18 on exit)
    r_new = s - omega * As                # Tk 4 (line 9)
    an_new, beta_rr_new = ops.dot2(r_new, rhat, r_new, r_new)   # Tk 4 — ...
    p_half = lax.optimization_barrier(p - omega * Ap)  # ...overlaps Tk 5 (line 12)
    restart = jnp.sqrt(jnp.abs(an_new)) < restart_thresh
    p_reg = r_new + (an_new / (ad * omega)) * p_half   # Tk 7 (line 17)
    p_new = jnp.where(restart, r_new, p_reg)           # Tk 6 (line 14)
    rhat_new = jnp.where(restart, r_new / jnp.sqrt(beta_rr_new), rhat)  # line 15
    an_next = jnp.where(restart, jnp.sqrt(beta_rr_new), an_new)
    return (x_new, r_new, p_new, rhat_new, an_next, beta_rr_new)


register_method(MethodDef(
    name="bicgstab_b1", vectors=("x", "r", "p", "rhat"),
    scalars=("an", "beta_rr"), res_scalar="beta_rr",
    init=_bicgstab_b1_init, step=_bicgstab_b1_step,
    variant_of="bicgstab", params=("eps_restart",),
    reduction_hides=("none", "vec", "vec"), spmvs_per_iter=2,
    description="BiCGStab one-blocking (Alg. 2) with restart"))


def _merged_bicgstab_matvec(ops, preconditioned: bool):
    if not preconditioned:
        return ops.matvec
    return lambda v: ops.matvec(ops.M(v))


def _make_bicgstab_merged_init(preconditioned: bool):
    def init(ops, x0):
        mv = _merged_bicgstab_matvec(ops, preconditioned)
        r0 = ops.b - ops.matvec(x0)
        y0 = jnp.zeros_like(ops.b) if preconditioned else x0
        w = mv(r0)
        t = mv(w)
        rho, rhw = ops.dotn((r0, r0), (r0, w))   # r̂ = r0
        alpha = rho / rhw
        rr = rho                           # r̂ = r0 ⇒ (r̂,r0) = ‖r0‖²
        return (y0, r0, w, t, r0, w, t, r0, rho, alpha, rr)
    return init


def _make_bicgstab_merged_step(preconditioned: bool):
    def step(ops, state):
        mv = _merged_bicgstab_matvec(ops, preconditioned)
        y, r, w, t, p, s, z, rhat, rho, alpha, rr = state
        q = r - alpha * s                  # classical s_j
        yv = w - alpha * z                 # = A q
        v = lax.optimization_barrier(mv(z))          # SpMV 1 — independent...
        (qy, yy, qq, rhq, rhy, rht, rhv, rhz, rhs) = ops.dotn(   # ...of the
            (q, yv), (yv, yv), (q, q), (rhat, q), (rhat, yv),    # ONE psum
            (rhat, t), (rhat, v), (rhat, z), (rhat, s))
        omega = qy / yy
        y = y + alpha * p + omega * q
        r = q - omega * yv
        # recurrence-based ‖r‖² (the stability caveat in docs/API.md):
        # ‖q − ωy‖² from pre-update dots; clamp the rounding negatives.
        rr_new = jnp.maximum(qq - 2.0 * omega * qy + omega * omega * yy, 0.0)
        rho_new = rhq - omega * rhy
        beta = (rho_new / rho) * (alpha / omega)
        w = yv - omega * (t - alpha * v)   # = A r_new
        t = mv(w)                          # SpMV 2
        rhw = rhy - omega * (rht - alpha * rhv)      # (r̂, w_new)
        alpha_new = rho_new / (rhw + beta * (rhs - omega * rhz))
        p = r + beta * (p - omega * s)
        s = w + beta * (s - omega * z)     # = A p_new
        z = t + beta * (z - omega * v)     # = A s_new
        return (y, r, w, t, p, s, z, rhat, rho_new, alpha_new, rr_new)
    return step


_BICGSTAB_MERGED_DOC = """Single-reduction BiCGStab (cf. Cools–Vanroose).

Auxiliary images ``w = A r``, ``t = A w``, ``s = A p``, ``z = A s`` are
maintained by recurrence so that ω's pair, ρ, the α denominator
``r̂·(A p)`` and ‖r‖² are all linear in dots of vectors available BEFORE ω
— nine dots, ONE stacked psum per iteration.  Two SpMVs remain (``v = A z``
and ``t = A w_new``); ``v`` is dataflow-independent of the reduction, so
the scheduler can hide the psum behind it (the ``optimization_barrier``
pins it as its own task).  The preconditioned form runs the same core on
the right-preconditioned operator ``B = A∘M⁻¹`` with a zero initial guess
and recovers ``x = x0 + M⁻¹ y`` once at exit — the residual is unchanged
by right preconditioning, so stopping stays TRUE-residual."""


def _make_bicgstab_merged_fused_step(preconditioned: bool):
    def fused_step(ops, state):
        """Single-reduction BiCGStab as THREE fused HBM passes: SpMV 1
        (``v = A z̃``) + the intermediates ``q``/``y`` + all NINE dot
        partials in one slab sweep (``bicgstab_spmv_dots``; partials on
        the iteration's ONE stacked psum), the ω-half x/r/w updates in one
        VMEM pass (``bicgstab_update1``), then SpMV 2 fused with the three
        direction recurrences (``bicgstab_spmv_update``).  Identical
        recurrence to the unfused step; the preconditioned form applies
        ``M`` to each SpMV operand (right preconditioning)."""
        y, r, w, t, p, s, z, rhat, rho, alpha, rr = state
        zi = ops.M(z) if preconditioned else z
        v, q, yv, parts = ops.A.bicgstab_spmv_dots(
            zi, z, r, w, s, rhat, t, alpha)                      # pass 1
        qy, yy, qq, rhq, rhy, rht, rhv, rhz, rhs = parts
        omega = qy / yy
        rr_new = jnp.maximum(qq - 2.0 * omega * qy + omega * omega * yy, 0.0)
        rho_new = rhq - omega * rhy
        beta = (rho_new / rho) * (alpha / omega)
        y, r, w = ops.A.bicgstab_update1(
            alpha, omega, y, p, q, yv, t, v)                     # pass 2
        wi = ops.M(w) if preconditioned else w
        t, p, s, z = ops.A.bicgstab_spmv_update(
            wi, w, r, p, s, z, v, omega, beta)                   # pass 3
        rhw = rhy - omega * (rht - alpha * rhv)
        alpha_new = rho_new / (rhw + beta * (rhs - omega * rhz))
        return (y, r, w, t, p, s, z, rhat, rho_new, alpha_new, rr_new)
    return fused_step


def _pbicgstab_merged_finalize(ops, x0, state):
    # the loop iterates in the preconditioned ŷ space; recover x once
    return x0 + ops.M(state[0])


def _make_bicgstab_merged_refresh(preconditioned: bool):
    def refresh(ops, x0, state):
        """Residual replacement for single-reduction BiCGStab: recover the
        TRUE residual from the iterate (via ``finalize`` in the
        preconditioned ŷ space), rebuild every recurrence image ``w,t,s,z``
        from it and recompute ρ, α and ‖r‖² in one stacked reduction."""
        mv = _merged_bicgstab_matvec(ops, preconditioned)
        y, r, w, t, p, s, z, rhat, rho, alpha, rr = state
        x = x0 + ops.M(y) if preconditioned else y
        r = ops.b - ops.matvec(x)
        w = mv(r)
        t = mv(w)
        s = mv(p)
        z = mv(s)
        rho, rr, rhs = ops.dotn((rhat, r), (r, r), (rhat, s))
        alpha = rho / rhs                  # α = ρ / r̂·(B p)
        return (y, r, w, t, p, s, z, rhat, rho, alpha, rr)
    return refresh


register_method(MethodDef(
    name="bicgstab_merged",
    vectors=("x", "r", "w", "t", "p", "s", "z", "rhat"),
    scalars=("rho", "alpha", "rr"), res_scalar="rr",
    init=_make_bicgstab_merged_init(False),
    step=_make_bicgstab_merged_step(False),
    variant_of="bicgstab", reduce_hide="merged",
    fused_kernels=("bicgstab_spmv_dots", "bicgstab_update1",
                   "bicgstab_spmv_update"),
    fused_init=_make_bicgstab_merged_init(False),
    fused_step=_make_bicgstab_merged_fused_step(False),
    guard=_rho_underflow_guard(8, 10),
    refresh=_make_bicgstab_merged_refresh(False), refresh_spmvs=5,
    reduction_hides=("none",), spmvs_per_iter=2,
    description="single-reduction BiCGStab: nine dots, ONE stacked psum "
                "(Cools–Vanroose recurrences)"))

register_method(MethodDef(
    name="pbicgstab_merged",
    vectors=("x", "r", "w", "t", "p", "s", "z", "rhat"),
    scalars=("rho", "alpha", "rr"), res_scalar="rr",
    init=_make_bicgstab_merged_init(True),
    step=_make_bicgstab_merged_step(True),
    finalize=_pbicgstab_merged_finalize,
    variant_of="pbicgstab", reduce_hide="merged", accepts_precond=True,
    fused_kernels=("bicgstab_spmv_dots", "bicgstab_update1",
                   "bicgstab_spmv_update"),
    fused_init=_make_bicgstab_merged_init(True),
    fused_step=_make_bicgstab_merged_fused_step(True),
    guard=_rho_underflow_guard(8, 10),
    refresh=_make_bicgstab_merged_refresh(True), refresh_spmvs=5,
    reduction_hides=("none",), spmvs_per_iter=2,
    precond_applies_per_iter=2,
    description="right-preconditioned single-reduction BiCGStab "
                "(merged core on A∘M⁻¹, true-residual stopping)"))


# =============================================================================
# Stationary methods
# =============================================================================

def _jacobi_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    rr = ops.dot(r, r)
    return (x0, r, rr)


def _jacobi_step(ops, state):
    """Jacobi: x += D⁻¹ r; one SpMV + one reduction per iteration."""
    x, r, rr = state
    x = x + r / ops.diag
    r = ops.b - ops.matvec(x)
    rr = ops.dot(r, r)
    return (x, r, rr)


register_method(MethodDef(
    name="jacobi", vectors=("x", "r"), scalars=("rr",), res_scalar="rr",
    init=_jacobi_init, step=_jacobi_step, stationary=True,
    default_maxiter=1000,
    reduction_hides=("none",), spmvs_per_iter=1,
    description="x += D^-1 r; 1 SpMV + 1 blocking residual reduction"))


def _plane_sweep(A, b, x, *, forward: bool) -> jax.Array:
    """One relaxed Gauss-Seidel sweep: GS-fresh across z-planes, Jacobi within
    a plane, stale across device blocks (halos exchanged once per sweep)."""
    nz = x.shape[2]

    def step(i, xp):
        k = i if forward else nz - 1 - i
        off = A.stencil.plane_offdiag_apply(xp, k)
        plane = (b[:, :, k] - off) / A.diag
        return lax.dynamic_update_slice(xp, plane[:, :, None], (1, 1, k + 1))

    xp = A.pad_exchange(x)
    xp = lax.fori_loop(0, nz, step, xp)
    return xp[1:-1, 1:-1, 1:-1]


def _stationary_init(ops, x0):
    r = ops.b - ops.matvec(x0)
    rr = ops.dot(r, r)
    return (x0, rr)


def _gauss_seidel_step(ops, state):
    """Relaxed symmetric GS (paper §3.4 Code 4, TPU adaptation): forward
    sweep (ascending z-planes) then backward sweep (descending), each using
    the freshest available plane values — the deterministic analogue of the
    paper's benign data races that "mimic the Gauss-Seidel behaviour"."""
    x, rr = state
    x = _plane_sweep(ops.A, ops.b, x, forward=True)
    x = _plane_sweep(ops.A, ops.b, x, forward=False)
    r = ops.b - ops.matvec(x)
    rr = ops.dot(r, r)
    return (x, rr)


def _colour_mask(shape: tuple[int, int, int], colour: int) -> jax.Array:
    i = lax.broadcasted_iota(jnp.int32, shape, 0)
    j = lax.broadcasted_iota(jnp.int32, shape, 1)
    k = lax.broadcasted_iota(jnp.int32, shape, 2)
    return ((i + j + k) % 2) == colour


def _rb_half_sweep(A, b, x, colour_mask) -> jax.Array:
    off = A.stencil.offdiag_apply_padded(A.pad_exchange(x))
    return jnp.where(colour_mask, (b - off) / A.diag, x)


def _gauss_seidel_rb_step(ops, state):
    """Red-black coloured symmetric GS (paper §3.4): forward = red, black;
    backward = black, red.  Exact GS reordering for the 7-pt stencil
    (bipartite); a coloured relaxation for the 27-pt one, with
    correspondingly different convergence (the effect the paper measures)."""
    x, rr = state
    red = _colour_mask(x.shape, 0)
    black = _colour_mask(x.shape, 1)
    x = _rb_half_sweep(ops.A, ops.b, x, red)      # forward
    x = _rb_half_sweep(ops.A, ops.b, x, black)
    x = _rb_half_sweep(ops.A, ops.b, x, black)    # backward
    x = _rb_half_sweep(ops.A, ops.b, x, red)
    r = ops.b - ops.matvec(x)
    rr = ops.dot(r, r)
    return (x, rr)


# The sweeps read their halos at the first plane/colour, so neither SpMV's
# exchange hides (halo_hides "none"), and each half-sweep exchanges its own.
register_method(MethodDef(
    name="gauss_seidel_rb", vectors=("x",), scalars=("rr",),
    res_scalar="rr", init=_stationary_init, step=_gauss_seidel_rb_step,
    stationary=True, default_maxiter=1000,
    reduction_hides=("none",), spmvs_per_iter=2,
    halo_hides=("none", "none"),
    halo_exchanges_per_iter=5,   # 4 colour half-sweeps + the residual matvec
    description="red-black coloured symmetric Gauss-Seidel (§3.4)"))

register_method(MethodDef(
    name="gauss_seidel", vectors=("x",), scalars=("rr",),
    res_scalar="rr", init=_stationary_init, step=_gauss_seidel_step,
    variant_of="gauss_seidel_rb", stationary=True, default_maxiter=1000,
    reduction_hides=("none",), spmvs_per_iter=2,
    halo_hides=("none", "none"),
    halo_exchanges_per_iter=3,   # fwd + bwd plane sweeps + residual matvec
    description="relaxed tasked symmetric GS (§3.4 Code 4, TPU adaptation)"))
