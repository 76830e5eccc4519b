"""Per-iteration TPU time model for the solver scaling figures.

The paper measures wall-clock on MareNostrum4; this repo targets TPU v5e and
derives the same *relative efficiency* curves from the roofline terms (the
container is CPU-only — DESIGN.md §7).  Model per iteration and device:

  T = T_mem + T_halo + T_precond + Σ_r max(0, Λ(n) - hide_r)

  * T_mem   — the method's touched-elements traffic / HBM bandwidth (the
              paper's own §3.1 memory model; solvers are memory-bound),
  * T_halo  — nearest-neighbour face exchange per SpMV over ICI; with
              ``halo_mode="overlap"`` each registry-marked SpMV's exchange
              hides behind its interior apply and only the excess
              max(0, t_halo - t_spmv) stays on the critical path,
  * T_precond — the preconditioner applies' traffic + any halo exchanges
              they perform (from the repro.precond metadata: applies/iter
              come from the registry, per-apply touched elements and halo
              matvecs from the Preconditioner instance; block-Jacobi is
              communication-free, SSOR's half-sweep exchanges cannot hide).
              No reduction term: the built-ins add zero reductions — that
              is the subsystem's design constraint,
  * Λ(n)    — all-reduce latency, λ·ceil(log2 chips)·(1+noise·log2 chips):
              the noise term models the system-noise amplification the paper
              measures (Allreduce 1e-5 s in isolation vs 1e-3 s in
              application context, §4.2),
  * hide_r  — the overlap window of reduction r (0 for blocking reductions;
              the SpMV or vector-update time for reductions the variant
              overlaps, per §3.1's own overlap condition; the SpMV + M-apply
              for the "pipe" kind — the pipelined variants' single stacked
              reduction rides behind the body's SpMV, see ``t_reduce``).

The merged variants (cg_merged & co., reduce_hide="merged") pay Λ(n) ONCE
per iteration instead of 2–3 times; the pipelined ones (cg_pipe/pcg_pipe)
additionally hide that one payment behind the SpMV — their curves in
fig3/fig56 are flat in Λ until Λ(n) exceeds a whole SpMV.

Validated against the dry-run solver cells at 256/512 chips (roofline.py
cross-checks hlo_bytes against this T_mem within the f32-legalisation factor).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

from benchmarks.common import ALLREDUCE_LAT, HBM_BW, ICI_BW
from repro.api.registry import REGISTRY
from repro.core.operators import touched_elements_per_iter

# Noise regimes: per-log2-stage amplification of collective latency.
#   "tpu"   — synchronous SPMD fabric, negligible OS jitter (ICI),
#   "noisy" — the paper's MPI-cluster regime: calibrated so a 3072-rank
#             all-reduce costs ~1.1 ms, matching §4.2's measured 1e-3 s
#             ("up to two orders of magnitude larger than the minimum
#             latency" of 1e-5 s).
NOISE = {"tpu": 0.03, "noisy": 1.5}


@dataclass(frozen=True)
class MethodModel:
    name: str
    n_spmv: int               # SpMVs per iteration
    reductions: tuple         # per reduction: hide window kind
    # hide kinds: "none" (blocking), "spmv", "vec" (one vector update)
    halo_hides: tuple = ()    # per SpMV: "interior" (overlappable) | "none"
    precond_applies: int = 0  # M^{-1} applications per iteration
    refresh_spmvs: int = 0    # SpMV-equivalents per residual replacement


#: derived from the solver registry — the per-iteration communication
#: structure is method metadata, declared once in repro.api.registry.
METHODS = {
    name: MethodModel(name, spec.spmvs_per_iter,
                      tuple((h,) for h in spec.reduction_hides),
                      spec.halo_hides, spec.precond_applies_per_iter,
                      getattr(spec.method_def, "refresh_spmvs", 0))
    for name, spec in REGISTRY.items()
}


def iteration_breakdown(method: str, nbar: int,
                        local_grid: tuple[int, int, int],
                        chips: int, *, dtype_bytes: int = 8,
                        decomposition: str = "1d", noise: str = "tpu",
                        execution: str = "dataflow",
                        halo_mode: str = "concat",
                        precond: str | None = None,
                        precond_params: dict | None = None,
                        refresh_every: int = 0) -> dict:
    """``execution``: "mpi" = every reduction blocks (the paper's MPI-only
    baseline); "dataflow" = reductions hide behind their overlap windows
    (what the task runtime buys in the paper / XLA buys here).

    ``halo_mode="overlap"`` additionally hides each SpMV's halo exchange
    behind its interior apply (the interior/shell split of
    ``DistributedOp._matvec_overlap``) for the SpMVs the registry marks
    ``halo_hides="interior"`` — the Gauss-Seidel sweeps read their halos at
    the first plane/colour and stay exposed.  Under ``execution="mpi"``
    halos block regardless (the paper's fork-join exchange_externals).

    ``precond`` adds the t_precond term for the methods that apply one
    (``REGISTRY[...].precond_applies_per_iter``): per apply, the
    preconditioner's touched-elements traffic plus its halo exchanges
    (hidden like a regular SpMV's when the instance marks them
    ``halo_hide="interior"`` and overlap is on).  This prices ONE
    iteration; the payoff — fewer iterations — is the other axis of the
    trade-off (see benchmarks/table_iterations.py for measured counts).

    ``refresh_every`` prices residual replacement (repro.resilience: the
    merged/pipelined drift mitigation, ``SolverOptions.residual_replacement``)
    as an amortised per-iteration term ``t_rr``: every N-th iteration pays
    the method's ``refresh_spmvs`` SpMV-equivalents (memory + halo, never
    hidden — the refresh sits on the critical path by construction) plus
    one blocking stacked reduction to re-derive the recurrence scalars.
    0 (the default) or a method with no refresh hook prices as 0.

    Returns the per-phase split ``{"t_mem", "t_halo", "t_precond",
    "t_reduce", "t_rr", "total"}``; :func:`iteration_time` is its
    ``total``.
    """
    r = local_grid[0] * local_grid[1] * local_grid[2]
    m = METHODS[method]
    touched = touched_elements_per_iter(method, nbar)
    t_mem = touched * r * dtype_bytes / HBM_BW
    t_vec = 3 * r * dtype_bytes / HBM_BW          # one z = ax+by update
    t_spmv = (nbar + 2) * r * dtype_bytes / HBM_BW
    # halo: 1-D decomposition exchanges 2 faces per SpMV
    if decomposition == "1d":
        face = local_grid[0] * local_grid[1] * dtype_bytes
        t_halo_spmv = 2 * face / ICI_BW if chips > 1 else 0.0
    else:  # 3-D blocks: surface scales with block^(2/3)
        face = (r ** (2 / 3)) * dtype_bytes
        t_halo_spmv = 6 * face / ICI_BW if chips > 1 else 0.0
    t_halo = 0.0
    for halo_hide in m.halo_hides:
        if (halo_mode == "overlap" and execution == "dataflow"
                and halo_hide == "interior"):
            # the interior apply (~the whole SpMV's HBM traffic) runs while
            # the ppermutes fly; only the excess stays on the critical path
            t_halo += max(0.0, t_halo_spmv - t_spmv)
        else:
            t_halo += t_halo_spmv
    # preconditioner applies (pcg family: 1, pbicgstab family: 2, else 0)
    t_pre_apply = 0.0
    if precond not in (None, "none") and m.precond_applies:
        from repro.precond import make_precond
        inst = make_precond(precond, **(precond_params or {}))
        t_pre_apply = (inst.touched_elements_per_apply(nbar) * r * dtype_bytes
                       / HBM_BW)
        for _ in range(inst.halo_matvecs_per_apply):
            if (halo_mode == "overlap" and execution == "dataflow"
                    and inst.halo_hide == "interior"):
                t_pre_apply += max(0.0, t_halo_spmv - t_spmv)
            else:
                t_pre_apply += t_halo_spmv
    t_pre = t_pre_apply * m.precond_applies
    # reductions — the t_reduce hide term: per reduction, the all-reduce
    # latency Λ(n) minus the variant's overlap window.  "pipe" is the
    # Ghysels–Vanroose window: the pipelined stacked psum rides behind the
    # body's SpMV plus (for pcg_pipe) the preconditioner apply it also
    # overlaps — structurally the same trick halo_mode="overlap" plays for
    # the ppermutes, applied to the global reduction.
    t_red = t_reduce(m, chips, noise=noise, execution=execution,
                     t_vec=t_vec, t_spmv=t_spmv, t_pre_apply=t_pre_apply)
    # residual replacement, amortised over its period: refresh_spmvs
    # un-hidden SpMVs + one blocking stacked reduction every N iterations
    t_rr = 0.0
    if refresh_every > 0 and m.refresh_spmvs:
        t_rr = (m.refresh_spmvs * (t_spmv + t_halo_spmv)
                + reduction_latency(chips, noise=noise)) / refresh_every
    return {"t_mem": t_mem, "t_halo": t_halo, "t_precond": t_pre,
            "t_reduce": t_red, "t_rr": t_rr,
            "total": t_mem + t_halo + t_pre + t_red + t_rr}


def iteration_time(method: str, nbar: int, local_grid: tuple[int, int, int],
                   chips: int, **kw) -> float:
    """Total modelled per-iteration time — ``iteration_breakdown(...)``
    summed (see that function for the knobs and the model)."""
    return iteration_breakdown(method, nbar, local_grid, chips, **kw)["total"]


def reduction_latency(chips: int, *, noise: str = "tpu") -> float:
    """Λ(n): modelled all-reduce latency at ``chips`` devices."""
    if chips <= 1:
        return 0.0
    stages = math.ceil(math.log2(chips))
    return ALLREDUCE_LAT * stages * (1 + NOISE[noise] * stages)


def t_reduce(m: MethodModel, chips: int, *, noise: str, execution: str,
             t_vec: float, t_spmv: float, t_pre_apply: float = 0.0) -> float:
    """The per-iteration reduction term: Σ_r max(0, Λ(n) − hide_r).

    Hide windows per kind: "none" 0, "vec" one vector update, "spmv" the
    SpMV, "pipe" the SpMV + preconditioner apply the pipelined stacked
    reduction overlaps.  Under ``execution="mpi"`` every reduction blocks
    (the paper's fork-join baseline).
    """
    if chips <= 1:
        return 0.0
    lat = reduction_latency(chips, noise=noise)
    total = 0.0
    for (kind,) in m.reductions:
        if execution == "mpi":
            hide = 0.0
        else:
            hide = {"none": 0.0, "vec": t_vec, "spmv": t_spmv,
                    "pipe": t_spmv + t_pre_apply}[kind]
        total += max(0.0, lat - hide)
    return total


def weak_efficiency(method: str, nbar: int, chips: int,
                    local=(128, 128, 128), **kw) -> float:
    """T(1)/T(n) at constant per-chip work (the paper's Fig. 3/4 metric)."""
    t1 = iteration_time(method, nbar, local, 1, **kw)
    tn = iteration_time(method, nbar, local, chips, **kw)
    return t1 / tn


def strong_efficiency(method: str, nbar: int, chips: int,
                      global_grid=(128, 128, 6144), **kw) -> float:
    t1 = iteration_time(method, nbar, global_grid, 1, **kw)
    local = (global_grid[0], global_grid[1], max(global_grid[2] // chips, 1))
    tn = iteration_time(method, nbar, local, chips, **kw)
    return t1 / (chips * tn)
