"""Predicted-vs-measured cost attribution per (method, mesh, halo_mode).

The scaling model (``benchmarks/scaling_model.py``) *predicts* where an
iteration's time goes — memory-bound compute, halo exchange, global
reductions.  This module *measures* the same split with the existing step
machinery and reports both side by side, so model drift is a first-class,
inspectable number instead of a vibe:

  * ``t_iter``    — one full solver iteration, the method's
                    ``MethodDef.step`` lowered standalone by
                    ``solve_step_shardmap`` (trip-count-exact, the same
                    machinery the dry-run costs);
  * ``t_halo``    — the halo-assembly phase: ``DistributedOp.pad_exchange``
                    (ppermutes + concat/scatter assembly) in isolation,
                    times the registry's ``halo_exchanges_per_iter``;
  * ``t_reduce``  — the reduction phase: one global ``psum`` dot in
                    isolation, times ``allreduces_per_iter``;
  * ``t_compute`` — the remainder ``t_iter - t_halo - t_reduce`` (interior
                    compute; can dip negative on a noisy host — it is
                    reported raw so the three phases always sum to
                    ``t_iter`` exactly).

Each micro-phase runs ``inner`` trips inside one compiled ``fori_loop``
behind ``lax.optimization_barrier`` (no loop-invariant hoisting), timed as
a min over repeats — kernels, not container noise (the bench_kernels
convention).  ``jax.profiler`` trace hooks are available via
``profile_dir`` for a device-level timeline next to the numbers.

Caveat: the model prices TPU v5e (``benchmarks/common.py`` constants); on
the CPU containers that run CI the drift ratios are dominated by the
hardware mismatch and only the *relative* split is meaningful.  On the
target hardware the drift column is the tuning signal.

CLI: ``python -m repro.obs attribute --devices 8 --methods cg cg_merged
cg_pipe`` (runs the measurement; also emits ``obs.attribution`` metric
records to the active trace) or ``python -m repro.obs attribute
TRACE.jsonl`` (re-render a table from a trace that carries such records).
"""

from __future__ import annotations

import contextlib
import time

from repro.obs import trace as _trace


def _time_min(fn, args, *, repeats: int) -> float:
    """Min-over-repeats wall time of ``fn(*args)``, compile outside."""
    import jax
    jax.block_until_ready(fn(*args))           # warm-up / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _phase_fns(problem, method: str, mesh, *, halo_mode: str, inner: int):
    """(step_chain, halo_chain, reduce_chain, layout) — each a jitted fn
    over global arrays running ``inner`` trips of one phase."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import (DistributedOp, init_step_state,
                                        make_layout, solve_step_shardmap)
    from repro.core.solvers import LocalOp

    layout = make_layout(mesh, None)
    stencil = problem.stencil
    spec = layout.spec()

    step_fn, _ = solve_step_shardmap(problem, method, mesh,
                                     halo_mode=halo_mode)

    @jax.jit
    def step_chain(b, *state):
        for _ in range(inner):
            state = step_fn(b, *state)
        return state

    def local_halo(x_loc):
        op = DistributedOp(stencil, layout, halo_mode=halo_mode)

        def body(_, x):
            xp = op.pad_exchange(lax.optimization_barrier(x))
            return xp[1:-1, 1:-1, 1:-1]

        return lax.fori_loop(0, inner, body, x_loc)

    halo_chain = jax.jit(jax.shard_map(local_halo, mesh=mesh, in_specs=(spec,),
                                   out_specs=spec))

    def local_reduce(x_loc):
        op = DistributedOp(stencil, layout, halo_mode=halo_mode)

        def body(_, c):
            x, acc = c
            xb = lax.optimization_barrier(x)
            return (x, acc + op.dot(xb, xb))

        return lax.fori_loop(0, inner, body,
                             (x_loc, jnp.zeros((), x_loc.dtype)))[1]

    reduce_chain = jax.jit(jax.shard_map(local_reduce, mesh=mesh,
                                     in_specs=(spec,), out_specs=P()))

    state0 = init_step_state(method, LocalOp(stencil), problem.b(),
                             problem.x0())
    return step_chain, halo_chain, reduce_chain, layout, state0


def predicted_split(method: str, problem, mesh, layout, *,
                    halo_mode: str) -> dict:
    """The scaling model's per-phase prediction for this (method, mesh,
    halo_mode) — ``benchmarks.scaling_model.iteration_breakdown`` with the
    mesh translated to its chips/local-grid/decomposition terms."""
    from benchmarks.scaling_model import iteration_breakdown

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    local = tuple(
        g // (axes[a] if a is not None else 1)
        for g, a in zip(problem.shape, layout.dim_axes))
    ndim = sum(a is not None for a in layout.dim_axes)
    chips = int(mesh.devices.size)
    return iteration_breakdown(
        method, problem.stencil.npoint, local, chips,
        decomposition="1d" if ndim <= 1 else "3d",
        halo_mode="overlap" if halo_mode == "overlap" else "concat")


def measure_phase_split(problem, method: str, mesh, *,
                        halo_mode: str = "concat", inner: int = 8,
                        repeats: int = 5, profile_dir: str | None = None
                        ) -> dict:
    """One attribution row: measured t_iter/t_halo/t_reduce/t_compute next
    to the model's prediction.  Emits the row as an ``obs.attribution``
    metric record to the active trace (if any)."""
    import jax

    from repro.api.registry import get_solver

    spec = get_solver(method)
    with _trace.span("attribute.measure", method=method,
                     halo_mode=halo_mode):
        step_chain, halo_chain, reduce_chain, layout, state0 = _phase_fns(
            problem, method, mesh, halo_mode=halo_mode, inner=inner)
        prof = (jax.profiler.trace(profile_dir) if profile_dir
                else contextlib.nullcontext())
        with prof:
            t_iter = _time_min(step_chain, state0, repeats=repeats) / inner
            x = problem.b()
            t_halo1 = _time_min(halo_chain, (x,), repeats=repeats) / inner
            t_red1 = _time_min(reduce_chain, (x,), repeats=repeats) / inner
    n_halo = spec.halo_exchanges_per_iter
    n_red = spec.allreduces_per_iter
    t_halo = n_halo * t_halo1
    t_red = n_red * t_red1
    pred = predicted_split(method, problem, mesh, layout,
                           halo_mode=halo_mode)
    row = {
        "method": method,
        "halo_mode": halo_mode,
        "grid": list(problem.shape),
        "mesh": {"axes": list(mesh.axis_names),
                 "shape": list(mesh.devices.shape),
                 "devices": int(mesh.devices.size)},
        "counts": {"halo_exchanges": n_halo, "allreduces": n_red},
        "measured": {
            "t_iter": t_iter,
            "t_halo": t_halo,
            "t_reduce": t_red,
            # raw remainder: the three phases sum to t_iter EXACTLY
            "t_compute": t_iter - t_halo - t_red,
        },
        "predicted": pred,
        "drift": {
            "total": t_iter / pred["total"] if pred["total"] else None,
            "halo": t_halo / pred["t_halo"] if pred["t_halo"] else None,
            "reduce": t_red / pred["t_reduce"] if pred["t_reduce"] else None,
        },
    }
    _trace.emit(_trace.make_metric("obs.attribution", **row))
    return row


def attribution_report(methods, grid, mesh, *, halo_mode: str = "concat",
                       inner: int = 8, repeats: int = 5,
                       profile_dir: str | None = None) -> list[dict]:
    """Attribution rows for several methods on one mesh."""
    import jax
    import jax.numpy as jnp

    from repro.core.problems import make_problem

    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    problem = make_problem(tuple(grid), "27pt", dtype=dtype)
    return [measure_phase_split(problem, m, mesh, halo_mode=halo_mode,
                                inner=inner, repeats=repeats,
                                profile_dir=profile_dir)
            for m in methods]


def _us(v) -> str:
    return "      -" if v is None else f"{v * 1e6:10.1f}"


def format_table(rows: list[dict]) -> str:
    """The predicted-vs-measured table (times in microseconds/iteration).
    ``meas``/``pred`` column pairs per phase; ``drift`` = measured/predicted
    total."""
    head = (f"{'method':<18} {'halo':<8} "
            f"{'iter_us':>10} {'comp_us':>10} "
            f"{'halo_us':>10} {'halo_pred':>10} "
            f"{'red_us':>10} {'red_pred':>10} "
            f"{'pred_us':>10} {'drift':>8}")
    lines = [head, "-" * len(head)]
    for r in rows:
        m, p, d = r["measured"], r["predicted"], r["drift"]
        drift = f"{d['total']:8.1f}x" if d["total"] else "       -"
        lines.append(
            f"{r['method']:<18} {r['halo_mode']:<8} "
            f"{_us(m['t_iter'])} {_us(m['t_compute'])} "
            f"{_us(m['t_halo'])} {_us(p['t_halo'])} "
            f"{_us(m['t_reduce'])} {_us(p['t_reduce'])} "
            f"{_us(p['total'])} {drift}")
    return "\n".join(lines)


def rows_from_trace(records: list[dict]) -> list[dict]:
    """Recover attribution rows from a trace's ``obs.attribution`` metric
    records (the ``attribute TRACE.jsonl`` re-render path)."""
    return [r["attrs"] for r in records
            if r.get("kind") == "metric" and r.get("name") == "obs.attribution"]
