#!/usr/bin/env python3
"""Bring-up smoke test: the solver's main path on a TPU, checked.

    python chip_smoke.py               # one chip, phases a-d
    python chip_smoke.py --four-chips  # four chips: the sharded solve only

One process drives the chip; nothing here starts another interpreter.
Every phase goes through the entry points a user calls (``SolverSession``
built from the ``configs/hpcg.py`` cells, ``repro.launch.solve.main`` and
``repro.serve``) and is checked against the true relative residual
``‖b − A x‖ / ‖b‖``, computed in float64 with the ``jnp`` stencil reference
(``Stencil.matvec``), not with the solver's own operator.

  a  ``hpcg-cg-27pt`` at its config size: 128³, float64, XLA.
  b  chip-filling 27-point solves at 512³ float32: ``cg``, then ``bicgstab``.
  c  the fused Pallas path: ``cg_merged`` with ``pallas=True`` at 256³
     float32, against the XLA ``cg_merged`` solve of the same problem.
  d  serving: 8 requests through ``repro.serve`` in two 128³ float64
     buckets, ``cg`` and ``pcg`` + Chebyshev.

``--four-chips`` runs ``hpcg-cg-27pt`` weak-scaled to 128×128×512 (128³
per chip) on the 1-D and the 2-D (2×2) layouts, against the same problem
solved on one chip.

A phase fails on any exception, a rejected request, a solve that ends in
any status but converged, a residual above its tolerance, or a fallback
rung of the session's recovery ladder (a ``resilience.attempt`` span in
the trace).  The last line of stdout, printed only when every phase passed
on a TPU, is ``{"ok": true, "device": {...}}``; the exit code is 0 then
and 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: where the run's repro.obs trace goes: ``$REPRO_TRACE``, else a file
#: listed in .gitignore
TRACE_PATH = (os.environ.get("REPRO_TRACE")
              or os.path.join(ROOT, "TRACE_chip_smoke.jsonl"))


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def true_rel_residual(problem, x, b=None) -> float:
    """``‖b − A x‖ / ‖b‖`` in float64 on one device, with the stencil's
    ``jnp`` reference apply, jitted so that XLA fuses the 27 shifted
    terms instead of holding each as a grid-sized temporary."""
    import jax
    import jax.numpy as jnp

    def rel(x, b):
        x, b = x.astype(jnp.float64), b.astype(jnp.float64)
        r = b - problem.stencil.matvec(x)
        return jnp.linalg.norm(r) / jnp.linalg.norm(b)

    dev = jax.devices()[0]
    x = jax.device_put(x, dev)
    b = problem.b() if b is None else jax.device_put(b, dev)
    return float(jax.jit(rel)(x, b))


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_session(sess):
    """One solve through the session: (result, compile s, wall s incl.
    compile), the result ready on the device."""
    import jax
    t0 = time.perf_counter()
    res = jax.block_until_ready(sess.solve())
    wall = time.perf_counter() - t0
    compile_s = sum(v["compile_s"] for v in sess.cache_stats().values())
    return res, compile_s, wall


def check_solve(name: str, sess, res, tol: float) -> dict:
    """The checks every single-RHS solve passes; returns its record."""
    from repro.core.methods import status_name
    st = status_name(res.status)
    check(st == "converged", f"{name}: status {st}, want converged")
    rel = true_rel_residual(sess.problem, res.x)
    check(rel <= tol, f"{name}: true relative residual {rel} > tol {tol}")
    return {"iters": int(res.iters), "res_norm": float(res.res_norm),
            "true_rel_residual": rel, "status": st}


def compiled_text(sess) -> str:
    """The HLO text of the session's one compiled single-RHS executable."""
    (exe,) = sess._executables.values()
    return exe.as_text()


def program_bytes(sess) -> int:
    """Device bytes the session's executable needs by the compiler's
    account: arguments, outputs and temporaries, aliased ones once.  On a
    v5e the runtime's peak counter did not count the temporaries."""
    (exe,) = sess._executables.values()
    ma = exe.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


# -- the phases ---------------------------------------------------------------

def phase_a(grid=None) -> None:
    """``hpcg-cg-27pt`` at its config size, float64 on XLA, through the
    session and through the ``launch/solve.py`` CLI."""
    from repro.configs.hpcg import SOLVER_CONFIGS
    from repro.launch.solve import main as solve_main

    cfg = SOLVER_CONFIGS["hpcg-cg-27pt"]
    grid = tuple(grid or cfg.local_grid)
    sess = cfg.session(grid=grid)
    res, compile_s, wall = run_session(sess)
    rec = check_solve("a", sess, res, cfg.tol)
    out = solve_main(["--config", cfg.name, "--grid", *map(str, grid),
                      "--json"])
    check(out["iters"] == rec["iters"],
          f"a: launch.solve took {out['iters']} iterations, the session "
          f"{rec['iters']}")
    report("a", config=cfg.name, grid=grid, dtype="float64", tol=cfg.tol,
           compile_s=compile_s, wall_s=wall, cli_iters=out["iters"], **rec)


def phase_b(grid=(512, 512, 512), tol=1e-5) -> None:
    """Chip-filling float32 solves: 27-point ``cg``, then ``bicgstab``."""
    from repro.configs.hpcg import SOLVER_CONFIGS

    for name in ("hpcg-cg-27pt", "hpcg-bicgstab-27pt"):
        cfg = SOLVER_CONFIGS[name]
        sess = cfg.session(grid=grid, f64=False, tol=tol, norm_ref=None)
        res, compile_s, wall = run_session(sess)
        # the process's high-water mark so far: read before the float64
        # residual check adds its own buffers
        peak = peak_bytes()
        rec = check_solve("b", sess, res, tol)
        del res
        report("b", config=name, grid=grid, dtype="float32", tol=tol,
               maxiter=cfg.maxiter, compile_s=compile_s, wall_s=wall,
               peak_bytes_in_use=peak, program_bytes=program_bytes(sess),
               **rec)


def phase_c(grid=(256, 256, 256), tol=1e-5) -> None:
    """The fused Pallas ``cg_merged`` against its XLA twin."""
    import jax
    from repro.api import SolverOptions, SolverSession

    runs = {}
    for pallas in (True, False):
        opts = SolverOptions(tol=tol, f64=False, norm_ref=None, pallas=pallas)
        sess = SolverSession(method="cg_merged", grid=grid, stencil="27pt",
                             options=opts)
        res, compile_s, wall = run_session(sess)
        rec = check_solve("c", sess, res, tol)
        if pallas and jax.default_backend() == "tpu":
            check("tpu_custom_call" in compiled_text(sess),
                  "c: no tpu_custom_call in the pallas=True executable")
        runs[pallas] = rec
        report("c", method="cg_merged", pallas=pallas, grid=grid,
               dtype="float32", tol=tol, compile_s=compile_s, wall_s=wall,
               **rec)
    d = abs(runs[True]["iters"] - runs[False]["iters"])
    check(d <= 2, f"c: Pallas and XLA cg_merged differ by {d} iterations")


def phase_d(grid=(128, 128, 128), tol=1e-6, maxiter=1000,
            per_bucket=4) -> None:
    """Eight requests in two float64 buckets through ``repro.serve``."""
    from repro.core.problems import make_problem
    from repro.serve import (ServeConfig, SolverService, TraceBucket,
                             generate_trace, replay)

    buckets = tuple(
        TraceBucket(grid=grid, method=m, stencil="27pt", precond=p,
                    count=per_bucket, tol=tol, maxiter=maxiter,
                    norm_ref=None)
        for m, p in (("cg", "none"), ("pcg", "chebyshev")))
    service = SolverService(ServeConfig(max_batch=per_bucket))
    trace = generate_trace(buckets, seed=0)
    t0 = time.perf_counter()
    try:
        results = replay(service, trace)
    finally:
        service.close()
    wall = time.perf_counter() - t0
    rejects = service.rejects()
    check(not rejects, f"d: {len(rejects)} rejected: "
          f"{[(r.bucket, r.reason) for r in rejects.values()]}")
    check(len(results) == len(trace),
          f"d: {len(results)} of {len(trace)} requests answered")
    problem = make_problem(grid, "27pt")
    by_id = {r.id: r for r in trace}
    worst = 0.0
    for rid, out in results.items():
        check(out.status == "converged",
              f"d: request {rid} ({out.bucket}) ended {out.status}")
        worst = max(worst, true_rel_residual(problem, out.x, by_id[rid].b))
    check(worst <= tol, f"d: worst true relative residual {worst} > {tol}")
    snap = service.snapshot()
    report("d", grid=grid, dtype="float64", tol=tol, requests=len(trace),
           answered=len(results), rejected=len(rejects), wall_s=wall,
           iters=sorted(int(r.iters) for r in results.values()),
           worst_true_rel_residual=worst,
           compile_s={b: st["compile_s"]
                      for b, st in snap["cache"]["per_bucket"].items()},
           p50_s=snap["p50_s"], p99_s=snap["p99_s"])


def phase_four_chips(local=(128, 128, 128)) -> None:
    """``hpcg-cg-27pt`` weak-scaled over four chips, 1-D and 2-D layouts,
    against the same global problem on one chip."""
    import jax
    from repro.configs.hpcg import SOLVER_CONFIGS

    n = len(jax.devices())
    check(n == 4, f"four-chips: {n} devices, want 4")
    cfg = SOLVER_CONFIGS["hpcg-cg-27pt"]
    grid = (local[0], local[1], local[2] * n)
    one = cfg.session(grid=grid, layout="local")
    res1, compile_s, wall = run_session(one)
    peak = peak_bytes()
    rec1 = check_solve("four-chips/one", one, res1, cfg.tol)
    report("four-chips", layout="one-chip", grid=grid, dtype="float64",
           compile_s=compile_s, wall_s=wall, peak_bytes_in_use=peak, **rec1)
    del res1
    for layout in ("1d", "2d"):
        sess = cfg.session(grid=grid, layout=layout)
        res, compile_s, wall = run_session(sess)
        rec = check_solve(f"four-chips/{layout}", sess, res, cfg.tol)
        devices = {s.device for s in res.x.addressable_shards}
        check(len(devices) == n,
              f"four-chips/{layout}: x sits on {len(devices)} devices")
        d = abs(rec["iters"] - rec1["iters"])
        check(d <= 1, f"four-chips/{layout}: {rec['iters']} iterations vs "
              f"{rec1['iters']} on one chip")
        report("four-chips", layout=layout,
               mesh=dict(sess.backend.mesh.shape), grid=grid,
               dtype="float64", compile_s=compile_s, wall_s=wall,
               shard_devices=len(devices), **rec)


ONE_CHIP_PHASES = (("a", phase_a), ("b", phase_b), ("c", phase_c),
                   ("d", phase_d))
FOUR_CHIP_PHASES = (("four-chips", phase_four_chips),)


def run_phases(phases) -> list[str]:
    """Run every phase, whatever an earlier one did; names of the failed."""
    from repro.obs import trace as obs

    if os.path.exists(TRACE_PATH):
        os.remove(TRACE_PATH)
    obs.enable(TRACE_PATH)
    failed = []
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                fn()
            except Exception:
                traceback.print_exc()
                failed.append(name)
                report(name, ok=False, seconds=time.perf_counter() - t0)
    finally:
        obs.disable()
    rungs = [r for r in obs.read_trace(TRACE_PATH)
             if r.get("name") == "resilience.attempt"]
    if rungs:
        print(f"recovery ladder ran {len(rungs)} attempt(s): "
              f"{[r['attrs'] for r in rungs]}", file=sys.stderr)
        failed.append("fallback")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve on four chips and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing "
              f"was run", file=sys.stderr)
        return 1
    from repro.core.problems import enable_f64
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    enable_f64()   # phases a, d and four-chips solve in float64
    failed = run_phases(FOUR_CHIP_PHASES if args.four_chips
                        else ONE_CHIP_PHASES)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
