"""Distributed solver driver — the paper's workload end-to-end.

A thin client of ``repro.api``: backend resolution (local / 1-D paper-faithful
/ 2-D / 3-D shard_map), kernel choice (XLA vs Pallas), preconditioning and
timing all live in the facade; this module only parses flags.

PYTHONPATH=src python -m repro.launch.solve --method cg_nb --stencil 27pt \
    --grid 64 64 64

# preconditioned: pcg/pbicgstab take --precond (repro.precond registry);
# compare the iters/res_norm fields of the JSON result against the plain run
PYTHONPATH=src python -m repro.launch.solve --method pcg --precond chebyshev \
    --stencil 27pt --grid 64 64 64 --json
"""

from __future__ import annotations

import argparse
import json

import jax.numpy as jnp

from repro.api import (LAYOUTS, SolverOptions, SolverSession, precond_names,
                       solver_names)
from repro.configs.hpcg import SOLVER_CONFIGS
from repro.core.problems import enable_f64


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, choices=sorted(SOLVER_CONFIGS),
                    help="named HPCG cell supplying method/stencil/tol/"
                         "maxiter defaults (explicit flags win)")
    ap.add_argument("--method", default=None, choices=solver_names())
    ap.add_argument("--stencil", default=None, choices=["7pt", "27pt"])
    ap.add_argument("--grid", type=int, nargs=3, default=[64, 64, 64])
    ap.add_argument("--tol", type=float, default=None)
    ap.add_argument("--maxiter", type=int, default=None)
    ap.add_argument("--layout", default="auto", choices=list(LAYOUTS),
                    help="auto = local on 1 device, else the paper-faithful "
                         "1-D z decomposition")
    ap.add_argument("--f64", action=argparse.BooleanOptionalAction,
                    default=True, help="double precision (--no-f64 for f32)")
    ap.add_argument("--pallas", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="use the Pallas stencil kernel for the local SpMV")
    ap.add_argument("--precond", default=None, choices=list(precond_names()),
                    help="preconditioner for pcg/pbicgstab (repro.precond): "
                         "jacobi | block_jacobi | ssor | chebyshev; "
                         "cuts iterations at the cost of extra local sweeps "
                         "but zero extra reductions")
    ap.add_argument("--json", action="store_true",
                    help="also print the result record as one JSON line")
    ap.add_argument("--batch", type=int, default=0,
                    help="also solve N random right-hand sides in one "
                         "compiled call (the serving path)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="timed repetitions after the warm-up/compile call")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="append repro.obs/v1 trace records (solve lifecycle "
                         "spans) to PATH; equivalent to REPRO_TRACE=PATH")
    ap.add_argument("--telemetry", action="store_true",
                    help="carry the per-iteration scalar history through the "
                         "solve (SolverOptions.telemetry) and report the "
                         "convergence curve; off = bitwise-identical solve")
    ap.add_argument("--telemetry-buffer", type=int, default=None,
                    help="telemetry row cap (default "
                         "SolverOptions.telemetry_buffer)")
    args = ap.parse_args(argv)

    if args.trace:
        from repro.obs import trace as obs
        obs.enable(args.trace)

    cfg = SOLVER_CONFIGS[args.config] if args.config else None
    method = args.method or (cfg.method if cfg else "cg_nb")
    stencil = args.stencil or (cfg.stencil if cfg else "27pt")
    if args.f64:
        # process-global x64 is owned HERE, at the CLI entry point — the
        # facade refuses to flip it implicitly (see SolverOptions.f64)
        enable_f64()
    overrides = dict(f64=args.f64, layout=args.layout, pallas=args.pallas)
    if args.telemetry:
        overrides["telemetry"] = True
    if args.telemetry_buffer is not None:
        overrides["telemetry_buffer"] = args.telemetry_buffer
    if args.precond is not None:
        overrides["precond"] = args.precond
    if args.tol is not None:
        overrides["tol"] = args.tol
    if args.maxiter is not None:
        overrides["maxiter"] = args.maxiter
    opts = (cfg.to_options(**overrides) if cfg
            else SolverOptions(**overrides))
    sess = SolverSession(method=method, grid=tuple(args.grid),
                         stencil=stencil, options=opts)
    res, stats = sess.timed_solve(repeats=args.repeats, warmup=1)
    dt = stats["median"]

    err = float(jnp.max(jnp.abs(res.x - sess.problem.x_true())))
    print(f"[solve] {sess.describe()} "
          f"iters={int(res.iters)} res={float(res.res_norm):.3e} "
          f"err_inf={err:.3e} wall={dt:.2f}s")
    # iters + achieved residual ride along with the timing for EVERY method,
    # so preconditioned and plain runs are directly comparable from the JSON
    out = {"method": method, "stencil": stencil,
           "precond": sess.options.precond,
           "iters": int(res.iters), "res_norm": float(res.res_norm),
           "err": err, "wall_s": dt, "backend": sess.backend.describe()}
    if args.telemetry:
        from repro.obs.convergence import curve_record
        out["convergence"] = curve_record(res, method, scalars=True)
        print(f"[solve] telemetry: {out['convergence']['telemetry_rows']} "
              f"rows, scalars={sorted(out['convergence']['scalars'])}")

    if args.batch:
        import numpy as np
        rng = np.random.default_rng(0)
        bs = jnp.asarray(rng.standard_normal((args.batch, *args.grid)),
                         dtype=res.x.dtype)
        bres, bstats = sess.timed_solve_batched(bs, repeats=args.repeats)
        print(f"[solve] batched x{args.batch}: iters="
              f"{np.asarray(bres.iters).tolist()} wall={bstats['median']:.2f}s")
        out["batch_wall_s"] = bstats["median"]
        out["batch_iters"] = np.asarray(bres.iters).tolist()
        out["batch_res_norm"] = np.asarray(bres.res_norm).tolist()
    if args.json:
        print(json.dumps(out))
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
