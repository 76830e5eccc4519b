"""Paper §4.1 table: iterations to convergence on the HPCG system.

Paper (128^3, one MareNostrum4 node, eps=1e-6 absolute):
  7pt : BiCGStab 8,  CG 12, symGS 9,   Jacobi 18
  27pt: BiCGStab 45, CG 72, symGS 142, Jacobi 515

Set BENCH_FULL=1 to run the exact 128^3 sizes (≈2 min on CPU); the default
64^3 shows the same structure at ~1/8 the cost.

``--precond`` (or ``make bench-precond``) additionally runs pcg/pbicgstab
with every repro.precond implementation and reports the iteration count
next to the plain method's — the measured side of the reductions-vs-
iterations trade-off the scaling model prices.
"""

from __future__ import annotations

import os

from benchmarks.common import csv
from repro.api import SolverOptions, SolverSession
from repro.core.problems import enable_f64
from repro.precond import PRECONDITIONERS

PRECONDS = tuple(sorted(PRECONDITIONERS))

PAPER = {
    ("7pt", "bicgstab"): 8, ("7pt", "cg"): 12,
    ("7pt", "gauss_seidel"): 9, ("7pt", "jacobi"): 18,
    ("27pt", "bicgstab"): 45, ("27pt", "cg"): 72,
    ("27pt", "gauss_seidel"): 142, ("27pt", "jacobi"): 515,
}


def main(precond: bool = False) -> None:
    enable_f64()      # paper precision; owned by the driver, not the facade
    n = 128 if os.environ.get("BENCH_FULL") else 64
    opts = SolverOptions(tol=1e-6, maxiter=700, layout="local")
    plain: dict[tuple[str, str], int] = {}
    for stencil in ("7pt", "27pt"):
        for method in ("bicgstab", "cg", "gauss_seidel", "jacobi"):
            sess = SolverSession(method=method, grid=(n, n, n),
                                 stencil=stencil, options=opts)
            res, t = sess.timed_solve(repeats=3)
            plain[(stencil, method)] = int(res.iters)
            csv(f"iters_{stencil}_{method}_{n}^3",
                t["median"] * 1e6,
                f"iters={int(res.iters)};paper128={PAPER[(stencil, method)]};"
                f"res={float(res.res_norm):.2e}")
    if not precond:
        return
    for stencil in ("7pt", "27pt"):
        for method, base in (("pcg", "cg"), ("pbicgstab", "bicgstab")):
            for p in PRECONDS:
                sess = SolverSession(method=method, grid=(n, n, n),
                                     stencil=stencil,
                                     options=opts.replace(precond=p))
                res, t = sess.timed_solve(repeats=3)
                csv(f"iters_{stencil}_{method}+{p}_{n}^3",
                    t["median"] * 1e6,
                    f"iters={int(res.iters)};"
                    f"plain_{base}={plain[(stencil, base)]};"
                    f"res={float(res.res_norm):.2e}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--precond", action="store_true",
                    help="also run pcg/pbicgstab with every preconditioner")
    main(precond=ap.parse_args().precond)
