"""Fig. 4: weak scalability of Jacobi + symmetric Gauss-Seidel, including the
GS-variant iteration-count effect the paper measures in Fig. 4(d)
(MPI 157 vs bicoloured 166 vs relaxed 150 at the 27pt stencil).

Part 1: efficiency curves from the iteration-time model.
Part 2: measured iteration counts of the GS variants on CPU (the convergence
        differences are real algorithm properties, not hardware ones).
"""

from __future__ import annotations

from benchmarks.common import csv
from benchmarks.scaling_model import iteration_time
from repro.api import SolverOptions, SolverSession
from repro.core.problems import enable_f64

CHIPS = (1, 8, 64, 256, 512, 1024, 4096)


def main() -> None:
    enable_f64()      # paper precision; owned by the driver, not the facade
    for noise in ("tpu", "noisy"):
        for stencil, nbar in (("7pt", 7), ("27pt", 27)):
            for method, ex in (("jacobi", "mpi"), ("jacobi", "dataflow"),
                               ("gauss_seidel", "mpi"),
                               ("gauss_seidel", "dataflow")):
                t_ref = iteration_time(method, nbar, (128, 128, 128), 1,
                                       noise=noise, execution="mpi")
                halo = "overlap" if ex == "dataflow" else "concat"
                effs = [round(t_ref / iteration_time(
                    method, nbar, (128, 128, 128), n, noise=noise,
                    execution=ex, halo_mode=halo), 4) for n in CHIPS]
                csv(f"fig4_{noise}_{stencil}_{method}_{ex}", 0.0,
                    "eff@" + "/".join(map(str, CHIPS)) + "="
                    + "/".join(map(str, effs)))

    # GS variant convergence (measured)
    counts = {}
    for variant in ("gauss_seidel", "gauss_seidel_rb"):
        res = SolverSession(
            method=variant, grid=(48, 48, 48), stencil="27pt",
            options=SolverOptions(tol=1e-6, maxiter=1500,
                                  layout="local")).solve()
        counts[variant] = int(res.iters)
        csv(f"fig4d_iters_{variant}", 0.0, f"iters={int(res.iters)}")
    csv("fig4d_variant_ratio", 0.0,
        f"relaxed/rb={counts['gauss_seidel']/counts['gauss_seidel_rb']:.3f}"
        f" (paper: 150/166={150/166:.3f})")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
