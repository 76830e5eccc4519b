"""Fig. 3: weak scalability of the Krylov methods (CG/CG-NB, BiCGStab/B1).

Relative parallel efficiency vs chip count for both stencils, from the
roofline-based iteration-time model (benchmarks/scaling_model.py), normalised
like the paper to the classical method at one node.  The paper's claim to
reproduce: the nonblocking variants hold efficiency at scale because their
reductions ride behind the SpMV / vector updates (CG-NB +19.7%/+25% over
blocking CG at 64 nodes; here the analogue at 512-4096 chips).

Beyond the paper: the preconditioned curves (pcg + each repro.precond
implementation, t_precond term included) quantify the reductions-vs-
iterations trade-off.  Per curve we emit the weak-scaling efficiency AND
the break-even factor — how much the preconditioner must cut the iteration
count to beat plain cg wall-clock at that chip count.  The built-ins add
zero reductions per iteration, so the break-even factor *shrinks* as the
all-reduce latency grows with scale: preconditioning pays off more, not
less, at 4096 chips.
"""

from __future__ import annotations

from benchmarks.common import csv
from benchmarks.scaling_model import iteration_time
from repro.api import REGISTRY, variant_pairs
from repro.precond import PRECONDITIONERS

CHIPS = (1, 8, 64, 256, 512, 1024, 4096)
PRECONDS = tuple(sorted(PRECONDITIONERS))


def main() -> None:
    # the Krylov (classical, nonblocking-variant) pairs from the registry;
    # the preconditioned forms get their own dedicated curves below, not
    # the paper's variant slots (unpreconditioned pcg is just cg + one
    # extra blocking reduction — not a communication-hiding variant)
    pairs = [p for p in variant_pairs()
             if p[0] in ("cg", "bicgstab")
             and not REGISTRY[p[1]].accepts_precond]
    for noise in ("tpu", "noisy"):
        for stencil, nbar in (("7pt", 7), ("27pt", 27)):
            for pair in pairs:
                # three curves like the paper: MPI-only classical, task-based
                # classical, task-based nonblocking variant
                t_ref = iteration_time(pair[0], nbar, (128, 128, 128), 1,
                                       noise=noise, execution="mpi")
                runs = [(pair[0], "mpi"), (pair[0], "dataflow"),
                        (pair[1], "dataflow")]
                ts = {}
                for method, ex in runs:
                    halo = "overlap" if ex == "dataflow" else "concat"
                    effs = []
                    for n in CHIPS:
                        t = iteration_time(method, nbar, (128, 128, 128), n,
                                           noise=noise, execution=ex,
                                           halo_mode=halo)
                        effs.append(round(t_ref / t, 4))
                        ts[(method, ex, n)] = t
                    csv(f"fig3_{noise}_{stencil}_{method}_{ex}", 0.0,
                        "eff@" + "/".join(map(str, CHIPS)) + "="
                        + "/".join(map(str, effs)))
                # headline: nonblocking-task vs MPI-only classical (the
                # paper's +19.7%/+25% comparison at 64 nodes)
                for n in (512, 4096):
                    t_c = ts[(pair[0], "mpi", n)]
                    t_v = ts[(pair[1], "dataflow", n)]
                    csv(f"fig3_{noise}_{stencil}_{pair[1]}_vs_mpi_at_{n}",
                        0.0, f"{(t_c / t_v - 1) * 100:.1f}%")
            # preconditioned weak scaling: efficiency curves with t_precond,
            # plus the break-even iteration-reduction factor vs plain cg
            t_ref = iteration_time("cg", nbar, (128, 128, 128), 1,
                                   noise=noise, execution="mpi")
            t_cg = {n: iteration_time("cg", nbar, (128, 128, 128), n,
                                      noise=noise, halo_mode="overlap")
                    for n in CHIPS}
            for M in PRECONDS:
                effs, brk = [], []
                for n in CHIPS:
                    t = iteration_time("pcg", nbar, (128, 128, 128), n,
                                       noise=noise, halo_mode="overlap",
                                       precond=M)
                    effs.append(round(t_ref / t, 4))
                    brk.append(round(t / t_cg[n], 3))
                csv(f"fig3_{noise}_{stencil}_pcg+{M}", 0.0,
                    "eff@" + "/".join(map(str, CHIPS)) + "="
                    + "/".join(map(str, effs)))
                csv(f"fig3_{noise}_{stencil}_pcg+{M}_breakeven", 0.0,
                    "iters_factor@" + "/".join(map(str, CHIPS)) + "="
                    + "/".join(map(str, brk)))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
