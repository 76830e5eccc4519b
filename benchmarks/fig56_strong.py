"""Figs. 5-6: strong scalability, fixed 128x128x6144 grid.

Reproduces the paper's qualitative findings in the TPU model: per-chip work
shrinks with n while collective latency does not, so every method's
efficiency decays; methods with fewer/hidden blocking reductions decay
slower; past the point where the block fits on-chip cache/VMEM the advantage
vanishes (the paper's data-locality crossover).
"""

from __future__ import annotations

from benchmarks.common import csv
from benchmarks.scaling_model import strong_efficiency
from repro.api import solver_names

CHIPS = (1, 8, 48, 96, 192, 384, 768, 1536, 3072, 6144)


def main() -> None:
    # every registered method with a scaling-model entry (rb-GS shares the
    # relaxed-GS curve, so only the relaxed variant is plotted)
    methods = [m for m in solver_names() if m != "gauss_seidel_rb"]
    for noise in ("tpu", "noisy"):
        for stencil, nbar in (("7pt", 7), ("27pt", 27)):
            for method in methods:
                effs = [round(strong_efficiency(method, nbar, n, noise=noise,
                                                halo_mode="overlap"), 4)
                        for n in CHIPS]
                csv(f"fig56_{noise}_{stencil}_{method}", 0.0,
                    "eff@" + "/".join(map(str, CHIPS)) + "="
                    + "/".join(map(str, effs)))
            # crossover: first n losing >half the single-chip efficiency
            for method in ("cg", "cg_nb"):
                cross = next((n for n in CHIPS if strong_efficiency(
                    method, nbar, n, noise=noise,
                    halo_mode="overlap") < 0.5), None)
                csv(f"fig56_{noise}_{stencil}_{method}_half_eff_at", 0.0,
                    str(cross))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
