"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``
prints ``name,us_per_call,derived`` CSV lines per the repo contract.

  table_iterations  — §4.1 iteration-count table (vs the paper's values)
  fig2_variants     — Fig. 2 execution-time box stats + Fig. 1 barrier traces
  fig3_weak_ksm     — Fig. 3 weak-scaling efficiencies (KSMs)
  fig4_weak_stationary — Fig. 4 weak scaling + GS-variant iteration effect
  fig56_strong      — Figs. 5-6 strong scaling
  roofline          — §Roofline terms for every dry-run cell
"""

from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    fig2_variants,
    fig3_weak_ksm,
    fig4_weak_stationary,
    fig56_strong,
    roofline,
    table_iterations,
)

MODULES = {
    "table_iterations": table_iterations,
    "fig2_variants": fig2_variants,
    "fig3_weak_ksm": fig3_weak_ksm,
    "fig4_weak_stationary": fig4_weak_stationary,
    "fig56_strong": fig56_strong,
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(MODULES))
    ap.add_argument("--list-methods", action="store_true",
                    help="print the solver registry (methods + per-iteration "
                         "communication metadata) and exit")
    args = ap.parse_args()
    if args.list_methods:
        from repro.api import REGISTRY
        for name in sorted(REGISTRY):
            s = REGISTRY[name]
            print(f"{name},reductions={s.reductions_per_iter},"
                  f"blocking={s.blocking_reductions},spmvs={s.spmvs_per_iter},"
                  f"variant_of={s.variant_of or '-'},"
                  f"{'stationary' if s.stationary else 'krylov'}")
        return
    names = [args.only] if args.only else list(MODULES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        print(f"# --- {name} ---")
        try:
            MODULES[name].main()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
