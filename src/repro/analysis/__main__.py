"""``python -m repro.analysis`` — run the contract auditor.

Modes::

    python -m repro.analysis --lint-only          # AST + kernel passes only
    python -m repro.analysis --write AUDIT.json   # measure, (re)write baseline
    python -m repro.analysis --check AUDIT.json   # full audit, fail on drift

``--check`` is the CI gate: all three passes, comparing the measured HLO
against both the MethodDef communication metadata and the committed
byte-level baseline.  Exit status 1 on any violation.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static contract auditor: comms/donation HLO audit, "
                    "MethodDef AST lint, Pallas kernel checks")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", metavar="AUDIT.json",
                      help="full audit against this committed baseline")
    mode.add_argument("--write", metavar="AUDIT.json",
                      help="measure and (re)write the baseline, then verify")
    mode.add_argument("--lint-only", action="store_true",
                      help="skip the (slow) HLO measurement passes")
    ap.add_argument("--methods", default=None,
                    help="comma-separated subset (debugging; baseline "
                         "comparison is skipped for subsets)")
    args = ap.parse_args(argv)

    from repro.analysis.lint_kernels import check_kernels
    from repro.analysis.lint_methods import check_methods
    from repro.analysis.violation import format_violations

    violations = []

    print("[1/3] MethodDef AST + state-layout lint", flush=True)
    violations += check_methods()
    print("[2/3] Pallas kernel static checks", flush=True)
    violations += check_kernels()

    if args.lint_only:
        print("[3/3] HLO comms/donation audit: skipped (--lint-only)")
    else:
        from repro.analysis.audit import compare, run_measurements
        methods = args.methods.split(",") if args.methods else None
        print("[3/3] HLO comms/donation audit "
              "(compiling every method x mesh in a subprocess)", flush=True)
        measured = run_measurements(methods)
        n_cfg = sum(len(measured.get(k, {})) for k in
                    ("comms", "donate_mesh", "local", "mesh_aliases"))
        print(f"      measured {n_cfg} configurations", flush=True)
        baseline = None
        if args.check and methods is None:
            try:
                with open(args.check) as f:
                    baseline = json.load(f)
            except OSError as e:
                print(f"cannot read baseline {args.check!r}: {e}",
                      file=sys.stderr)
                return 1
        violations += compare(measured, baseline=baseline)
        if args.write and methods is None and not violations:
            from repro.analysis.audit import GRID, MESHES, STENCIL
            doc = {"grid": list(GRID), "stencil": STENCIL,
                   "meshes": {k: {"devices": list(v[0]), "axes": list(v[1])}
                              for k, v in MESHES.items()},
                   "measured": measured}
            with open(args.write, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            print(f"wrote baseline: {args.write} ({n_cfg} configurations)")

    if violations:
        print(format_violations(violations), file=sys.stderr)
        print(f"FAILED: {len(violations)} violation(s)", file=sys.stderr)
        return 1
    print("OK: all contracts hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
