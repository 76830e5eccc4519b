#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python bench/run.py --workload cg27-f32-512 --seed 7 --seconds 20 --trace 0

from the root of a checkout.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer ones, read from a profiler trace of
the window.  The last lines on stderr are the numbers the check compared,
each beside its limit.  The run exits 2 and prints no result when JAX finds
no TPU of a known kind, or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_spec()
    harness.load_cell(spec, args.workload)   # an unknown name fails here
    harness.enable_compile_cache()
    try:
        result = harness.run_cell(spec, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_proc0=T_PROC0)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
