#!/usr/bin/env python3
"""The readings a cell's check limit is set from, in one process.

    python bench/control.py --workload cg27-f32-512 --seconds 3 \\
        --seeds 11 12 13 ... --control-seeds 21 22 23

For each of ``--seeds`` it runs the cell as ``bench/run.py`` does, with a
window of ``--seconds``, and prints the true relative residuals that the
check compared: the program's readings.  For each of ``--control-seeds`` it
puts the plain reference CG (``bench/reference.py``) in the program's place,
computed in the precision below the configuration's (bfloat16 for float32,
float32 for float64), solves the same right-hand sides and prints what the
same check reads: the control's readings, which must fail the limit.
Every line is one JSON object.  Not part of a benchmark run.
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

#: the control's precision: the step below the configuration's
LOWER = {"float64": "float32", "float32": "bfloat16"}


def control_readings(spec: dict, name: str, seed: int, *,
                     require_tpu: bool = True, config: dict | None = None,
                     traffic: dict | None = None) -> dict:
    """Solve the cell's right-hand sides for ``seed`` with the reference CG
    in the lower precision; returns the check's readings."""
    import jax
    import jax.numpy as jnp

    from bench import generator, harness, reference

    cell, cfg, mix = harness.load_cell(spec, name)
    config = config or cfg
    traffic = traffic or mix
    devices = harness.cell_devices(int(cell["chips"]), require_tpu)
    harness._x64(config["precision"] == "float64")
    sess = harness.build_session(config, devices)
    shape, dtype = sess.problem.shape, sess.problem.dtype
    sharding = sess.backend.sharding()
    del sess
    pool = generator.make_rhs(traffic, seed, shape, dtype, sharding)
    low = jnp.dtype(LOWER[config["precision"]])
    xs, iters = [], []
    t0 = time.perf_counter()
    for b in pool:
        x, k = reference.cg(b.astype(low), config["tol"], config["maxiter"])
        xs.append(jax.block_until_ready(x.astype(dtype)))
        iters.append(int(k))
    solve_s = time.perf_counter() - t0
    was = harness._x64(True)
    try:
        res = [reference.true_rel_residual(x, b) for x, b in zip(xs, pool)]
    finally:
        harness._x64(was)
    limit = config["check"]["residual_limit"]
    return {"control": True, "workload": name, "seed": seed,
            "precision": str(low), "iters": iters, "solve_s": solve_s,
            "true_rel_residuals": res, "limit": limit,
            "fails": not max(res) <= limit}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_spec()
    harness.enable_compile_cache()
    for seed in args.seeds:
        r = harness.run_cell(spec, args.workload, seed, args.seconds, False,
                             t_proc0=time.perf_counter())
        print(json.dumps({"control": False, "workload": args.workload,
                          "seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "iters": r["solves"]["iters"],
                          "true_rel_residuals":
                              r["solves"]["true_rel_residuals"],
                          "checks": r["checks"]}), flush=True)
    for seed in args.control_seeds:
        print(json.dumps(control_readings(spec, args.workload, seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
