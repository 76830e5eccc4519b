"""Serving benchmark: replay the fixed heterogeneous trace through
``repro.serve`` and write ``BENCH_serve.json``.

The workload is ``repro.serve.trace.MIXED_BUCKETS`` (two grids x two
methods, one preconditioned — four executables) streamed through the
service's continuous batcher.  The record carries the SLO numbers a
capacity plan needs — sustained QPS, p50/p95/p99 end-to-end latency,
per-bucket compile seconds — plus the integrity facts the CI gate
asserts:

  * ``dropped == 0``      — every admitted request completed;
  * ``compiles_per_bucket == 1`` — each bucket compiled exactly once
    (``SolverSession.cache_stats()``), i.e. the padded-batch executable
    cache actually amortises compilation across the stream;
  * ``qps >= qps_floor`` and ``p99_s <= p99_ceiling_s`` — the smoke
    SLO gate on the fixed CPU trace (loose bounds: CI containers are
    noisy; a 10x regression still fails loudly).

``--chaos`` replays the smoke workload under injected faults (one
preemption absorbed by the in-place retry, one bucket whose compile
fails and must turn into typed rejects) and writes a SEPARATE record,
``BENCH_serve_chaos.json``, with its own gate (``check_chaos_record``):
every request accounted for (completed + rejected == submitted), the
broken bucket fully rejected with reason ``compile_failed``, and the
preemption retried rather than requeued.  The clean-run record and its
``compiles_per_bucket == 1`` invariant are never polluted by chaos.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_serve            # full
    PYTHONPATH=src python -m benchmarks.bench_serve --smoke    # CI gate
    PYTHONPATH=src python -m benchmarks.bench_serve --chaos    # chaos gate
    PYTHONPATH=src python -m benchmarks.bench_serve --check BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import os

import jax

from benchmarks.common import csv, trajectory_append, trajectory_row
from repro.core.problems import enable_f64
# SMOKE_BUCKETS lives with the trace definitions since PR 8 (launch/serve.py
# and make obs-smoke replay the same workload); re-exported here for
# back-compat with callers that imported it from the bench
from repro.serve import (SMOKE_BUCKETS, ServeConfig, SolverService,
                         generate_trace, replay)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: smoke-gate SLO bounds on the fixed CPU trace (generous: a CI container
#: is noisy; these catch order-of-magnitude regressions, not jitter)
SMOKE_QPS_FLOOR = 0.5
SMOKE_P99_CEILING_S = 60.0


def check_record(path: str) -> dict:
    """The artifact-level gate: assert an existing BENCH_serve.json still
    reports zero drops, one compile per bucket, and SLOs within the
    bounds recorded alongside the measurements."""
    with open(path) as f:
        record = json.load(f)
    meta, m = record["meta"], record["metrics"]
    problems = []
    if record["dropped"] != 0:
        problems.append(f"dropped {record['dropped']} request(s)")
    bad_compiles = {b: n for b, n in record["compiles_per_bucket"].items()
                    if n != 1}
    if bad_compiles:
        problems.append(f"compiles per bucket != 1: {bad_compiles}")
    if m["qps"] < meta["qps_floor"]:
        problems.append(f"qps {m['qps']:.2f} < floor {meta['qps_floor']}")
    if m["p99_s"] > meta["p99_ceiling_s"]:
        problems.append(
            f"p99 {m['p99_s']:.2f}s > ceiling {meta['p99_ceiling_s']}s")
    if problems:
        raise SystemExit(f"[bench_serve] {path}: " + "; ".join(problems))
    print(f"[bench_serve] {path}: {record['completed']} requests over "
          f"{len(record['compiles_per_bucket'])} buckets, 0 dropped, "
          f"1 compile/bucket, qps={m['qps']:.2f} (floor "
          f"{meta['qps_floor']}), p99={m['p99_s']:.2f}s (ceiling "
          f"{meta['p99_ceiling_s']}s)")
    return record


def check_chaos_record(path: str) -> dict:
    """The chaos-run gate: every submitted request accounted for, the
    injected compile failure converted to typed rejects (exactly the
    broken bucket's traffic), and the injected preemption absorbed by the
    in-place retry instead of a WAL requeue."""
    with open(path) as f:
        record = json.load(f)
    m = record["metrics"]
    problems = []
    if record["completed"] + record["rejected"] != record["requests"]:
        problems.append(
            f"{record['requests']} submitted but only "
            f"{record['completed']} completed + {record['rejected']} "
            f"rejected — requests stranded")
    want_cf = record["meta"]["expect_compile_fail_rejects"]
    got_cf = record["rejects_by_reason"].get("compile_failed", 0)
    if got_cf != want_cf:
        problems.append(f"compile_failed rejects {got_cf} != "
                        f"expected {want_cf} (the broken bucket's traffic)")
    if m["retries"] < 1:
        problems.append("injected preemption never hit the retry path")
    if m["preemptions"] != 0:
        problems.append(f"{m['preemptions']} preemption(s) fell through "
                        f"to the WAL requeue despite the retry budget")
    if problems:
        raise SystemExit(f"[bench_serve --chaos] {path}: "
                         + "; ".join(problems))
    print(f"[bench_serve --chaos] {path}: {record['requests']} requests -> "
          f"{record['completed']} completed, {record['rejected']} typed "
          f"rejects ({record['rejects_by_reason']}), "
          f"{m['retries']} retry(ies), 0 stranded")
    return record


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids + small counts (the CI gate)")
    ap.add_argument("--chaos", action="store_true",
                    help="smoke workload under injected faults (one "
                         "preemption + one compile failure); writes "
                         "BENCH_serve_chaos.json with its own gate")
    ap.add_argument("--check-chaos", metavar="JSON",
                    help="assert an existing BENCH_serve_chaos.json still "
                         "meets the chaos gate")
    ap.add_argument("--check", metavar="JSON",
                    help="don't bench: assert an existing BENCH_serve.json "
                         "still meets its recorded SLO bounds")
    ap.add_argument("--scale", type=int, default=None,
                    help="trace size multiplier per bucket (default: "
                         "smoke 1, full 4)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-capacity", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--qps-floor", type=float, default=None)
    ap.add_argument("--p99-ceiling", type=float, default=None)
    ap.add_argument("--out", default=None,
                    help="record path (default BENCH_serve.json, or "
                         "BENCH_serve_chaos.json under --chaos)")
    args = ap.parse_args(argv)
    args.out = args.out or os.path.join(
        ROOT, "BENCH_serve_chaos.json" if args.chaos else "BENCH_serve.json")

    if args.check:
        return check_record(args.check)
    if args.check_chaos:
        return check_chaos_record(args.check_chaos)

    enable_f64()
    smoke = args.smoke or args.chaos
    buckets = SMOKE_BUCKETS if smoke else None
    scale = args.scale or (1 if smoke else 4)
    trace = (generate_trace(buckets, seed=args.seed, scale=scale)
             if buckets else generate_trace(seed=args.seed, scale=scale))
    injector = None
    expect_cf = 0
    if args.chaos:
        from repro.resilience import ChaosInjector, ChaosPlan
        # one bucket that will never compile + one preemption the retry
        # budget must absorb; both seeded, so the record is reproducible
        broken = "bicgstab_b1"
        expect_cf = sum(1 for r in trace if r.method == broken)
        injector = ChaosInjector(ChaosPlan(
            seed=args.seed, fail_compile_buckets=(broken,),
            preempt_at=(0,)))
        cfg = ServeConfig(max_batch=args.max_batch,
                          cache_capacity=args.cache_capacity,
                          guards=True, max_retries=2,
                          retry_backoff_s=0.01, retry_seed=args.seed)
    else:
        cfg = ServeConfig(max_batch=args.max_batch,
                          cache_capacity=args.cache_capacity)
    service = SolverService(cfg, injector=injector)
    results = replay(service, trace)
    service.close()
    snap = service.snapshot()

    compiles = {b: st["misses"]
                for b, st in snap["cache"]["per_bucket"].items()}
    record = {
        "meta": {
            "backend": jax.default_backend(),
            "smoke": bool(smoke),
            "chaos": bool(args.chaos),
            "expect_compile_fail_rejects": expect_cf,
            "seed": args.seed,
            "scale": scale,
            "max_batch": cfg.max_batch,
            "cache_capacity": cfg.cache_capacity,
            "qps_floor": args.qps_floor or SMOKE_QPS_FLOOR,
            "p99_ceiling_s": args.p99_ceiling or SMOKE_P99_CEILING_S,
        },
        "requests": len(trace),
        "completed": len(results),
        "rejected": len(service.rejects()),
        "rejects_by_reason": snap["rejects_by_reason"],
        "dropped": len(trace) - len(results) - len(service.rejects()),
        "compiles_per_bucket": compiles,
        "compile_s_per_bucket": {
            b: st["compile_s"]
            for b, st in snap["cache"]["per_bucket"].items()},
        "metrics": {k: snap[k] for k in
                    ("qps", "p50_s", "p95_s", "p99_s", "queue_depth_max",
                     "preemptions", "requeued", "retries", "device_losses",
                     "completed")},
        "per_bucket": snap["per_bucket"],
    }
    for b, st in snap["per_bucket"].items():
        csv(f"bench_serve_{b}_p50", st["p50_s"] * 1e6,
            f"served={st['served']} p99_ms={st['p99_s']*1e3:.1f}")
    csv("bench_serve_qps", 0.0, f"qps={snap['qps']:.2f} "
        f"p99_ms={snap['p99_s']*1e3:.1f}")
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[bench_serve] wrote {args.out}")
    hist = os.path.splitext(args.out)[0] + "_history.jsonl"
    trajectory_append(hist, trajectory_row(
        "serve", smoke=bool(smoke), chaos=bool(args.chaos), scale=scale,
        requests=len(trace), completed=len(results),
        qps=snap["qps"], p50_s=snap["p50_s"], p99_s=snap["p99_s"]))
    print(f"[bench_serve] appended {hist}")
    # same criterion as the standalone --check gates, by construction
    if args.chaos:
        check_chaos_record(args.out)
    else:
        check_record(args.out)
    return record


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
