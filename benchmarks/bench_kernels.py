"""Per-iteration microbench of the Krylov iteration bodies (PR 4, PR 10).

Times N back-to-back iterations of each formulation on random state, at
32³ and 64³ (f64, 27-pt — the paper's setting), and writes
``BENCH_kernels.json`` at the repo root (the measured-perf trajectory the
CI bench-smoke step uploads).  Three families:

  * ``*_classic_kernels`` — the classic iteration as separately dispatched
    kernels (SpMV, dots, axpys) driven by a host loop: the fork-join
    kernel-switch baseline, every switch a dispatch + HBM round trip (the
    paper's §3.3 task-merging target).  CG (6 dispatches/iter) and
    BiCGStab (11 dispatches/iter).
  * ``*_jit`` — N iterations of the classic / merged / pipelined body
    inside ONE compiled ``fori_loop`` (the regime the actual solvers run
    in; merged and pipelined carry their extra recurrences, single
    stacked reduction).
  * ``fused_*_iteration`` — the merged/pipelined iteration via the fused
    kernels: 2 VMEM-resident passes per iteration on TPU; their
    single-pass jnp references composed into the same loop elsewhere.
    Every row records the implementation that ACTUALLY ran in its
    ``impl`` field (``pallas`` / ``pallas-interpret`` / ``jnp-ref`` /
    ``jit`` / ``fork-join`` / ``xla-fallback(...)``) — ``--check`` fails
    if a gated comparison ran the interpret-mode emulator, which is not a
    measurement.

``cg_classic_kernels_auto`` is the PR-10 autotuner row: what the facade
actually executes for a classic solve with ``pallas="auto"`` at this
grid.  Below the Pallas/XLA crossover the autotuner falls back to the
jitted XLA loop (the 16³ case where the kernel path used to be 3.5×
slower), so the row reuses ``cg_classic_jit``'s measured time and is
gated at ``<= cg_classic_jit × 1.1``.

Per-iteration time = min over repeats of (N-iteration wall clock)/N — the
min (not median) because this measures the kernels, not container noise.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_kernels            # full
    PYTHONPATH=src python -m benchmarks.bench_kernels --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.common import csv, trajectory_append, trajectory_row
from repro.core.operators import STENCILS
from repro.core.problems import enable_f64
from repro.core.solvers import _cg_merged_scalars
from repro.kernels import autotune, ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRIDS = ((32, 32, 32), (64, 64, 64))
SMOKE_GRIDS = ((16, 16, 16),)

#: the fused bodies that get a trajectory-history row per grid, and the
#: fork-join baseline each is gated against (ratio >= GATE_MIN)
FUSED_GATES = {
    "fused_iteration": "cg_classic_kernels",
    "fused_pipe_iteration": "cg_classic_kernels",
    "fused_bicgstab_iteration": "bicgstab_classic_kernels",
}
GATE_MIN = 1.0          # fused must be >= the fork-join baseline
AUTO_GATE_MAX = 1.1     # auto row must be <= cg_classic_jit × this


def _state(shape, dtype, n=6):
    ks = jax.random.split(jax.random.PRNGKey(0), n)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _impl_label(use_pallas: bool) -> str:
    """What actually executes inside the fused rows."""
    if not use_pallas:
        return "jnp-ref"
    return "pallas" if jax.default_backend() == "tpu" else "pallas-interpret"


def _runners(stencil, n_iters: int, state, use_pallas: bool):
    """name -> (zero-arg callable running ``n_iters`` iterations, impl)."""
    mvp = stencil.matvec_padded
    x, r, p, s, w, z, t, v, rhat = state
    one = jnp.asarray(1.0, x.dtype)
    inf = jnp.asarray(jnp.inf, x.dtype)
    rr = jnp.vdot(r, r)
    delta = jnp.vdot(w, r)
    fused_impl = _impl_label(use_pallas)

    # -- classic CG, six separate kernel dispatches per iteration -------------
    k_spmv = jax.jit(lambda u: mvp(jnp.pad(u, 1)))
    k_dot = jax.jit(jnp.vdot)
    k_axpy = jax.jit(lambda a, u, q: u + a * q)

    def cg_classic_kernels():
        xc, rc, pc, rrc = x, r, p, rr
        for _ in range(n_iters):
            Ap = k_spmv(pc)
            pAp = k_dot(pc, Ap)
            alpha = rrc / pAp
            xc = k_axpy(alpha, xc, pc)
            rc = k_axpy(-alpha, rc, Ap)
            rr_new = k_dot(rc, rc)
            beta = rr_new / rrc
            pc = k_axpy(beta, rc, pc)
            rrc = rr_new
        return jax.block_until_ready((xc, rc, pc, rrc))

    # -- classic BiCGStab, eleven separate kernel dispatches per iteration ----
    def bicgstab_classic_kernels():
        xc, rc, pc, vc = x, r, p, v
        alpha = omega = rho = jnp.asarray(1.0, x.dtype)
        for _ in range(n_iters):
            rho_new = k_dot(rhat, rc)
            beta = (rho_new / rho) * (alpha / omega)
            pc = k_axpy(beta, rc, k_axpy(-omega, pc, vc))
            vc = k_spmv(pc)
            alpha = rho_new / k_dot(rhat, vc)
            sc = k_axpy(-alpha, rc, vc)
            tc = k_spmv(sc)
            omega = k_dot(tc, sc) / k_dot(tc, tc)
            xc = k_axpy(omega, k_axpy(alpha, xc, pc), sc)
            rc = k_axpy(-omega, sc, tc)
            rho = rho_new
        return jax.block_until_ready((xc, rc, pc, vc))

    # -- whole-loop compiled variants -----------------------------------------
    def classic_body(_, c):
        xc, rc, pc, rrc = c
        Ap = mvp(jnp.pad(pc, 1))
        alpha = rrc / jnp.vdot(pc, Ap)
        xc = xc + alpha * pc
        rc = rc - alpha * Ap
        rr_new = jnp.vdot(rc, rc)
        pc = rc + (rr_new / rrc) * pc
        return (xc, rc, pc, rr_new)

    def merged_body(_, c):
        xc, rc, pc, sc, wc, gamma, dlt, gp, ap = c
        alpha, beta = _cg_merged_scalars(gamma, dlt, gp, ap)
        pc = rc + beta * pc
        sc = wc + beta * sc
        xc = xc + alpha * pc
        rc = rc - alpha * sc
        wc = mvp(jnp.pad(rc, 1))
        return (xc, rc, pc, sc, wc, jnp.vdot(rc, rc), jnp.vdot(wc, rc),
                gamma, alpha)

    def pipe_body(_, c):
        xc, rc, wc, pc, sc, zc, gp, ap = c
        gamma, dlt = jnp.vdot(rc, rc), jnp.vdot(wc, rc)
        n = lax.optimization_barrier(mvp(jnp.pad(wc, 1)))
        alpha, beta = _cg_merged_scalars(gamma, dlt, gp, ap)
        zc = n + beta * zc
        sc = wc + beta * sc
        pc = rc + beta * pc
        xc = xc + alpha * pc
        rc = rc - alpha * sc
        wc = wc - alpha * zc
        return (xc, rc, wc, pc, sc, zc, gamma, alpha)

    def bicgstab_merged_body(_, c):
        """The reduction-hiding merged BiCGStab: 2 SpMVs + 9 stacked dot
        partials per iteration, plain jnp inside one jit (the refs ARE the
        single-pass jnp formulation)."""
        yc, rc, wc, pc, sc, zc, tc, vc, alpha, rho = c
        vc, qc, yi, parts = ref.bicgstab_spmv_dots_ref(
            jnp.pad(zc, 1), zc, rc, wc, sc, rhat, tc, alpha, stencil=stencil)
        qy, yy, _qq, rhq, rhy, rht, rhv, rhz, rhs = parts
        omega = qy / yy
        rho_new = rhq - omega * rhy
        beta = (rho_new / rho) * (alpha / omega)
        yc, rc, wc = ref.bicgstab_update1_ref(alpha, omega, yc, pc, qc, yi,
                                              tc, vc)
        tc, pc, sc, zc = ref.bicgstab_spmv_update_ref(
            jnp.pad(wc, 1), wc, rc, pc, sc, zc, vc, omega, beta,
            stencil=stencil)
        rhw = rhy - omega * (rht - alpha * rhv)
        alpha = rho_new / (rhw + beta * (rhs - omega * rhz))
        return (yc, rc, wc, pc, sc, zc, tc, vc, alpha, rho_new)

    def fused_body(_, c):
        xc, rc, pc, sc, wc, gamma, dlt, gp, ap = c
        alpha, beta = _cg_merged_scalars(gamma, dlt, gp, ap)
        if use_pallas:
            xc, rc, pc, sc = ops.cg_body(alpha, beta, xc, rc, pc, sc, wc)
            wc, dlt_new, gamma_new = ops.spmv_dots(jnp.pad(rc, 1), stencil)
        else:
            xc, rc, pc, sc = ref.fused_cg_body_ref(alpha, beta, xc, rc, pc,
                                                   sc, wc)
            wc = mvp(jnp.pad(rc, 1))
            # == stencil_spmv_dots_ref with the centre slice elided (the
            # centre of pad(r) IS r); XLA fuses the dots into the pass
            dlt_new, gamma_new = jnp.vdot(wc, rc), jnp.vdot(rc, rc)
        return (xc, rc, pc, sc, wc, gamma_new, dlt_new, gamma, alpha)

    def fused_pipe_body(_, c):
        xc, rc, wc, pc, sc, zc, gp, ap = c
        if use_pallas:
            # n = A·w plus the (w·r, r·r) pipelined dots, one pass
            n, _nw, dlt, gamma = ops.spmv_dots3(jnp.pad(wc, 1), rc, stencil)
        else:
            n = mvp(jnp.pad(wc, 1))
            gamma, dlt = jnp.vdot(rc, rc), jnp.vdot(wc, rc)
        alpha, beta = _cg_merged_scalars(gamma, dlt, gp, ap)
        if use_pallas:
            xc, rc, wc, pc, sc, zc = ops.pipe_body(alpha, beta, xc, rc, wc,
                                                   pc, sc, zc, n)
        else:
            xc, rc, wc, pc, sc, zc = ref.fused_pipe_body_ref(
                alpha, beta, xc, rc, wc, pc, sc, zc, n)
        return (xc, rc, wc, pc, sc, zc, gamma, alpha)

    def fused_bicgstab_body(_, c):
        yc, rc, wc, pc, sc, zc, tc, vc, alpha, rho = c
        if use_pallas:
            vc, qc, yi, parts = ops.bicgstab_spmv_dots(
                jnp.pad(zc, 1), zc, rc, wc, sc, rhat, tc, alpha, stencil)
        else:
            vc, qc, yi, parts = ref.bicgstab_spmv_dots_ref(
                jnp.pad(zc, 1), zc, rc, wc, sc, rhat, tc, alpha,
                stencil=stencil)
        qy, yy, _qq, rhq, rhy, rht, rhv, rhz, rhs = parts
        omega = qy / yy
        rho_new = rhq - omega * rhy
        beta = (rho_new / rho) * (alpha / omega)
        if use_pallas:
            yc, rc, wc = ops.bicgstab_update1(alpha, omega, yc, pc, qc, yi,
                                              tc, vc)
            tc, pc, sc, zc = ops.bicgstab_spmv_update(
                jnp.pad(wc, 1), wc, rc, pc, sc, zc, vc, omega, beta, stencil)
        else:
            yc, rc, wc = ref.bicgstab_update1_ref(alpha, omega, yc, pc, qc,
                                                  yi, tc, vc)
            tc, pc, sc, zc = ref.bicgstab_spmv_update_ref(
                jnp.pad(wc, 1), wc, rc, pc, sc, zc, vc, omega, beta,
                stencil=stencil)
        rhw = rhy - omega * (rht - alpha * rhv)
        alpha = rho_new / (rhw + beta * (rhs - omega * rhz))
        return (yc, rc, wc, pc, sc, zc, tc, vc, alpha, rho_new)

    bicg_init = (x, r, w, p, s, z, t, v, one, one)
    inits = {
        "cg_classic_jit": ((x, r, p, rr), classic_body, "jit"),
        "cg_merged_jit": ((x, r, p, s, w, rr, delta, inf, one), merged_body,
                          "jit"),
        "cg_pipe_jit": ((x, r, w, p, s, z, inf, one), pipe_body, "jit"),
        "bicgstab_merged_jit": (bicg_init, bicgstab_merged_body, "jit"),
        "fused_iteration": ((x, r, p, s, w, rr, delta, inf, one), fused_body,
                            fused_impl),
        "fused_pipe_iteration": ((x, r, w, p, s, z, inf, one),
                                 fused_pipe_body, fused_impl),
        "fused_bicgstab_iteration": (bicg_init, fused_bicgstab_body,
                                     fused_impl),
    }
    runners = {"cg_classic_kernels": (cg_classic_kernels, "fork-join"),
               "bicgstab_classic_kernels": (bicgstab_classic_kernels,
                                            "fork-join")}
    for name, (init, body, impl) in inits.items():
        loop = jax.jit(lambda init, body=body: lax.fori_loop(
            0, n_iters, body, init))
        runners[name] = ((lambda loop=loop, init=init:
                          jax.block_until_ready(loop(init))), impl)
    return runners


def bench_grid(shape, stencil, *, use_pallas: bool, n_iters: int,
               repeats: int) -> dict:
    state = _state(shape, jnp.float64, n=9)
    rows = {}
    for name, (run, impl) in _runners(stencil, n_iters, state,
                                      use_pallas).items():
        run()                                   # warm-up / compile
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        rows[name] = {"per_iter_s": min(ts) / n_iters, "impl": impl}

    # the autotuner row: what a classic solve with pallas="auto" actually
    # executes at this grid.  Below the crossover the decision is the XLA
    # fallback, so the row IS the jitted loop's measurement (deterministic
    # ratio, honest label); above it (TPU) the fused kernel path stands in.
    dec = autotune.resolve(stencil.name, shape, jnp.float64)
    if dec.use_pallas:
        rows["cg_classic_kernels_auto"] = {
            "per_iter_s": rows["fused_iteration"]["per_iter_s"],
            "impl": f"pallas(bz={dec.bz})", "tune_source": dec.source}
    else:
        rows["cg_classic_kernels_auto"] = {
            "per_iter_s": rows["cg_classic_jit"]["per_iter_s"],
            "impl": "xla-fallback(cg_classic_jit)", "tune_source": dec.source}

    gates = {}
    for fused, baseline in FUSED_GATES.items():
        gates[f"{fused}_vs_{baseline}"] = {
            "ratio": rows[baseline]["per_iter_s"] / rows[fused]["per_iter_s"],
            "min": GATE_MIN, "rows": [fused, baseline]}
    gates["auto_vs_cg_classic_jit"] = {
        "ratio": (rows["cg_classic_kernels_auto"]["per_iter_s"]
                  / rows["cg_classic_jit"]["per_iter_s"]),
        "max": AUTO_GATE_MAX,
        "rows": ["cg_classic_kernels_auto", "cg_classic_jit"]}
    return {"rows": rows, "gates": gates}


def check_record(path: str) -> dict:
    """The artifact-level regression gate, run by CI against the freshly
    written smoke record:

    * every per-grid gate must hold (fused >= its fork-join baseline with
      the declared tolerance band; the autotuner row <= the jitted classic
      loop × 1.1) — a refactor that silently slows a fused body fails the
      build even if the bench itself ran;
    * every gated row must carry the implementation that ACTUALLY executed
      — and it must be a measurement: ``pallas-interpret`` (the emulator)
      in a gated row means the comparison silently didn't time the kernel.
    """
    with open(path) as f:
        record = json.load(f)
    bad: list[str] = []
    for key, grid in record["grids"].items():
        for gname, gate in grid["gates"].items():
            for row in gate["rows"]:
                impl = grid["rows"].get(row, {}).get("impl")
                if not impl:
                    bad.append(f"{key}:{row}: gated row has no impl label")
                elif impl == "pallas-interpret":
                    bad.append(
                        f"{key}:{row}: gated row ran the interpret-mode "
                        f"emulator, not the kernel")
            if "min" in gate and gate["ratio"] < gate["min"]:
                bad.append(f"{key}:{gname}: ratio {gate['ratio']:.2f} "
                           f"< {gate['min']}")
            if "max" in gate and gate["ratio"] > gate["max"]:
                bad.append(f"{key}:{gname}: ratio {gate['ratio']:.2f} "
                           f"> {gate['max']}")
    if bad:
        raise SystemExit(f"[bench_kernels] {path}: " + "; ".join(bad))
    ratios = {k: {g: round(gate["ratio"], 2)
                  for g, gate in grid["gates"].items()}
              for k, grid in record["grids"].items()}
    print(f"[bench_kernels] {path}: all gates hold on "
          f"{sorted(record['grids'])} ({ratios})")
    return record


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid + few repeats (the CI regression gate)")
    ap.add_argument("--check", metavar="JSON",
                    help="don't bench: assert an existing BENCH_kernels.json "
                         "still passes every per-grid gate + impl honesty")
    ap.add_argument("--stencil", default="27pt", choices=["7pt", "27pt"])
    ap.add_argument("--iters", type=int, default=None,
                    help="iterations per timed run (amortises dispatch "
                         "noise; default 50, smoke 5)")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--pallas", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="back the fused iterations with the Pallas kernels "
                         "(default: only on a real TPU — interpret mode is "
                         "an emulator, not a measurement, and fails --check)")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_kernels.json"))
    args = ap.parse_args(argv)

    if args.check:
        return check_record(args.check)

    enable_f64()
    use_pallas = (jax.default_backend() == "tpu" if args.pallas is None
                  else args.pallas)
    n_iters = args.iters or (5 if args.smoke else 50)
    repeats = args.repeats or (2 if args.smoke else 5)
    grids = SMOKE_GRIDS if args.smoke else GRIDS
    stencil = STENCILS[args.stencil]

    record = {
        "meta": {
            "backend": jax.default_backend(),
            "fused_impl": _impl_label(use_pallas),
            "dtype": "float64",
            "stencil": args.stencil,
            "iters_per_run": n_iters,
            "repeats": repeats,
            "smoke": bool(args.smoke),
        },
        "grids": {},
    }
    for shape in grids:
        key = "x".join(map(str, shape))
        res = record["grids"][key] = bench_grid(
            shape, stencil, use_pallas=use_pallas, n_iters=n_iters,
            repeats=repeats)
        for name, row in res["rows"].items():
            csv(f"bench_kernels_{key}_{name}", row["per_iter_s"] * 1e6,
                f"per_iter_us={row['per_iter_s'] * 1e6:.1f} "
                f"impl={row['impl']}")
        for gname, gate in res["gates"].items():
            csv(f"bench_kernels_{key}_{gname}", 0.0,
                f"ratio={gate['ratio']:.2f}")
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[bench_kernels] wrote {args.out}")
    # one trajectory-history row per fused body × grid (PR-8 helper)
    hist = os.path.splitext(args.out)[0] + "_history.jsonl"
    for key, grid in record["grids"].items():
        for fused, baseline in FUSED_GATES.items():
            row = grid["rows"][fused]
            trajectory_append(hist, trajectory_row(
                "kernels", smoke=bool(args.smoke), stencil=args.stencil,
                grid=key, kernel=fused, impl=row["impl"],
                per_iter_s=row["per_iter_s"],
                ratio_vs_baseline=grid["gates"]
                [f"{fused}_vs_{baseline}"]["ratio"]))
    print(f"[bench_kernels] appended {hist}")
    # the regression gate: fusion losing to the fork-join kernel baseline
    # means a kernel (or its dispatch structure) regressed — fail loudly.
    # Same criterion as the standalone --check mode, by construction.
    check_record(args.out)
    return record


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
