"""Comms/donation audit: the registry's communication metadata vs compiled HLO.

For every registry method the audit lowers the shard_map *iteration body*
(``solve_step_shardmap`` — one step == one while-loop body, guaranteed by
tests/test_step_parity.py) on three mesh shapes (1-D/2-D/3-D over 8 host
devices), both blocking and overlapped halo modes, with and without the
Pallas fused body where the method declares one, and with a bound Jacobi
preconditioner where it accepts one — then asserts on the compiled HLO:

* ``all-reduce`` count == ``SolverSpec.allreduces_per_iter`` (+ the
  preconditioner's ``extra_reductions_per_apply`` × applies);
* ``collective-permute`` count == halo exchanges × 2 × split dims, where
  halo exchanges = ``halo_exchanges_per_iter`` (+ the preconditioner's
  ``halo_matvecs_per_apply`` × applies);
* **no other collective at all** — an accidental ``all-gather`` (the
  classic symptom of a lost sharding annotation) or an unfused psum pair
  fails the audit by construction;
* collective *bytes* equal to the committed AUDIT.json baseline — counts
  catch structural drift, bytes catch payload drift (a state-layout change
  that keeps the collective count but moves the traffic).

Donation: the whole-solve function is lowered with ``donate_argnums=(1,)``
(exactly what ``SolverSession`` passes when ``SolverOptions.donate`` is
set) and the audit asserts ONE donation annotation with donation on —
``tf.aliasing_output`` on the local path, ``jax.buffer_donor`` once
shardings are attached — and ZERO with it off, for every method on the
local path and both mesh shapes; for representative methods it further
compiles the mesh solve and asserts XLA *granted* the alias
(``input_output_alias`` names parameter 1, i.e. x0's buffer is reused).

Measurements run in a fresh subprocess (`worker_main`) because host-device
count is fixed at jax import; the parent process builds expectations from
the registry and compares.  ``python -m repro.analysis`` drives this.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.analysis.violation import Violation
from repro.api.registry import REGISTRY, SolverSpec

#: audit meshes: name -> (device grid, axis names, # grid dims actually split)
MESHES: dict[str, tuple[tuple[int, ...], tuple[str, ...], int]] = {
    "1d": ((8,), ("cells",), 1),
    "2d": ((2, 4), ("data", "model"), 2),
    "3d": ((2, 2, 2), ("pod", "data", "model"), 3),
}
N_DEVICES = 8
GRID = (8, 8, 16)            # divisible by every audit mesh layout
STENCIL = "27pt"
#: mesh shapes the donation lowering runs on (the ">= 2 mesh shapes" gate)
DONATION_MESHES = ("1d", "2d")
#: methods whose mesh solve is fully compiled to check the granted alias
ALIAS_METHODS = ("cg", "cg_merged", "bicgstab")
#: methods whose whole mesh solve is compiled plain AND guarded to assert
#: the breakdown guards ride existing carry scalars — identical collective
#: counts with guards on (repro.resilience's zero-extra-collectives claim)
GUARD_METHODS = ("cg", "cg_merged", "bicgstab_merged")
#: the preconditioner bound for the precond-accepting methods' extra configs
AUDIT_PRECOND_SWEEPS = 2


def _precond_meta() -> dict[str, int]:
    """Cost metadata of the audit's Jacobi preconditioner instance."""
    from repro.precond import PointJacobi
    p = PointJacobi(sweeps=AUDIT_PRECOND_SWEEPS)
    return {
        "extra_reductions_per_apply": p.extra_reductions_per_apply,
        "halo_matvecs_per_apply": p.halo_matvecs_per_apply,
    }


def comms_jobs(registry: dict[str, SolverSpec] | None = None) -> list[dict]:
    """The comms audit matrix.  Key: ``method|mesh|halo|kernel|precond``."""
    registry = REGISTRY if registry is None else registry
    jobs = []

    def add(method, mesh, halo, kern="xla", prec="none"):
        jobs.append(dict(key=f"{method}|{mesh}|{halo}|{kern}|{prec}",
                         method=method, mesh=mesh, halo=halo,
                         pallas=(kern == "pallas"), precond=prec))

    for name in sorted(registry):
        spec = registry[name]
        add(name, "1d", "concat")
        add(name, "1d", "overlap")
        add(name, "2d", "auto")
        add(name, "3d", "auto")
        if spec.accepts_precond:
            add(name, "1d", "auto", prec="jacobi")
            add(name, "2d", "auto", prec="jacobi")
        if spec.has_fused_body:
            add(name, "1d", "auto", kern="pallas")
            add(name, "2d", "auto", kern="pallas")
    return jobs


def expected_comms(spec: SolverSpec, mesh: str, *,
                   precond: str = "none",
                   precond_meta: dict[str, int] | None = None) -> dict[str, int]:
    """Collective counts the registry metadata predicts for one config."""
    n_split = MESHES[mesh][2]
    allreduce = spec.allreduces_per_iter
    halos = spec.halo_exchanges_per_iter
    if precond != "none":
        meta = precond_meta or _precond_meta()
        allreduce += (spec.precond_applies_per_iter
                      * meta["extra_reductions_per_apply"])
        halos += (spec.precond_applies_per_iter
                  * meta["halo_matvecs_per_apply"])
    return {
        "all-reduce": allreduce,
        "collective-permute": halos * 2 * n_split,
        "all-gather": 0,
        "reduce-scatter": 0,
        "all-to-all": 0,
    }


# =============================================================================
# Measurement worker (runs in a subprocess with 8 host devices)
# =============================================================================

def worker_main() -> None:
    """Measure every job; print one JSON record on the last stdout line.

    Reads an optional JSON filter from stdin: ``{"methods": [...]}``
    restricts the matrix (used by the fast subset test).  Must run in a
    fresh process: host-device count is fixed at jax import.
    """
    import jax
    jax.config.update("jax_enable_x64", True)
    from jax.sharding import NamedSharding

    from repro.analysis.hlo import (
        collective_stats,
        count_collectives,
        donation_markers,
        input_output_aliases,
    )
    from repro.core.compat import make_mesh
    from repro.core.distributed import (
        solve_shardmap,
        solve_step_shardmap,
        step_state_layout,
    )
    from repro.core.methods import Ops, get_method, run_method
    from repro.core.problems import make_problem
    from repro.core.solvers import LocalOp
    from repro.precond import PointJacobi

    raw = sys.stdin.read().strip()
    filt = json.loads(raw) if raw else {}
    methods = filt.get("methods")

    assert jax.device_count() == N_DEVICES, (
        f"worker needs {N_DEVICES} host devices, got {jax.device_count()} — "
        f"run via run_measurements() / the CLI, not directly")

    prob = make_problem(GRID, STENCIL)
    meshes = {name: make_mesh(devs, axes)
              for name, (devs, axes, _) in MESHES.items()}

    def precond_of(name):
        return PointJacobi(sweeps=AUDIT_PRECOND_SWEEPS) if name == "jacobi" \
            else None

    # --- comms: compiled iteration bodies -----------------------------------
    comms = {}
    for job in comms_jobs():
        if methods is not None and job["method"] not in methods:
            continue
        mesh = meshes[job["mesh"]]
        fn, layout = solve_step_shardmap(
            prob, job["method"], mesh, halo_mode=job["halo"],
            precond=precond_of(job["precond"]), pallas_fused=job["pallas"])
        sh = NamedSharding(mesh, layout.spec())
        vecs, scals = step_state_layout(job["method"])
        arr = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
        scal = jax.ShapeDtypeStruct((), prob.dtype)
        args = [arr] * (1 + len(vecs)) + [scal] * len(scals)
        txt = jax.jit(fn).lower(*args).compile().as_text()
        stats = collective_stats(txt)
        comms[job["key"]] = {
            "counts": {op: s["count"] for op, s in sorted(stats.items())},
            "bytes": sum(s["bytes"] for s in stats.values()),
        }

    # --- donation on the mesh paths (lowered markers) -----------------------
    donate_mesh = {}
    for name in sorted(REGISTRY):
        if methods is not None and name not in methods:
            continue
        for mesh_name in DONATION_MESHES:
            mesh = meshes[mesh_name]
            fn, layout = solve_shardmap(prob, name, mesh, maxiter=5)
            sh = NamedSharding(mesh, layout.spec())
            sds = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
            rec = {}
            for mode, jit_kw in (("on", dict(donate_argnums=(1,))),
                                 ("off", {})):
                txt = jax.jit(fn, **jit_kw).lower(sds, sds).as_text()
                rec[mode] = donation_markers(txt)
            donate_mesh[f"{name}|{mesh_name}"] = rec

    # --- local path: donation markers + zero collectives + granted alias ----
    local = {}
    for name in sorted(REGISTRY):
        if methods is not None and name not in methods:
            continue
        mdef = get_method(name)
        A = LocalOp(prob.stencil)

        def fn(b, x0, _mdef=mdef, _A=A):
            ops = Ops(_A, b, norm_ref=1.0)
            return run_method(_mdef, ops, x0, tol=1e-6, maxiter=5)

        sds = jax.ShapeDtypeStruct(prob.shape, prob.dtype)
        lowered_on = jax.jit(fn, donate_argnums=(1,)).lower(sds, sds)
        compiled = lowered_on.compile()
        ctext = compiled.as_text()
        local[name] = {
            "markers_on": donation_markers(lowered_on.as_text()),
            "markers_off": donation_markers(jax.jit(fn).lower(sds, sds)
                                            .as_text()),
            "collectives": count_collectives(ctext),
            "aliased_params": input_output_aliases(ctext),
        }

    # --- granted alias on a compiled mesh solve (representative set) --------
    mesh_aliases = {}
    for name in ALIAS_METHODS:
        if methods is not None and name not in methods:
            continue
        mesh = meshes["1d"]
        fn, layout = solve_shardmap(prob, name, mesh, maxiter=5)
        sh = NamedSharding(mesh, layout.spec())
        sds = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
        ctext = jax.jit(fn, donate_argnums=(1,)).lower(sds, sds).compile() \
                   .as_text()
        mesh_aliases[f"{name}|1d"] = input_output_aliases(ctext)

    # --- guard invariance: arming the guards adds zero collectives ----------
    # The breakdown guards (repro.resilience) must ride scalars the loop
    # already carries post-psum; compile the WHOLE mesh solve plain and
    # guarded (telemetry off, no residual replacement — the raise-policy
    # configuration) and record each one's collective counts.
    from repro.core.methods import GuardSpec
    guard_invariance = {}
    for name in GUARD_METHODS:
        if methods is not None and name not in methods:
            continue
        mesh = meshes["1d"]
        rec = {}
        for mode, gs in (("plain", None), ("guarded", GuardSpec())):
            fn, layout = solve_shardmap(prob, name, mesh, maxiter=5,
                                        guard_spec=gs)
            sh = NamedSharding(mesh, layout.spec())
            sds = jax.ShapeDtypeStruct(prob.shape, prob.dtype, sharding=sh)
            ctext = jax.jit(fn).lower(sds, sds).compile().as_text()
            rec[mode] = count_collectives(ctext)
        guard_invariance[f"{name}|1d"] = rec

    print(json.dumps({"comms": comms, "donate_mesh": donate_mesh,
                      "local": local, "mesh_aliases": mesh_aliases,
                      "guard_invariance": guard_invariance}))


def run_measurements(methods: list[str] | None = None, *,
                     timeout: int = 1200) -> dict:
    """Run :func:`worker_main` in a subprocess with 8 host devices."""
    import repro
    # repro is a namespace package (no __init__.py): locate it via __path__
    pkg_dir = os.path.abspath(list(repro.__path__)[0])      # .../src/repro
    src = os.path.dirname(os.path.dirname(pkg_dir))         # repo root
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{N_DEVICES}").strip()
    env["PYTHONPATH"] = (os.path.join(src, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    # the audit compiles on host devices: never touch an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c",
         "from repro.analysis.audit import worker_main; worker_main()"],
        input=json.dumps({"methods": methods} if methods else {}),
        capture_output=True, text=True, timeout=timeout, env=env, cwd=src)
    if proc.returncode != 0:
        raise RuntimeError(
            f"audit worker failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# =============================================================================
# Comparison: measured vs registry expectations vs committed baseline
# =============================================================================

def compare(measured: dict,
            registry: dict[str, SolverSpec] | None = None,
            baseline: dict | None = None) -> list[Violation]:
    """Every contract breach between measurement, registry and baseline."""
    registry = REGISTRY if registry is None else registry
    meta = _precond_meta()
    out: list[Violation] = []

    # --- comms vs registry ---------------------------------------------------
    for key, rec in sorted(measured.get("comms", {}).items()):
        method, mesh, _halo, _kern, prec = key.split("|")
        spec = registry.get(method)
        if spec is None:
            out.append(Violation("comms", key, "method",
                                 expected="a registered method",
                                 actual="unknown"))
            continue
        want = expected_comms(spec, mesh, precond=prec, precond_meta=meta)
        counts = rec["counts"]
        for op, n in want.items():
            got = counts.get(op, 0)
            if got != n:
                out.append(Violation(
                    "comms", key, op, expected=n, actual=got,
                    detail="registry metadata vs compiled iteration body"))
        for op, got in counts.items():
            if op not in want and got:
                out.append(Violation(
                    "comms", key, op, expected=0, actual=got,
                    detail="unexpected collective opcode in the body"))

    # --- donation ------------------------------------------------------------
    for key, rec in sorted(measured.get("donate_mesh", {}).items()):
        if rec.get("on") != 1:
            out.append(Violation(
                "donation", key, "markers_on", expected=1,
                actual=rec.get("on"),
                detail="donate=True must annotate exactly x0 for donation"))
        if rec.get("off") != 0:
            out.append(Violation(
                "donation", key, "markers_off", expected=0,
                actual=rec.get("off"),
                detail="donate=False must not annotate any argument"))
    for name, rec in sorted(measured.get("local", {}).items()):
        if rec.get("markers_on") != 1:
            out.append(Violation(
                "donation", f"{name}|local", "markers_on", expected=1,
                actual=rec.get("markers_on")))
        if rec.get("markers_off") != 0:
            out.append(Violation(
                "donation", f"{name}|local", "markers_off", expected=0,
                actual=rec.get("markers_off")))
        if rec.get("collectives"):
            out.append(Violation(
                "comms", f"{name}|local", "collectives", expected={},
                actual=rec["collectives"],
                detail="single-device solve must compile collective-free"))
        if rec.get("aliased_params") != [1]:
            out.append(Violation(
                "donation", f"{name}|local", "input_output_alias",
                expected=[1], actual=rec.get("aliased_params"),
                detail="XLA must grant the x0 (param 1) buffer reuse"))
    for key, aliased in sorted(measured.get("mesh_aliases", {}).items()):
        if aliased != [1]:
            out.append(Violation(
                "donation", key, "input_output_alias",
                expected=[1], actual=aliased,
                detail="compiled mesh solve must reuse x0's buffer"))

    # --- guard invariance ----------------------------------------------------
    for key, rec in sorted(measured.get("guard_invariance", {}).items()):
        if rec.get("guarded") != rec.get("plain"):
            out.append(Violation(
                "guard_invariance", key, "collectives",
                expected=rec.get("plain"), actual=rec.get("guarded"),
                detail="arming the breakdown guards must add zero "
                       "collectives (guards ride carried post-psum "
                       "scalars)"))

    # --- drift vs the committed baseline ------------------------------------
    if baseline is not None:
        out += compare_baseline(measured, baseline)
    return out


def compare_baseline(measured: dict, baseline: dict) -> list[Violation]:
    """Exact equality against AUDIT.json (counts AND bytes)."""
    out: list[Violation] = []
    base = baseline.get("measured", baseline)
    for section in ("comms", "donate_mesh", "local", "mesh_aliases"):
        got, want = measured.get(section, {}), base.get(section, {})
        for key in sorted(set(got) | set(want)):
            if key not in want:
                out.append(Violation(
                    "baseline", f"{section}:{key}", "coverage",
                    expected="present in AUDIT.json", actual="new config",
                    detail="rewrite the baseline: make audit-write"))
            elif key not in got:
                out.append(Violation(
                    "baseline", f"{section}:{key}", "coverage",
                    expected=want[key], actual="config no longer measured"))
            elif got[key] != want[key]:
                out.append(Violation(
                    "baseline", f"{section}:{key}", "drift",
                    expected=want[key], actual=got[key],
                    detail="measured HLO drifted from the committed "
                           "baseline"))
    return out
