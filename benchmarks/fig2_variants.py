"""Fig. 2: classical vs proposed variants — execution time + barrier traces.

Two parts:
  (a) box-whisker execution times (median/q1/q3 of 10 runs) of CG vs CG-NB
      and BiCGStab vs B1 on one device (the paper's same-resources protocol),
  (b) the Fig. 1 trace argument, structurally: an 8-device subprocess lowers
      one iteration of each method and reports per-all-reduce overlap slack
      from the compiled HLO (zero-slack == the blocking barriers the arrows
      mark in the paper's Paraver traces).

Both parts route through ``repro.api``: part (a) uses ``SolverSession`` with
the facade's warm-up/blocked timing; part (b) uses ``SolverSession.step_fn``
with the paper-faithful operator options.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import csv
from repro.api import SolverOptions, SolverSession, variant_pairs
from repro.core.problems import enable_f64

# The "algo" (fusion-disabled) view needs --xla_disable_hlo_passes, which
# this jaxlib cannot take per-compile (repeated proto field); the parent runs
# this script twice with the passes disabled via XLA_FLAGS instead.
_TRACE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
import sys, json
sys.path.insert(0, "src")
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.api import SolverOptions, SolverSession
from repro.analysis.hlo import overlap_slack
from repro.core.compat import make_mesh
from repro.core.distributed import step_state_layout
from repro.core.problems import make_problem

view = os.environ.get("TRACE_VIEW", "fused")
mesh = make_mesh((2, 4), ("data", "model"))
prob = make_problem((32, 32, 32), "27pt", dtype=jnp.float32)
b = prob.b()
out = {}
for m in ("cg", "cg_nb", "bicgstab", "bicgstab_b1"):
    # paper-faithful implementation for the structural trace (the conv/concat
    # traffic optimisations shift XLA fusion boundaries and obscure the
    # algorithm-level dependence structure)
    sess = SolverSession(prob, method=m, mesh=mesh, options=SolverOptions(
        f64=False, halo_mode="scatter",
        matvec_padded=prob.stencil.matvec_padded))
    fn, layout = sess.step_fn()
    sh = NamedSharding(mesh, layout.spec())
    vecs, scals = step_state_layout(m)   # derived from the MethodDef
    args = ([jax.device_put(b, sh)] * (1 + len(vecs))
            + [jnp.array(1.0, jnp.float32)] * len(scals))
    c = jax.jit(fn).lower(*args).compile()
    rep = [r for r in overlap_slack(c.as_text())
           if r["op"].startswith("all-reduce")]
    out[m] = {view: [round(r["slack_bytes"]) for r in rep]}
print(json.dumps(out))
"""


def _run_trace(view: str) -> dict:
    env = dict(os.environ)
    env["TRACE_VIEW"] = view
    # an analysis on host devices: the child must never reach for the
    # accelerator the parent already holds
    env["JAX_PLATFORMS"] = "cpu"
    if view == "algo":   # algorithm-level dependence structure, unfused
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_disable_hlo_passes="
                            "fusion,cpu-instruction-fusion").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _TRACE_SCRIPT], capture_output=True, text=True,
        timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"fig1 trace ({view}) subprocess failed:\n"
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> None:
    enable_f64()      # paper precision; owned by the driver, not the facade
    n = 64
    # the paper's (classical, nonblocking-variant) comparisons only — the
    # preconditioned forms are variants in lineage, not in barrier structure
    from repro.api import REGISTRY
    krylov_pairs = [(base, var) for base, var in variant_pairs()
                    if base in ("cg", "bicgstab")
                    and not REGISTRY[var].accepts_precond]
    for stencil in ("7pt",):
        base = {}
        for classical, variant in krylov_pairs:
            for method in (classical, variant):
                sess = SolverSession(
                    method=method, grid=(n, n, n), stencil=stencil,
                    options=SolverOptions(tol=1e-6, maxiter=700,
                                          layout="local"))
                res, t = sess.timed_solve(repeats=10)
                per_iter = t["median"] / max(int(res.iters), 1)
                base[method] = t["median"]
                csv(f"fig2_{stencil}_{method}", t["median"] * 1e6,
                    f"iters={int(res.iters)};per_iter_us={per_iter*1e6:.1f};"
                    f"q1={t['q1']*1e6:.0f};q3={t['q3']*1e6:.0f}")
        csv("fig2_cgnb_vs_cg_ratio", 0.0,
            f"ratio={base['cg_nb']/base['cg']:.3f}")
        csv("fig2_b1_vs_bicgstab_ratio", 0.0,
            f"ratio={base['bicgstab_b1']/base['bicgstab']:.3f}")

    # structural barrier trace (Fig. 1 analogue): one subprocess per view
    slacks: dict = {}
    for view in ("algo", "fused"):
        for m, views in _run_trace(view).items():
            slacks.setdefault(m, {}).update(views)
    vec = 32 ** 3 * 4 // 8
    for m, views in slacks.items():
        for view, sl in views.items():
            hard = sum(1 for s in sl if s < vec)
            csv(f"fig1_trace_{m}_{view}", 0.0,
                f"allreduce_slack_bytes={sl};hard_barriers={hard}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
