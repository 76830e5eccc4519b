"""Distributed solver demo: the paper's weak-scaling experiment in miniature.

Spawns a subprocess with 8 host devices and runs CG-NB twice through the SAME
``repro.api.solve`` call — once forced local, once on the paper-faithful 1-D
z decomposition (``layout="1d"`` resolves to shard_map over all 8 devices) —
and verifies the two backends agree; then prints the TPU-projected
weak-scaling table from the roofline model.

PYTHONPATH=src python examples/solver_scaling.py
"""

import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax.numpy as jnp
from repro.api import SolverOptions, solve
from repro.core.problems import enable_f64

enable_f64()      # paper precision; the facade no longer flips x64 itself

opts = SolverOptions(tol=1e-6, maxiter=300)
kw = dict(method="cg_nb", grid=(32, 32, 64), stencil="27pt", options=opts)
res = solve(layout="1d", **kw)       # shard_map over 8 devices (HPCCG layout)
ref = solve(layout="local", **kw)    # single-device reference
print(f"distributed: iters={int(res.iters)} res={float(res.res_norm):.2e}  "
      f"(single-device: iters={int(ref.iters)}) "
      f"max|dx|={float(jnp.abs(res.x-ref.x).max()):.2e}")
"""

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the demo's mesh is 8 host devices: the child never touches a chip
    subprocess.run([sys.executable, "-c", _SCRIPT], cwd=root, check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})

    sys.path.insert(0, os.path.join(root))
    sys.path.insert(0, os.path.join(root, "src"))
    from benchmarks.scaling_model import weak_efficiency

    print("\nTPU-projected weak-scaling efficiency (27pt, 128^3/chip, "
          "noisy-fabric regime):")
    print("chips     :  " + "  ".join(f"{n:>6d}" for n in (8, 64, 512, 4096)))
    # cg_merged pays the all-reduce latency ONCE per iteration, cg_pipe
    # additionally hides it behind the SpMV (PR 4, docs/API.md
    # §Reduction-hiding variants)
    for m in ("cg", "cg_nb", "cg_merged", "cg_pipe"):
        effs = [weak_efficiency(m, 27, n, noise="noisy")
                for n in (8, 64, 512, 4096)]
        print(f"{m:10s}:  " + "  ".join(f"{e:6.3f}" for e in effs))
