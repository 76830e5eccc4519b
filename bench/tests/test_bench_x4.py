"""The harness's four-chip path (the float32 configuration on a 1-D z split)
at a tiny size on four CPU devices, in a child interpreter (the device count
is fixed when JAX starts): a sound run is correct; with the halo exchange
between chips left out, or a step that returns its state unchanged, it is
not.  No four-chip cell is in BENCHMARK.json yet (PERF.md, Open questions);
this keeps the path that one will use checked."""

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]

CHILD = r"""
import dataclasses, json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax.numpy as jnp
from bench import harness
import repro.core.distributed as dist

spec = harness.load_spec()
spec["workloads"].append({"name": "x4", "config": "hpcg27-cg-f32-b512",
                          "traffic": "seq-rhs4", "chips": 4})
_, config, _ = harness.load_cell(spec, "x4")
config = dict(config, block_per_chip=[16, 16, 8])

def run():
    r = harness.run_cell(spec, "x4", 2**31 + 7, 0.3, False,
                         t_proc0=time.perf_counter(), require_tpu=False,
                         config=config)
    return {"correct": r["correct"], "count": r["device"]["count"],
            "residual": r["checks"]["true_rel_residual"]["value"]}

out = {"sound": run()}
real_pad = dist.DistributedOp._pad_exchange_concat
dist.DistributedOp._pad_exchange_concat = lambda self, x: jnp.pad(x, 1)
out["no_exchange"] = run()
dist.DistributedOp._pad_exchange_concat = real_pad
real_run = dist.run_method
dist.run_method = lambda mdef, *a, **kw: real_run(
    dataclasses.replace(mdef, step=lambda ops, st: st), *a, **kw)
out["step_unchanged"] = run()
print(json.dumps(out))
"""


def test_four_device_run_and_its_faults():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"] == {"correct": True, "count": 4,
                            "residual": out["sound"]["residual"]}
    assert out["no_exchange"]["correct"] is False
    assert out["no_exchange"]["residual"] > 1e-3
    assert out["step_unchanged"]["correct"] is False
