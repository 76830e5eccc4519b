"""Shared benchmark utilities: the per-device peaks table (and the v5e
hardware model the scaling/roofline projections target), the repo's CSV
line format, and the
benchmark *trajectory* — an append-only JSONL history of runs.

Timing lives in ``repro.api.timing`` (warm-up + ``block_until_ready``; the
paper reports medians of 10 repetitions); the measured benchmarks reach it
through ``SolverSession.timed_solve``.

``BENCH_*.json`` files are overwritten per run (the CI gate checks the
latest record); the trajectory files (``BENCH_*_history.jsonl``) are
*appended* so a regression can be dated: every row carries the git sha,
device kind, dtype and a wall-clock timestamp next to the numbers.
"""

from __future__ import annotations

import json
import subprocess
import time

#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
#: of HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip over 4 links.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9, "ici_bytes_per_s_link": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in ``PEAKS`` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; add them to benchmarks.common.PEAKS with their "
                       f"source") from None


#: the chip the scaling model and the dry-run roofline project onto
MODEL_DEVICE = "TPU v5 lite"
PEAK_FLOPS = peaks(MODEL_DEVICE)["flops_bf16"]
HBM_BW = peaks(MODEL_DEVICE)["hbm_bytes_per_s"]
ICI_BW = peaks(MODEL_DEVICE)["ici_bytes_per_s_link"]
ALLREDUCE_LAT = 5e-6         # base latency per hop-stage (model parameter)


def csv(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def git_sha() -> str | None:
    """The current commit (short sha), or None outside a work tree."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10)
    except OSError:
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def trajectory_row(bench: str, **payload) -> dict:
    """One history row: provenance columns (sha, device kind, dtype,
    timestamp) + the bench's own numbers.  Device/dtype come from jax at
    call time so the row records what actually ran, not what was asked."""
    import jax
    import jax.numpy as jnp

    return {
        "bench": bench,
        "t_wall": time.time(),
        "git_sha": git_sha(),
        "device": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "dtype": str(jnp.zeros(()).dtype),
        **payload,
    }


def trajectory_append(path: str, row: dict) -> None:
    """Append one row to a JSONL trajectory file (never overwrites —
    the point of the history is that old rows survive new runs)."""
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
