"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``benchmarks/common.py::PEAKS`` so that the yardstick cannot
move with the program.  A device kind that is not listed here is an error,
never a default: a roofline share against a guessed peak means nothing.
"""

from __future__ import annotations

#: TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s,
#: 1,600 Gbit/s of chip-to-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

