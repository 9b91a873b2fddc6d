"""Published per-chip peaks the benchmark divides by.

Copied from ``paddle_tpu/device/peaks.py`` so that a later PR cannot move
the yardstick. Source: Google Cloud TPU documentation, "TPU v5e" system
architecture page, per-chip figures: 197 TFLOP/s bf16, 16 GB HBM at
819 GB/s. A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    # substring of jax's device_kind, lower-cased
    "v5 lite": {"chip": "v5e", "bf16_flops": 197e12, "hbm_bytes": 16e9,
                "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e (per chip)"},
}


def peaks_for(device_kind: str) -> dict:
    low = device_kind.lower()
    for sub, row in PEAKS.items():
        if sub in low:
            return row
    raise LookupError("no published peaks for device_kind=%r in "
                      "benchmark/lib/peaks.py" % device_kind)
