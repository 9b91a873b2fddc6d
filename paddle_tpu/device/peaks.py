"""Published per-chip peaks: the one table every utilization, roofline
and planning number in the tree divides by.

Source: Google Cloud TPU documentation, the per-chip figures of each
generation's system-architecture page ("TPU v4", "TPU v5e", "TPU v5p",
"TPU v6e"): peak bf16 FLOP/s, HBM capacity and bandwidth, and the chip's
inter-chip-interconnect bandwidth divided by its four links. A chip that
is not in the table is an error, never a default: a utilization computed
against the wrong peak is worse than none.
"""
from __future__ import annotations

from typing import NamedTuple

__all__ = ["ChipPeaks", "CHIP_PEAKS", "chip_name", "chip_peaks",
           "require_chip"]


class ChipPeaks(NamedTuple):
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes
    hbm_bytes_per_s: float
    ici_link_bytes_per_s: float


CHIP_PEAKS = {
    "v4": ChipPeaks(275e12, 32e9, 1228e9, 50e9),
    "v5e": ChipPeaks(197e12, 16e9, 819e9, 50e9),
    "v5p": ChipPeaks(459e12, 95e9, 2765e9, 100e9),
    "v6e": ChipPeaks(918e12, 32e9, 1640e9, 100e9),
}

# substrings of ``jax.Device.device_kind`` (lower-cased), first match wins
_KIND_TO_CHIP = (
    ("v5 lite", "v5e"), ("v5litepod", "v5e"), ("v5e", "v5e"),
    ("v5p", "v5p"),
    ("v6 lite", "v6e"), ("v6e", "v6e"),
    ("v4", "v4"),
)


def chip_name(device) -> str:
    """Table key for a jax device; raises for anything not in the table
    (a CPU, a GPU, or a TPU generation nobody entered peaks for)."""
    kind = getattr(device, "device_kind", "")
    if getattr(device, "platform", "") == "tpu":
        low = kind.lower()
        for sub, name in _KIND_TO_CHIP:
            if sub in low:
                return name
    raise ValueError(
        f"no published peaks for device_kind={kind!r} "
        f"(platform={getattr(device, 'platform', '?')!r}); known chips: "
        f"{sorted(CHIP_PEAKS)}. Add the chip to "
        f"paddle_tpu/device/peaks.py with its source.")


def chip_peaks(device) -> ChipPeaks:
    return CHIP_PEAKS[chip_name(device)]


def require_chip():
    """The device a measuring or proving program runs on: jax's first
    device if it is a TPU in the table, else an error that names what jax
    found. Such programs do not fall back to the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            "needs a TPU; jax found platform=%r (device_kind=%r, %d "
            "device(s)). Nothing was run."
            % (dev.platform, dev.device_kind, len(jax.devices())))
    chip_name(dev)
    return dev
