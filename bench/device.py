"""The accelerator a run measures, and the published peaks it is held to.

Peaks are keyed by JAX's ``device_kind``.  A device that is not in the
table is an error, never a default: a share of a peak that was guessed
is no measurement.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # dense bf16 FLOP/s of one chip
    hbm_bytes_s: float       # HBM bandwidth of one chip, bytes/s
    hbm_bytes: float         # HBM capacity of one chip, bytes
    source: str


# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16e9,
                         source="Google Cloud documentation, TPU v5e"),
}


class NoChip(RuntimeError):
    """JAX found no accelerator, fewer chips than the cell asks for, or a
    device whose peaks are unknown."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise NoChip(f"no published peaks for device_kind {device_kind!r}; "
                     f"known: {sorted(PEAKS)}") from None


def require_chips(chips: int):
    """The first ``chips`` accelerator devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips, JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def describe(devices) -> dict:
    """The ``device`` entry of a result line: platform, kind, count and the
    peak bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
