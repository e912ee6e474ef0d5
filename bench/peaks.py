"""Peaks of each chip the benchmark may run on, keyed by ``device_kind``.

A roofline share is a measured time set against these numbers, so a device
whose kind is not listed raises: a share of a peak nobody recorded is not a
number.  Kept here, with the benchmark, so a change to the program cannot
move the yardstick.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """One chip.  ``modelled`` names the fields that are not published."""

    hbm_bytes_per_s: float
    hbm_bytes: float
    bf16_flops: float
    f32_flops: float
    source: str
    modelled: tuple[str, ...] = ()


# Google Cloud documentation, "TPU v5e" (system architecture page): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.  The f32 rate of
# the vector unit is not published; 1.9 TFLOP/s is a model (8 sublanes x 128
# lanes x 2 flops per FMA x ~0.94 GHz).  Both SU3 kernels are bound by HBM
# bytes at any f32 rate above 1.2 TFLOP/s, so the model only matters below it.
TPU_V5E = Peaks(
    hbm_bytes_per_s=819e9,
    hbm_bytes=16e9,
    bf16_flops=197e12,
    f32_flops=1.9e12,
    source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
           "16 GB HBM at 819 GB/s; f32 vector rate modelled",
    modelled=("f32_flops",),
)

PEAKS_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def for_device_kind(kind: str) -> Peaks:
    """The peaks of the chip ``jax.Device.device_kind`` names; raises on an
    unknown kind."""
    try:
        return PEAKS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {kind!r}; add its published "
            "figures to bench/peaks.py with their source") from None


def flops_for_dtype(peaks: Peaks, dtype: str) -> float:
    """The compute peak a kernel storing ``dtype`` words is held to."""
    return peaks.bf16_flops if dtype == "bfloat16" else peaks.f32_flops
