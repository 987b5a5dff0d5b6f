"""The device a run measures, and its published peaks.

Peaks are keyed by ``device_kind`` as JAX reports it; a kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import jax

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoAccelerator(RuntimeError):
    pass


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def require(chips: int) -> list:
    """The devices a cell runs on: ``chips`` TPUs, or raise."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, found {len(devs)}")
    peaks(devs[0].device_kind)  # an unknown kind fails before any work
    return devs[:chips]


def describe(devs: list) -> dict:
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes(devs: list) -> int:
    """Peak bytes on the fullest chip (0 where not reported): the peak of
    the allocator's buffers plus the peak of the memory the TPU runtime
    reserves for compiled programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak
