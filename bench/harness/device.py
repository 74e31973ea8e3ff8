"""The chip a run stands on: the check that it is there, and its peaks.

Peaks are published figures, keyed by the ``device_kind`` JAX reports. A
device that is not in the table is an error: a share of an unknown peak is
not a number.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip.

Utilization against the bf16 peak: the tower keeps float32 parameters, but
XLA runs a float32 matrix multiplication on the TPU at its default precision
as bf16 passes of the MXU, so bf16 is the peak those matmuls can reach.
"""
from __future__ import annotations

import sys

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or exit 2 without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "the benchmark runs on TPU only", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
