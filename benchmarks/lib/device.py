"""The device a run is on: what JAX reports, the table of published peaks,
and the memory peak. A device that is not in the table is an error."""
from __future__ import annotations

import sys

# Peaks of one chip, keyed by the exact ``device_kind`` a JAX device reports.
# Source: Google Cloud TPU documentation, system-architecture page "TPU v5e":
# 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device, what: str) -> float:
    kind = device.device_kind
    if kind not in PEAKS:
        raise ValueError(f"no published peak for device_kind {kind!r}; add it "
                         "with its source to benchmarks/lib/device.py")
    return PEAKS[kind][what]


def take_devices(chips: int, tiny: bool):
    """The devices this cell runs on. Without ``tiny`` they are TPU chips or
    the process exits non-zero with the reason; there is no CPU path."""
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if (not on_tpu and not tiny) or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX found {len(devices)}"
              f" x {devices[0].platform} ({devices[0].device_kind}). A run "
              "off the chip exists only as --tiny, which prints no metric.",
              file=sys.stderr)
        raise SystemExit(1)
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip as the runtime counts it. On this
    runtime ``peak_bytes_in_use`` covers live arrays (weights, optimizer
    state, cache pool, batches) and ``peak_bytes_reserved`` what loaded
    programs hold for their temporaries (12.31 GB for the BERT-base step
    whose compiler plans 12.42 GB), so the peak is their sum; a backend
    that reports neither gives 0."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def record(devices, memory_peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}
