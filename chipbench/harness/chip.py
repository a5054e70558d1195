"""The chip the benchmark runs on: device check and the table of peaks."""
from __future__ import annotations

import sys

# Published peaks per chip, keyed by JAX's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
# HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    pass


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a chip that is not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def require_chips(jax, n: int):
    """The first ``n`` accelerator devices, or ``NoChip`` when JAX found
    no accelerator or fewer than ``n``."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:n]


def device_record(devs) -> dict:
    """``device`` of the result line: platform, kind, count and the peak
    bytes in use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
