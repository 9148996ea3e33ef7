"""Peak rates of the chips this benchmark may run on, keyed by jax's
`device_kind`.  A kind that is not here is an error, never a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peak(device_kind: str, what: str) -> float:
    try:
        return float(PEAKS[device_kind][what])
    except KeyError:
        raise KeyError(
            f"no {what} peak for device kind {device_kind!r} in "
            f"benchmarks/peaks.py (known: {sorted(PEAKS)})") from None
