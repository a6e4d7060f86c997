"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device that is not listed is an error."""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip. JAX reports the chip as "TPU v5 lite".
_V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
