"""Published peaks of the chips this benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, never
a default: a roofline share against a guessed peak is worse than none.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 and 393 TOP/s int8 per
chip, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per
chip.  JAX names the chip "TPU v5 lite".
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; the table "
            f"in benchmarks/lib/peaks.py has {sorted(PEAKS)}") from None
