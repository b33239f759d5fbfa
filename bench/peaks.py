"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per
chip.  A device that is not in the table is an error, never a default: a
share of a peak is only worth reading against the chip it ran on.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 393e12,
                    "hbm_byte_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (the table has {sorted(PEAKS)})") from None
