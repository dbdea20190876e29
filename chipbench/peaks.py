"""Published peaks of each chip, keyed by JAX's ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
16 GB of HBM at 819 GB/s.  No float32 peak is published; the bfloat16
peak bounds any float32 path from above, so a share of it cannot pass
100% where the operations are counted right.

A chip that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to chipbench/peaks.py with their source (known: {sorted(PEAKS)})"
        ) from None
