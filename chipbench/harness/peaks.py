"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device missing from the table is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
    "TPU v5 lite": dict(bf16_flops_per_s=197e12, int8_ops_per_s=393e12,
                        hbm_bytes=16e9, hbm_bytes_per_s=819e9,
                        source="Google Cloud documentation, TPU v5e"),
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} (have "
            f"{sorted(PEAKS)}); add its published figures to "
            f"chipbench/harness/peaks.py") from None
