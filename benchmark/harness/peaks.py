"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture (197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip), as
``bench.py``'s ``DEVICE_PEAKS`` holds them. No metric reads this table yet;
it is here for the first ``<kernel>_roofline`` reader, which may not bring
its own. A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: str) -> dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.py with its source"
        ) from None
