"""Published peaks of each device, keyed by ``device_kind``.

A device not in the table is an error: a roofline share against an
assumed peak is no measurement.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture table)
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2 at "
                  "819 GB/s, 197 TFLOP/s bf16, 1,600 Gbit/s ICI per chip",
    },
}


def for_device(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"a row to bench/harness/peaks.py (have "
                       f"{sorted(PEAKS)})") from None
