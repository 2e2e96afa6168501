"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

An unknown kind is an error: a share of a peak is never taken against a
guessed peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e'",
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py "
                       f"with their source") from None
