"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

#: device_kind -> (bf16 FLOP/s, HBM bytes/s, HBM bytes, source)
PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip)",
    ),
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
