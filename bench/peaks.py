"""Published peaks per chip, keyed by JAX's ``device_kind``.

A chip that is not in the table is an error: a share of a peak computed
against a guessed peak would mean nothing.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s
        "hbm_bytes_s": 819e9,       # B/s
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': per-chip peaks",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
