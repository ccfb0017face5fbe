"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak taken against the wrong chip would read as a gain or a loss.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises ``KeyError`` for a
    device the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
