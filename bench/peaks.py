"""Published peaks of the devices the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, \"TPU v5e\"",
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
