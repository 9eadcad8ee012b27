"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` string that JAX reports.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

#: device_kind -> peaks of ONE chip
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,  # FLOP/s, dense bf16 on the MXU
        "int8_ops": 393e12,  # OP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, \"TPU v5e\" (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row to "
            f"bench/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
