"""Published peaks of each accelerator the benchmark may run on.

One table keyed by ``jax.Device.device_kind``.  A device that is not in the
table is an error: a share of a peak read against a guessed peak means
nothing.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, dense bfloat16 matrix units
    int8_ops: float        # OP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes of device memory
    ici_bits_s: float      # bits/s of chip-to-chip interconnect per chip
    source: str


_V5E = Peaks(
    bf16_flops=197e12, int8_ops=394e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
    ici_bits_s=1600e9,
    source="Google Cloud documentation, 'TPU v5e' (system architecture)")

TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; ``KeyError`` if unknown."""
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
