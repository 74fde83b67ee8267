"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip. A device that
is not listed is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s
    hbm_bytes: float        # bytes/s
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bytes=819e9,
             source='Google Cloud documentation, "TPU v5e"')

TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; add them to "
                            f"{__name__}.TABLE with their source") from None
