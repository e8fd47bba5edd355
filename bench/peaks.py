"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A kind that is not here is an error, never a default: a
share of a peak is only meaningful against the chip that ran.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_s: float     # B/s
    hbm_bytes: int             # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16 * 2**30,
                        source='Google Cloud, "TPU v5e"'),
}


def lookup(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
