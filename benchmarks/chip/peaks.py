"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`.  Copied from `src/repro/runtime/hlo_analysis.PEAKS` (as it
stands at this benchmark's first commit), with its source.  A kind that
is not listed raises: a roofline share is never taken against a guess."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # bytes/s
    hbm_bytes: float        # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect"),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
