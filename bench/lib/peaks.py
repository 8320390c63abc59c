"""Published peaks per chip, keyed by the `device_kind` JAX reports.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak would read as a real number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # dense bf16 MXU rate, FLOP/s
    int8_ops: float         # dense int8 MXU rate, OP/s
    hbm_bytes_per_s: float  # HBM bandwidth
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add the chip to "
            f"bench/lib/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None


def compute_peak(p: Peaks, operand_dtype: str) -> float:
    """The MXU rate an operand type is held against.  f32 has no MXU mode
    of its own: it runs as several bf16 passes, so it is held against the
    bf16 peak and tops out at a fraction of 100% set by those passes."""
    if operand_dtype in ("int8", "float8_e4m3fn", "float8_e5m2"):
        return p.int8_ops
    if operand_dtype in ("bfloat16", "float32"):
        return p.bf16_flops
    raise KeyError(f"no MXU peak for operand type {operand_dtype!r}")
