"""Operations and bytes of the problem, from its shape alone.

The counts never read the plan's tiles or padding: a later change to the
tile shape, the padding or the pass split cannot move them.  A roofline
share is the least time these counts allow on the chip's peaks, over the
time the kernel took.
"""

from __future__ import annotations

import dataclasses

from bench.lib.peaks import Peaks, compute_peak

FLOAT32_BYTES = 4
INDEX_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, p: Peaks, operand_dtype: str = "float32"
                      ) -> float:
        """max(operations / peak rate, bytes / HBM bandwidth)."""
        return max(self.flops / compute_peak(p, operand_dtype),
                   self.bytes / p.hbm_bytes_per_s)

    def bound(self, p: Peaks, operand_dtype: str = "float32") -> str:
        """Which of the two rooflines bounds the least time."""
        c = self.flops / compute_peak(p, operand_dtype)
        return "compute" if c >= self.bytes / p.hbm_bytes_per_s else "memory"


def allpairs_work(n: int, l: int, itemsize: int = FLOAT32_BYTES) -> Work:
    """Symmetric all-pairs over n variables of l samples: 2*l operations
    per unordered pair (diagonal included), n(n+1)/2 pairs; the operand is
    read once and each pair's result written once."""
    pairs = n * (n + 1) // 2
    return Work(flops=2.0 * l * pairs,
                bytes=float(n * l * itemsize + pairs * FLOAT32_BYTES))


def rect_topk_work(rows: int, n: int, l: int, k: int,
                   itemsize: int = FLOAT32_BYTES) -> Work:
    """`rows` real probe rows against an n-row corpus, keeping k partners
    per row: 2*l operations per (probe, corpus) pair; probes and corpus
    read once, k (value, index) pairs written per probe row."""
    return Work(flops=2.0 * l * n * rows,
                bytes=float((rows + n) * l * itemsize
                            + rows * k * (FLOAT32_BYTES + INDEX_BYTES)))
