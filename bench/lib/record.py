"""What a run hands to the per-layer metric readers."""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from bench.lib.peaks import Peaks
from bench.lib.trace import TraceView
from bench.lib.work import Work


@dataclasses.dataclass
class Record:
    kind: str                         # "solves" or "search"
    view: Optional[TraceView]         # None in an untraced run
    peaks: Optional[Peaks]            # None off the chip
    operand_dtype: str
    solves: int = 0                   # solves completed in the window
    solve_work: Optional[Work] = None  # one solve's work
    launches: List[Tuple[Work, float]] = dataclasses.field(
        default_factory=list)         # (work, weight) per served launch
    occupancy: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)         # (occupancy, weight) per launch
    compiles: dict = dataclasses.field(default_factory=dict)
    lags_s: List[float] = dataclasses.field(default_factory=list)
