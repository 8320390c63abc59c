"""The open-loop load generator: schedules drawn from a traffic file and
a seed.

The arrival times and set sizes come from the traffic file's own
`schedule_seed`, so every run offers the same load in the same order, and
the seed draws only which genes each query asks for.  At four fifths of
capacity the order of the gaps sets how the queue builds, so a seeded
order would move the tail from seed to seed.  Latency is taken from each
query's due time, so a stall that delays later sends counts against
them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named use of the seed.  Any non-negative whole
    number is a seed, also beyond 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, stream])


@dataclasses.dataclass(frozen=True)
class Query:
    due_s: float            # offset from the window's start
    genes: np.ndarray       # corpus rows asked for, sorted, unique


def _gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """n exponential gaps at `rate`: the n midpoint quantiles, permuted."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)


def _starts(gaps: np.ndarray) -> np.ndarray:
    """Due times from gaps, the first at 0.  The midpoint quantiles of n
    gaps at rate r sum to less than n / r, so every due time falls inside
    a window of n / r seconds."""
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def arrival_times(rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Poisson due times in [0, seconds) at rate `rate`."""
    return _starts(_gaps(max(1, int(round(rate * seconds))), rate, rng))


def zipf_sets(sizes: Sequence[int], n: int, s: float,
              rng: np.random.Generator) -> List[np.ndarray]:
    """Gene sets of the given sizes: genes drawn without replacement with
    probability proportional to rank**-s over a seeded permutation of the
    n genes (Gumbel top-k gives the same law as successive draws)."""
    perm = rng.permutation(n)
    logw = -s * np.log(np.arange(1, n + 1, dtype=np.float64))
    out = []
    for m in sizes:
        keys = logw + rng.gumbel(size=n)
        ranks = np.argpartition(-keys, m - 1)[:m]
        out.append(np.sort(perm[ranks]))
    return out


def set_sizes(lo: int, hi: int, count: int,
              rng: np.random.Generator) -> np.ndarray:
    """`count` sizes spread evenly over lo..hi, permuted."""
    span = np.arange(lo, hi + 1)
    return rng.permutation(np.resize(span, count))


def search_schedule(traffic: dict, n_genes: int, seed: int,
                    seconds: float) -> List[Query]:
    """The window's queries, in due order: due times and set sizes from
    the traffic's `schedule_seed`, the genes of each set from `seed`."""
    fixed = rng_for(int(traffic["schedule_seed"]), 1)
    due = arrival_times(float(traffic["rate_qps"]), seconds, fixed)
    lo, hi = traffic["set_size"]
    sizes = set_sizes(int(lo), int(hi), len(due), fixed)
    sets = zipf_sets(sizes, n_genes, float(traffic["zipf_s"]),
                     rng_for(seed, 1))
    return [Query(float(t), g) for t, g in zip(due, sets)]


def warm_sets(traffic: dict, n_genes: int, seed: int) -> List[np.ndarray]:
    """One gene set of every size the traffic can send, for set-up."""
    lo, hi = traffic["set_size"]
    return zipf_sets(list(range(int(lo), int(hi) + 1)), n_genes,
                     float(traffic["zipf_s"]), rng_for(seed, 2))


def nearest_rank(values: Sequence[float], pct: float) -> float:
    """The pct-th percentile by nearest rank over all values (inf counts
    as a value, so a missing answer lands in the tail)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no values")
    return float(v[max(0, math.ceil(pct / 100.0 * v.size) - 1)])
