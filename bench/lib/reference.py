"""The plain reference and the comparisons that decide `correct`.

The reference is float64 numpy/scipy on the host, in blocks of rows, and
imports nothing of the program.  The control is the same reference put in
the program's place one precision step down: the row transform in float32
and the products in three bf16 passes (`high`), the step from the `highest`
float32 the configuration states.  Each compared number has its limit in
the traffic file, set from readings of the program and of the control.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence

import numpy as np
import scipy.stats

WORKERS = 8


def _blocks(n: int, parts: int) -> List[slice]:
    edges = np.linspace(0, n, parts + 1).astype(int)
    return [slice(a, b) for a, b in zip(edges[:-1], edges[1:]) if b > a]


def ranks(x: np.ndarray) -> np.ndarray:
    """Average-tie ranks of each row (scipy.stats.rankdata), float64,
    computed in row blocks on a few threads."""
    out = np.empty(x.shape, np.float64)

    def one(s: slice) -> None:
        out[s] = scipy.stats.rankdata(x[s], axis=1)

    with ThreadPoolExecutor(WORKERS) as ex:
        list(ex.map(one, _blocks(x.shape[0], WORKERS * 4)))
    return out


def unit_rows(x: np.ndarray, measure: str) -> np.ndarray:
    """Rows centred and scaled to unit norm in float64, so that the
    measure of rows i and j is their dot product."""
    if measure == "spearman":
        z = ranks(x)
    elif measure == "pearson":
        z = np.asarray(x, np.float64)
    else:
        raise ValueError(f"no reference for measure {measure!r}")
    z = z - z.mean(axis=1, keepdims=True)
    norm = np.linalg.norm(z, axis=1, keepdims=True)
    return np.divide(z, norm, out=np.zeros_like(z), where=norm > 0)


def rows_of(zn: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """The measure of `rows` against every row, float64, clipped to its
    range as the measure is."""
    return np.clip(zn[rows] @ zn.T, -1.0, 1.0)


def row_gaps(got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Widest and mean |got - ref| over every entry of the compared rows."""
    d = np.abs(np.asarray(got, np.float64) - ref)
    return {"max_abs_dr": float(d.max()), "mean_abs_dr": float(d.mean())}


def topk_order(ref_rows: np.ndarray, k: int) -> np.ndarray:
    """Each row's k partners under the canonical order: |r| descending,
    then column ascending."""
    out = np.empty((ref_rows.shape[0], k), np.int64)
    cols = np.arange(ref_rows.shape[1])
    for i, row in enumerate(ref_rows):
        out[i] = np.lexsort((cols, -np.abs(row)))[:k]
    return out


def topk_gaps(idx: np.ndarray, vals: np.ndarray, ref_rows: np.ndarray,
              k: int) -> Dict[str, float]:
    """How far served top-k rows lie from the reference.

    value_gap: widest |served value - reference value| at the served
        columns;
    rank_gap: widest amount by which a served partner's reference |r|
        falls below the true k-th |r| of its row (0 when the served set is
        the true one, up to ties); a missing or repeated partner reads 1.
    """
    idx = np.asarray(idx)
    vals = np.asarray(vals, np.float64)
    value_gap, rank_gap = 0.0, 0.0
    for i in range(ref_rows.shape[0]):
        row, served = ref_rows[i], idx[i, :k]
        if (served < 0).any() or np.unique(served).size != k:
            rank_gap = max(rank_gap, 1.0)
            continue
        kth = np.sort(np.abs(row))[-k]
        value_gap = max(value_gap,
                        float(np.abs(vals[i, :k] - row[served]).max()))
        rank_gap = max(rank_gap, float(kth - np.abs(row[served]).min()))
    return {"value_gap": value_gap, "rank_gap": max(rank_gap, 0.0)}


def within(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(readings[k] <= limits[k] for k in limits)


def checks_line(readings: Dict[str, float], limits: Dict[str, float]
                ) -> Dict[str, dict]:
    """{name: {"value", "limit"}} for the result line, in limit order."""
    return {k: {"value": readings[k], "limit": limits[k]} for k in limits}


# -- the control: the reference one precision step down, on the device --


def control_unit_rows(x, measure: str):
    """float32 unit rows of the reference transform, on the device."""
    import jax.numpy as jnp
    if measure == "spearman":
        z = jnp.asarray(ranks(np.asarray(x)), jnp.float32)
    else:
        z = jnp.asarray(x, jnp.float32)
    z = z - jnp.mean(z, axis=1, keepdims=True)
    return z / jnp.linalg.norm(z, axis=1, keepdims=True)


def control_rows(u, rows: Sequence[int]) -> np.ndarray:
    """rows of u @ u.T in three bf16 passes (hi*hi + hi*lo + lo*hi), the
    `high` precision, written out so that it is the same on every
    backend."""
    import jax
    import jax.numpy as jnp
    hi = u.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (u - hi).astype(jnp.bfloat16).astype(jnp.float32)
    r = jnp.asarray(np.asarray(rows))
    dot = lambda a, b: jnp.dot(a, b.T,  # noqa: E731
                               precision=jax.lax.Precision.HIGHEST)
    out = dot(hi[r], hi) + dot(hi[r], lo) + dot(lo[r], hi)
    return np.asarray(jnp.clip(out, -1.0, 1.0))
