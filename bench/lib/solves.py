"""Driver of the batch traffic: whole all-pairs solves back to back.

Each solve runs `corr()` on an array the program has not seen, a fresh
on-device copy of the compendium made inside the window, so the transform
cache cannot skip the transform.  A solve ends in `block_until_ready` on
its result; a few seeded rows of every result are kept on the device (a
small gather, waited for) before the result and its input are dropped, and
compared with the float64 reference once the window has closed.

Traffic parameters (bench/traffic/<name>.json, "kind": "solves"):
    measure          the measure corr() computes
    rows_per_solve   result rows kept from each solve for the check
    limits           {number: limit} for the comparison
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import reference
from bench.lib.data import make_compendium
from bench.lib.record import Record
from bench.lib.spec import Cell
from bench.lib.trace import span
from bench.lib.traffic import rng_for
from bench.lib.work import allpairs_work


class SolvesDriver:
    def __init__(self, cell: Cell, seed: int, devices: List,
                 corr: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        if corr is None:
            from repro.core.api import corr
        self.cell, self.seed, self.devices = cell, seed, devices
        self.corr = corr
        cfg, tr = cell.config, cell.traffic
        self.n, self.l = int(cfg["n_genes"]), int(cfg["n_samples"])
        self.measure = tr["measure"]
        self.mesh = None
        sharding = None
        if int(cfg.get("mesh_chips", 1)) > 1:
            self.mesh = jax.make_mesh((len(devices),), ("d",),
                                      devices=devices)
            sharding = NamedSharding(self.mesh, PartitionSpec())
        self.x = make_compendium(seed, self.n, self.l,
                                 int(cfg["programs"]), sharding)
        self._copy = jax.jit(jnp.copy)
        self._take = jax.jit(lambda r, i: r[i])
        self._order = rng_for(seed, 3).permutation(self.n)
        self.kept: List = []
        self.solves = 0
        self.window_s = 0.0

    def _rows(self, k: int) -> np.ndarray:
        """The result rows kept from solve k: the next few of a seeded
        permutation of the genes."""
        per = int(self.cell.traffic["rows_per_solve"])
        return np.sort(np.resize(np.roll(self._order, -k * per), per))

    def _solve(self, k: int):
        import jax
        import jax.numpy as jnp
        with span("copy"):
            xc = self._copy(self.x)
        with span("solve"):
            r = jax.block_until_ready(
                self.corr(xc, measure=self.measure, mesh=self.mesh))
        with span("gather"):
            # kept rows ready before the result is dropped, so the next
            # solve starts with this one's result and input released
            rows = jax.block_until_ready(
                self._take(r, jnp.asarray(self._rows(k), jnp.int32)))
        del r, xc
        return rows

    def warm(self) -> None:
        """One solve through the window's own calls: every program the
        window runs is compiled or loaded here."""
        import jax
        jax.block_until_ready(self._solve(0))

    def window(self, seconds: float) -> None:
        """Solves back to back until `seconds` have passed; the window
        ends when the solve in flight then has finished."""
        import jax
        kept = []
        t0 = time.perf_counter()
        with span("window"):
            while True:
                kept.append(self._solve(len(kept)))
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(kept)
        self.window_s = time.perf_counter() - t0
        self.kept, self.solves = kept, len(kept)

    def end_to_end(self) -> Dict[str, float]:
        return {"solve_s": self.window_s / self.solves}

    def record(self, rec: Record) -> Record:
        rec.solves = self.solves
        rec.solve_work = allpairs_work(self.n, self.l)
        return rec

    def free(self) -> np.ndarray:
        """The kept rows on the host; every device array dropped."""
        got = np.concatenate([np.asarray(k) for k in self.kept])
        x = np.asarray(self.x)
        self.kept, self.x = [], None
        self._x_host = x
        return got

    def compared_rows(self) -> np.ndarray:
        return np.concatenate([self._rows(k) for k in range(self.solves)])

    def readings(self, got: np.ndarray) -> Dict[str, float]:
        """The kept rows against the float64 reference: the widest and
        the mean gap over all of them, and how many solves' rows broke a
        limit."""
        ref = reference.rows_of(self._zn(), self.compared_rows())
        return self._gaps(got, ref)

    def _zn(self) -> np.ndarray:
        if getattr(self, "_zn_host", None) is None:
            self._zn_host = reference.unit_rows(self._x_host, self.measure)
        return self._zn_host

    def _gaps(self, got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
        per = int(self.cell.traffic["rows_per_solve"])
        limits = self.cell.traffic["limits"]
        bad = sum(not reference.within(
            reference.row_gaps(got[i:i + per], ref[i:i + per]), limits)
            for i in range(0, len(got), per))
        return {**reference.row_gaps(got, ref), "bad_solves": bad}

    def control_readings(self) -> Dict[str, float]:
        """The control in the program's place, on the same rows."""
        import jax.numpy as jnp
        rows = self.compared_rows()
        u = reference.control_unit_rows(jnp.asarray(self._x_host),
                                        self.measure)
        got = reference.control_rows(u, rows)
        return self._gaps(got, reference.rows_of(self._zn(), rows))

    def counts(self, readings: Dict[str, float]) -> Dict[str, int]:
        return {"attempted": self.solves,
                "failed": int(readings["bad_solves"])}
