"""Streaming tile sinks: pluggable output handling for the all-pairs engine.

The executor (core/allpairs.allpairs) produces finalised (t, t) similarity
tiles pass by pass; a ``TileSink`` decides what becomes of them.  This is
the piece that lets one engine serve workloads whose *outputs* differ as
much as their measures do (cf. CoMet, arXiv:1705.08213):

  DenseSink      scatter tiles into an (n, n) device matrix — the classic
                 drivers' behaviour, right when R fits accelerator memory.
  HostSink       assemble into a host array or np.memmap — out-of-core
                 n x n results; device memory stays bounded by one pass.
  ReductionSink  fold each pass through a user callback — O(state) memory,
                 for anything that never needs the full matrix.
  EdgeCountSink  built-in reduction for co-expression graphs: edge counts,
                 per-node degrees, and (given labels) intra/inter-module
                 tallies above a |similarity| threshold — O(n) state.

Contract: ``open(plan)`` is called once with the run's ExecutionPlan;
``consume(ids, tiles)`` receives each pass's *valid* tiles (unique global
tile ids, upper-triangle order within the pass) while the next pass is
already dispatched (double buffering — a sink that blocks on host transfer
overlaps the device's next pass for free); ``result()`` closes the run.
Tiles arrive with the measure's epilogue already applied (fused in-kernel
by default); bounded measures are clipped either in-kernel (fused) or by
the sink (clipping is idempotent, so both paths agree bit-for-bit).
"""

from __future__ import annotations

import abc
import copy
import json
import os
import zlib
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import mapping
from repro.core.plan import ExecutionPlan
from repro.runtime import faults
from repro.runtime.tracing import span

Array = jax.Array


class TileSink(abc.ABC):
    """Consumes the executor's per-pass tile stream."""

    plan: ExecutionPlan

    def open(self, plan: ExecutionPlan) -> None:
        """Called once before the first pass; allocate state here."""
        self.plan = plan

    def resume_pass(self) -> int:
        """First pass index the executor should run.  0 unless the sink
        recovered persisted progress in open() (HostSink checkpointing) —
        the executor never dispatches passes below this index."""
        return 0

    def skip_passes(self) -> set:
        """Pass indices >= resume_pass() the executor must NOT dispatch.

        Empty unless the sink recovered coverage that is not a pass-index
        prefix — which happens exactly when a run was elastically
        repartitioned (device loss) between checkpoints: the old partition's
        completed tiles land scattered across the new partition's passes.
        """
        return set()

    def covered(self) -> Optional[np.ndarray]:
        """Bool bitmap over global tile ids whose output this sink already
        holds durably, or None for sinks without recoverable coverage.
        The recovering executor seeds its own coverage from this and
        filters re-run passes down to the genuinely missing tiles."""
        return None

    def rebind(self, new_plan: ExecutionPlan) -> None:
        """Adopt an elastically repartitioned plan mid-run (same geometry,
        measure and workload — only the device partition changed).  Durable
        sinks re-commit their sidecar under the new spec immediately, so a
        crash after the shrink resumes against the plan that will actually
        be re-run."""
        self.plan = new_plan

    def pass_complete(self, k: int) -> None:
        """Pass k's tiles have been consumed; durable sinks commit here."""

    @abc.abstractmethod
    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        """One pass's valid tiles: ids (P,) unique global tile ids, tiles
        (P, t, t) device array (epilogue applied; clipped iff fused)."""

    def consume_clamped(self, padded_ids: np.ndarray, sel: np.ndarray,
                        ids: np.ndarray, tiles: Array) -> None:
        """A mesh pass whose raw (p * launch, t, t) buffer contains clamped
        tail-device slots (duplicates of tile total-1 etc.).  `sel` indexes
        the valid slots (whose ids are `ids`, in order); `padded_ids` gives
        every slot's clamped id, duplicates carrying identical content.

        The default transfers to host and filters there — never a device
        gather of the valid slots, so per-device memory stays bounded by the
        pass buffer the kernel already wrote.  DenseSink overrides this to
        scatter the raw buffer with the clamped ids instead (duplicates are
        idempotent).
        """
        del padded_ids
        self.consume(ids, np.asarray(tiles)[sel])

    @abc.abstractmethod
    def result(self):
        """Finalise and return the run's output."""


def _scatter_tiles_device(r_pad: Array, tiles: Array, coords: Array,
                          placement: Optional[NamedSharding] = None) -> Array:
    """One batched scatter of (P, t, t) tiles into (n_pad, n_pad) at the
    (row, col) starts in coords (P, 2) — replaces the serial scan of
    dynamic_update_slice (P sequential HLO ops) with a single scatter.

    With a mesh `placement` the mesh-sharded tiles are first resharded to
    it inside this program: an XLA all-gather between the chips, never a
    copy through the host."""
    if placement is not None:
        tiles = jax.sharding.reshard(tiles, placement)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2),
        inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1),
    )
    return jax.lax.scatter(r_pad, coords, tiles, dnums,
                           indices_are_sorted=False, unique_indices=False)


_scatter_tiles_device = jax.jit(_scatter_tiles_device,
                                static_argnames=("placement",))


def output_sharding(tiles) -> Optional[NamedSharding]:
    """Where a dense output assembled from `tiles` lives: replicated over
    the mesh of mesh-sharded tiles, else None (the default device).

    Every device of the mesh then holds the whole padded matrix, and each
    pass's (p * launch, t, t) buffer is all-gathered over the chips' links
    inside the scatter program (scatter_tiles_at) — the placement XLA
    picks for a scatter of batch-sharded updates anyway (a row-sharded
    output would all-gather the matrix itself), now stated so that meshes
    with Explicit axes (``jax.make_mesh``'s default) accept it and no run
    funnels every shard onto one device."""
    sharding = getattr(tiles, "sharding", None)
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, PartitionSpec())
    return None


def scatter_tiles_at(r_pad: Array, tiles: Array, ys: np.ndarray,
                     xs: np.ndarray, t: int) -> Array:
    """Scatter (t, t) tiles into r_pad at tile coordinates (ys, xs) via one
    batched device scatter.  Workload-agnostic: callers invert ids with
    whichever bijection numbers their jobs.  Mesh-sharded tiles land in a
    mesh-replicated r_pad (see output_sharding): the compiled scatter
    all-gathers them first, one program per sharding and shape."""
    coords = jnp.stack([jnp.asarray(ys * t, jnp.int32),
                        jnp.asarray(xs * t, jnp.int32)], axis=1)
    return _scatter_tiles_device(r_pad, tiles.astype(r_pad.dtype), coords,
                                 placement=output_sharding(tiles))


def scatter_tiles(r_pad: Array, tiles: Array, ids: np.ndarray, t: int,
                  m: int) -> Array:
    """Scatter (t, t) tiles into the padded upper-triangle of R.

    The id -> (y, x) bijection is inverted for the whole batch at once
    (mapping.job_coord_batch, vectorised numpy) and the tiles land via a
    single batched device scatter.  Duplicate ids (a clamped short pass)
    carry identical tile contents, so write order does not matter.
    (Triangular spelling, kept for the legacy drivers; the sinks route
    through the plan's workload + scatter_tiles_at.)
    """
    ys, xs = mapping.job_coord_batch(m, np.asarray(ids))
    return scatter_tiles_at(r_pad, tiles, ys, xs, t)


def place_tiles_host(r: np.ndarray, tiles: np.ndarray, ys: np.ndarray,
                     xs: np.ndarray, t: int, mirror: bool = True) -> None:
    """Write a batch of (t, t) tiles (and, for symmetric workloads, their
    lower-triangle mirrors) into the host matrix r in-place — vectorised
    fancy-index scatter, no per-tile Python loop.  Works on plain arrays
    and np.memmap alike.  mirror=False for rectangular workloads, whose
    grid has no transpose twin."""
    span = np.arange(t)
    rows = (ys[:, None] * t + span)[:, :, None]  # (P, t, 1)
    cols = (xs[:, None] * t + span)[:, None, :]  # (P, 1, t)
    r[rows, cols] = tiles
    if not mirror:
        return
    off = ys != xs
    if np.any(off):
        r[cols[off].transpose(0, 2, 1), rows[off].transpose(0, 2, 1)] = \
            tiles[off].transpose(0, 2, 1)


def symmetrize(r_pad: Array, n: int) -> Array:
    """Mirror the scattered upper blocks into the lower triangle and crop."""
    idx = jnp.arange(r_pad.shape[0])
    upper = idx[:, None] <= idx[None, :]
    r_full = jnp.where(upper, r_pad, r_pad.T)
    return r_full[:n, :n]


class DenseSink(TileSink):
    """Accumulate tiles into a padded device matrix; result() is the
    symmetrised (n, n) similarity for triangular workloads — the four
    classic drivers' output, bit-identical to the pre-refactor assembly —
    or the cropped (n_rows, n_cols) cross-similarity for rectangular
    workloads (nothing to mirror)."""

    def open(self, plan: ExecutionPlan) -> None:
        from repro.core.allpairs import current_call  # lazy: it imports us
        super().open(plan)
        self.r_pad = None  # allocated where the first pass's tiles live
        self._call = current_call()  # trace span ids: the call, ...
        self._passes = 0             # ... and the pass, in arrival order

    def _scatter(self, ids: np.ndarray, tiles: Array) -> None:
        with span("sink.scatter", call=self._call,
                  **{"pass": self._passes}):
            placement = output_sharding(tiles)
            if self.r_pad is None:
                self.r_pad = jnp.zeros((self.plan.n_pad, self.plan.col_pad),
                                       jnp.float32, device=placement)
            elif placement is not None and getattr(
                    self.r_pad.sharding, "mesh", None) != placement.mesh:
                # The mesh shrank under recovery (rebind): copy the
                # replicated matrix onto the survivors, chip to chip.
                self.r_pad = jax.device_put(self.r_pad, placement)
            ys, xs = self.plan.workload.job_coord_batch(np.asarray(ids))
            self.r_pad = scatter_tiles_at(self.r_pad, tiles, ys, xs,
                                          self.plan.t)
        self._passes += 1

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        self._scatter(ids, tiles)

    def consume_clamped(self, padded_ids: np.ndarray, sel: np.ndarray,
                        ids: np.ndarray, tiles: Array) -> None:
        # Scatter the raw sharded buffer with the clamped ids: duplicate
        # slots hold identical tiles (the kernel clamps the same way), so
        # the write set equals the valid set — no selection of valid slots
        # on device, and bit-identical to the historical clamped-id
        # assembly.
        del sel, ids
        self._scatter(padded_ids, tiles)

    def result(self) -> Array:
        with span("sink.symmetrize", call=self._call):
            if self.plan.workload.needs_symmetrize:
                r = symmetrize(self.r_pad, self.plan.n)
            else:
                r = self.r_pad[: self.plan.n_rows, : self.plan.n_cols]
            # Fused runs leave the kernel fully finalised (epilogue +
            # clip).  Unfused runs had the epilogue applied on the pass
            # stream; only the bounded-measure clip remains — elementwise,
            # so applying it after symmetrise is bit-identical to the
            # historical order.
            meas = self.plan.measure
            if (not self.plan.fused and self.plan.clip
                    and meas.clip is not None):
                r = jnp.clip(r, *meas.clip)
            return r


def _id_intervals(ids: np.ndarray) -> List[List[int]]:
    """Compress a sorted unique id array into half-open ``[lo, hi)`` runs —
    the sidecar's tile-region encoding (plan-independent: global ids
    survive elastic repartitioning, pass indices do not)."""
    if ids.size == 0:
        return []
    breaks = np.nonzero(np.diff(ids) != 1)[0]
    starts = np.concatenate([[0], breaks + 1])
    ends = np.concatenate([breaks, [ids.size - 1]])
    return [[int(ids[s]), int(ids[e]) + 1] for s, e in zip(starts, ends)]


def _ids_from_intervals(ivs) -> np.ndarray:
    parts = [np.arange(int(lo), int(hi), dtype=np.int64) for lo, hi in ivs]
    return np.concatenate(parts) if parts else np.empty(0, np.int64)


class HostSink(TileSink):
    """Assemble tiles (and, for symmetric workloads, their mirrors) into a
    host matrix — a caller array, an np.memmap at `path`, or a freshly
    allocated ndarray.  Device memory stays bounded by one pass; the full
    result lives on host/disk.

    The host transfer in consume() blocks on the *previous* pass only (the
    executor has already dispatched the next), preserving Alg. 2's
    compute/offload overlap.

    Checkpoint/resume: with a memmap `path`, every completed pass is
    committed durably and *crash-atomically* — the memmap is flushed, then
    a sidecar ``<path>.progress.json`` is written to a temp file, fsynced,
    and renamed into place (a crash at any instant leaves either the old
    or the new sidecar, never a truncated one).  The sidecar (version 2)
    records the plan spec, the last completed pass index, and per-commit
    coverage entries: the committed tile-id intervals plus a CRC32 of the
    written tile regions.  ``HostSink(path=..., resume=True)`` (or
    ``corr(..., resume_from=path)``) validates the persisted spec against
    the current plan, re-verifies every entry's CRC against the memmap —
    corrupt regions are dropped and recomputed, never trusted — and
    reports the resume schedule to the executor: completed passes are
    never recomputed, and a run killed mid-pass re-runs only that pass.
    Entries are keyed by global tile ids, not pass indices, so a
    checkpoint taken before an elastic shrink (``rebind``) resumes
    correctly under the repartitioned plan.

    Fault-injection sites (runtime/faults.py): ``sink_write`` (tile
    placement; honours partial writes), ``sink_flush`` (durable flush),
    ``sink_commit`` (crash before the atomic rename).
    """

    SIDECAR_VERSION = 2

    def __init__(self, out: Optional[np.ndarray] = None,
                 path: Optional[str] = None, resume: bool = False):
        if out is not None and path is not None:
            raise ValueError("pass either a preallocated `out` or a memmap "
                             "`path`, not both")
        if resume and path is None:
            raise ValueError("resume=True requires a memmap `path` (the "
                             "progress sidecar lives next to it)")
        self._out = out
        self._path = path
        self._resume = resume

    @property
    def progress_path(self) -> Optional[str]:
        return None if self._path is None else self._path + ".progress.json"

    # -- sidecar integrity ---------------------------------------------------

    def _crc_of_ids(self, ids: np.ndarray) -> int:
        """CRC32 over the tile regions of `ids` in canonical (ascending id)
        order — the same fancy-index gather shape place_tiles_host writes,
        so the checksum covers exactly the committed bytes.  Mirrors are
        derived writes and deliberately excluded: recomputing a dropped
        region rewrites both halves."""
        ids = np.asarray(ids, np.int64)
        if ids.size == 0:
            return 0
        ys, xs = self.plan.workload.job_coord_batch(ids)
        t = self.plan.t
        span = np.arange(t)
        rows = (ys[:, None] * t + span)[:, :, None]
        cols = (xs[:, None] * t + span)[:, None, :]
        block = np.ascontiguousarray(np.asarray(self.r[rows, cols],
                                                dtype=np.float32))
        return zlib.crc32(block.tobytes()) & 0xFFFFFFFF

    def _write_progress(self, completed: int) -> None:
        # flush data before advancing the watermark: a crash between the
        # two leaves a pass marked incomplete (re-run), never a pass marked
        # complete with unflushed tiles (silent corruption)
        faults.check("sink_flush")
        if hasattr(self.r, "flush"):
            self.r.flush()
        tmp = self.progress_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": self.SIDECAR_VERSION,
                       "spec": self.plan.spec_dict(),
                       "completed": completed,
                       "entries": self._entries}, f)
            f.flush()
            os.fsync(f.fileno())
        # an injected fault here is a crash after the temp write but
        # *before* commit: the previous sidecar must stay intact/resumable
        faults.check("sink_commit")
        os.replace(tmp, self.progress_path)
        self._fsync_dir()

    def _fsync_dir(self) -> None:
        # persist the rename itself (directory entry), best-effort on
        # filesystems that refuse O_RDONLY directory fsync
        d = os.path.dirname(os.path.abspath(self.progress_path))
        try:
            fd = os.open(d, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)

    def _load_sidecar(self) -> dict:
        try:
            with open(self.progress_path) as f:
                state = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise ValueError(
                f"cannot resume from {self._path!r}: progress sidecar "
                f"unreadable ({e}).  The sidecar commit is atomic "
                f"(temp file + fsync + rename), so a crash cannot truncate "
                f"it — it is missing or was modified outside the engine.  "
                f"Delete {self.progress_path!r} and the memmap to restart "
                f"from scratch.") from None
        bad = None
        if not isinstance(state, dict):
            bad = f"expected a JSON object, got {type(state).__name__}"
        elif not isinstance(state.get("spec"), dict):
            bad = "missing plan spec"
        elif not isinstance(state.get("completed"), int):
            bad = "missing completed-pass watermark"
        elif not isinstance(state.get("entries", []), list) or any(
                not isinstance(e, dict) for e in state.get("entries", [])):
            bad = "malformed coverage entries"
        if bad is not None:
            raise ValueError(
                f"cannot resume from {self._path!r}: progress sidecar "
                f"garbled ({bad}).  Delete {self.progress_path!r} and the "
                f"memmap to restart from scratch.")
        return state

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        shape = (plan.n_pad, plan.col_pad)
        self._completed = -1
        self._skip: set = set()
        self._entries: List[dict] = []
        self._pending: List[np.ndarray] = []
        self._covered = np.zeros(plan.total_tiles, bool)
        if self._out is not None:
            if self._out.shape != shape:
                raise ValueError(
                    f"out shape {self._out.shape} != padded {shape}")
            self.r = self._out
        elif self._path is not None:
            if self._resume:
                self._open_resume(shape)
            else:
                self.r = np.memmap(self._path, dtype=np.float32, mode="w+",
                                   shape=shape)
                self.r[:] = 0.0
                self._write_progress(-1)
        else:
            self.r = np.zeros(shape, np.float32)

    def _open_resume(self, shape) -> None:
        state = self._load_sidecar()
        spec = self.plan.spec_dict()
        if state["spec"] != spec:
            raise ValueError(
                f"cannot resume from {self._path!r}: persisted plan "
                f"spec {state['spec']} does not match the requested run "
                f"{spec}")
        self.r = np.memmap(self._path, dtype=np.float32, mode="r+",
                           shape=shape)
        completed = int(state["completed"])
        if state.get("version", 1) >= 2:
            entries = state.get("entries", [])
        else:
            # v1 sidecar (pre-CRC format): trust its completed-pass prefix
            # — exactly its own semantics — and synthesise one verified
            # entry so every commit from here on is self-checking
            parts = [self.plan.pass_selection(k)[0]
                     for k in range(completed + 1)]
            ids = (np.unique(np.concatenate(parts)) if parts
                   else np.empty(0, np.int64))
            entries = [{"iv": _id_intervals(ids),
                        "crc": self._crc_of_ids(ids)}]
        dropped = 0
        for e in entries:
            ids = _ids_from_intervals(e.get("iv", []))
            if ids.size and (ids[0] < 0
                             or ids[-1] >= self.plan.total_tiles):
                dropped += 1
                continue
            if int(e.get("crc", -1)) != self._crc_of_ids(ids):
                dropped += 1  # corrupt region: recompute it, never trust it
                continue
            self._covered[ids] = True
            self._entries.append(e)
        k0, self._skip = self.plan.coverage_schedule(self._covered)
        self._completed = k0 - 1
        if dropped or state.get("version", 1) < 2:
            # durably prune corrupt entries (and upgrade v1) so a crash
            # right now never re-trusts a known-bad region
            self._write_progress(self._completed)

    # -- executor contract ---------------------------------------------------

    def resume_pass(self) -> int:
        return self._completed + 1

    def skip_passes(self) -> set:
        return set(self._skip)

    def covered(self) -> np.ndarray:
        return self._covered.copy()

    def rebind(self, new_plan: ExecutionPlan) -> None:
        """Adopt an elastically repartitioned plan mid-run.  Consumed-but-
        uncommitted tiles are committed first (their bytes are in self.r;
        the flush in _write_progress makes them durable before the sidecar
        advances), then the sidecar is rewritten under the new spec — a
        crash after the shrink resumes against the plan that will re-run.
        """
        self.plan = new_plan
        self._commit_pending()
        k0, self._skip = new_plan.coverage_schedule(self._covered)
        self._completed = k0 - 1
        if self._path is not None:
            self._write_progress(self._completed)

    def _commit_pending(self) -> None:
        if not self._pending:
            return
        ids = np.unique(np.concatenate(self._pending))
        self._pending = []
        self._covered[ids] = True
        if self._path is not None:
            self._entries.append({"iv": _id_intervals(ids),
                                  "crc": self._crc_of_ids(ids)})

    def pass_complete(self, k: int) -> None:
        self._completed = k
        self._commit_pending()
        if self._path is not None:
            self._write_progress(k)

    def _place(self, ids: np.ndarray, vals: np.ndarray) -> None:
        if ids.size == 0:
            return
        ys, xs = self.plan.workload.job_coord_batch(ids)
        place_tiles_host(self.r, vals, ys, xs, self.plan.t,
                         mirror=self.plan.workload.needs_symmetrize)

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        ids = np.asarray(ids, dtype=np.int64)
        vals = np.asarray(tiles)
        fault = faults.poll("sink_write")
        if isinstance(fault, faults.PartialWriteFault):
            # land a prefix of the batch, then fail — the pass never
            # completes, so the partial region stays uncovered (recomputed)
            self._place(ids[: int(len(ids) * fault.fraction)],
                        vals[: int(len(ids) * fault.fraction)])
            raise fault
        if fault is not None:
            raise fault
        self._place(ids, vals)
        self._pending.append(ids)

    def result(self) -> np.ndarray:
        r = self.r[: self.plan.n_rows, : self.plan.n_cols]
        meas = self.plan.measure
        if self.plan.clip and meas.clip is not None:
            np.clip(r, meas.clip[0], meas.clip[1], out=r)
        return r


class ShardedHostSink(TileSink):
    """Multi-host output sharding: each host persists only its disjoint
    global-tile-id range as chunked ``.npy`` files plus a JSON manifest —
    no host ever holds (or writes) more than its 1/n_hosts slice of the
    n x n result, which is what made CoMet's exascale all-pairs runs
    possible (arXiv:1705.08213: device-side reductions, disjoint per-node
    output shards).

    Ownership is ``plan.host_tile_range(host, n_hosts)`` — the union of the
    host's local devices' tile ranges, i.e. exactly the tiles whose pass
    outputs are addressable on this host under shard_map — and is *frozen*
    at open(): an elastic repartition mid-run (``rebind``) must not
    re-derive ownership, or two hosts could claim one tile's output.

    Durability extends the HostSink v2 sidecar scheme: every completed pass
    commits one chunk file (tiles in ascending-id order, written to a temp
    name, fsynced, renamed) and atomically rewrites the per-host manifest
    ``manifest.h<host>.json`` recording the plan spec, the frozen range,
    and per-chunk ``{file, iv, crc}`` entries (CRC32 over the chunk bytes).
    ``resume=True`` validates the spec, re-verifies every chunk's CRC —
    corrupt chunks are dropped and recomputed, never trusted — and reports
    the resume schedule through the standard coverage-bitmap contract, so
    ``recovery=RetryPolicy()`` and kill-and-resume compose exactly as for
    HostSink.  Tiles outside the host's range report as covered, so each
    host runs only its own pass range (passes with no owned tiles are
    skipped outright).

    ``open_manifest(dir)`` / ``assemble(dir)`` read the shards back —
    lazily (row ranges) or fully — without requiring this sink.

    Fault-injection sites: ``sink_write`` (tile staging; honours partial
    writes), ``sink_flush`` (chunk write), ``sink_commit`` (crash before
    the manifest rename).
    """

    MANIFEST_VERSION = 1

    # Distribution-only spec fields: elastic re-meshing (device loss ->
    # plan.repartition) changes p and the pass split WITHOUT changing a
    # bit of the output, so shard identity — resume validation and
    # cross-manifest agreement — must ignore them.
    _DISTRIBUTION_KEYS = frozenset({"p", "max_tiles_per_pass", "n_pass"})

    @classmethod
    def content_spec(cls, spec: dict) -> dict:
        """The output-identity part of a plan spec_dict."""
        return {k: v for k, v in spec.items()
                if k not in cls._DISTRIBUTION_KEYS}

    def __init__(self, dir: str, host: int = 0, n_hosts: int = 1,
                 resume: bool = False):
        if n_hosts <= 0:
            raise ValueError(f"n_hosts must be positive, got {n_hosts}")
        if not 0 <= host < n_hosts:
            raise ValueError(f"host {host} out of range for {n_hosts} hosts")
        self._dir = dir
        self._host = int(host)
        self._n_hosts = int(n_hosts)
        self._resume = resume

    @property
    def manifest_path(self) -> str:
        return os.path.join(self._dir, f"manifest.h{self._host}.json")

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        os.makedirs(self._dir, exist_ok=True)
        self._chunks: List[dict] = []
        self._pending: List[tuple] = []
        self._covered = np.zeros(plan.total_tiles, bool)
        if self._resume:
            self._open_resume()
        else:
            self._lo, self._hi = plan.host_tile_range(self._host,
                                                      self._n_hosts)
            self._mark_foreign()
            self._write_manifest()
        k0, self._skip = plan.coverage_schedule(self._covered)
        self._completed = k0 - 1

    def _mark_foreign(self) -> None:
        # other hosts' tiles are their problem: reporting them covered makes
        # this host's executor run exactly its own pass range
        self._covered[: self._lo] = True
        self._covered[self._hi:] = True

    def _chunk_crc(self, tiles: np.ndarray) -> int:
        return zlib.crc32(np.ascontiguousarray(
            tiles, dtype=np.float32).tobytes()) & 0xFFFFFFFF

    def _write_manifest(self) -> None:
        meas = self.plan.measure
        clip = (list(meas.clip)
                if self.plan.clip and meas.clip is not None else None)
        doc = {"version": self.MANIFEST_VERSION,
               "spec": self.plan.spec_dict(),
               "host": self._host, "n_hosts": self._n_hosts,
               "range": [int(self._lo), int(self._hi)],
               "clip_range": clip,
               "chunks": self._chunks}
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        faults.check("sink_commit")
        os.replace(tmp, self.manifest_path)
        self._fsync_dir()

    def _fsync_dir(self) -> None:
        try:
            fd = os.open(self._dir, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)

    def _open_resume(self) -> None:
        try:
            with open(self.manifest_path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise ValueError(
                f"cannot resume shard: manifest {self.manifest_path!r} "
                f"unreadable ({e}).  The manifest commit is atomic; delete "
                f"the shard directory to restart this host from scratch."
            ) from None
        spec = self.plan.spec_dict()
        if self.content_spec(doc.get("spec") or {}) != self.content_spec(spec):
            raise ValueError(
                f"cannot resume shard {self.manifest_path!r}: persisted "
                f"plan spec {doc.get('spec')} does not match the requested "
                f"run {spec}")
        if (doc.get("host"), doc.get("n_hosts")) != (self._host,
                                                     self._n_hosts):
            raise ValueError(
                f"cannot resume shard {self.manifest_path!r}: it belongs "
                f"to host {doc.get('host')}/{doc.get('n_hosts')}, not "
                f"{self._host}/{self._n_hosts}")
        self._lo, self._hi = (int(v) for v in doc["range"])
        self._mark_foreign()
        dropped = 0
        for e in doc.get("chunks", []):
            ids = _ids_from_intervals(e.get("iv", []))
            path = os.path.join(self._dir, e.get("file", ""))
            try:
                tiles = np.load(path)
            except (OSError, ValueError):
                dropped += 1
                continue
            if (tiles.shape != (ids.size, self.plan.t, self.plan.t)
                    or int(e.get("crc", -1)) != self._chunk_crc(tiles)):
                dropped += 1  # corrupt chunk: recompute it, never trust it
                try:
                    os.remove(path)
                except OSError:
                    pass
                continue
            self._covered[ids] = True
            self._chunks.append(e)
        if dropped:
            # durably prune so a crash right now never re-trusts a
            # known-bad chunk
            self._write_manifest()

    # -- executor contract ---------------------------------------------------

    def resume_pass(self) -> int:
        return self._completed + 1

    def skip_passes(self) -> set:
        return set(self._skip)

    def covered(self) -> np.ndarray:
        return self._covered.copy()

    def rebind(self, new_plan: ExecutionPlan) -> None:
        # ownership stays frozen across the repartition; only the pass
        # schedule is re-derived, and the manifest re-commits under the new
        # spec so a crash after the shrink resumes against the right plan
        self.plan = new_plan
        self._commit_pending()
        k0, self._skip = new_plan.coverage_schedule(self._covered)
        self._completed = k0 - 1
        self._write_manifest()

    def _commit_pending(self) -> None:
        if not self._pending:
            return
        ids = np.concatenate([p[0] for p in self._pending])
        tiles = np.concatenate([p[1] for p in self._pending])
        self._pending = []
        order = np.argsort(ids)
        ids, tiles = ids[order], np.ascontiguousarray(tiles[order],
                                                      dtype=np.float32)
        fresh = ~self._covered[ids]
        if not fresh.all():
            ids, tiles = ids[fresh], tiles[fresh]
        if ids.size == 0:
            return
        name = f"chunk-{int(ids[0]):010d}-{int(ids[-1]):010d}.npy"
        faults.check("sink_flush")
        tmp = os.path.join(self._dir, name + ".tmp")
        with open(tmp, "wb") as f:
            np.save(f, tiles)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self._dir, name))
        self._covered[ids] = True
        self._chunks.append({"file": name, "iv": _id_intervals(ids),
                             "crc": self._chunk_crc(tiles)})

    def pass_complete(self, k: int) -> None:
        self._completed = k
        self._commit_pending()
        self._write_manifest()

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        ids = np.asarray(ids, dtype=np.int64)
        own = (ids >= self._lo) & (ids < self._hi)
        if not own.any():
            return
        fault = faults.poll("sink_write")
        if isinstance(fault, faults.PartialWriteFault):
            cut = int(own.sum() * fault.fraction)
            self._pending.append((ids[own][:cut],
                                  np.asarray(tiles)[own][:cut]))
            raise fault
        if fault is not None:
            raise fault
        self._pending.append((ids[own], np.asarray(tiles)[own]))

    def result(self) -> dict:
        own = int(self._covered[self._lo: self._hi].sum())
        return {"dir": self._dir, "manifest": self.manifest_path,
                "host": self._host, "n_hosts": self._n_hosts,
                "range": (self._lo, self._hi), "tiles": own,
                "complete": own == self._hi - self._lo}


class ShardedMatrix:
    """Lazy reader over a ShardedHostSink output directory.

    Validates that every per-host manifest describes the same run (same
    plan spec), verifies chunk CRCs *as chunks are read* — a corrupt chunk
    is refused with an error naming the file, never silently zero-filled —
    and assembles either the full (n_rows, n_cols) matrix or any row range
    without materialising more than the requested rows plus one chunk.
    """

    def __init__(self, manifests: List[dict], dir: str):
        if not manifests:
            raise ValueError(f"no manifest.h*.json found in {dir!r}")
        self._dir = dir
        spec0 = manifests[0]["spec"]
        for d in manifests[1:]:
            if (ShardedHostSink.content_spec(d["spec"])
                    != ShardedHostSink.content_spec(spec0)):
                raise ValueError(
                    f"shard manifests disagree on the plan spec "
                    f"({dir!r}): {spec0} vs {d['spec']} — these shards "
                    f"come from different runs")
        self.spec = spec0
        self.n_rows = int(spec0["n_rows"])
        self.n_cols = int(spec0["n_cols"])
        self.t = int(spec0["t"])
        self.total_tiles = int(spec0["total_tiles"])
        self.symmetric = spec0["workload"] == "TriangularWorkload"
        self.clip_range = manifests[0].get("clip_range")
        self.hosts = sorted(int(d["host"]) for d in manifests)
        self.ranges = {int(d["host"]): tuple(int(v) for v in d["range"])
                       for d in manifests}
        t = self.t
        self._m = -(-self.n_rows // t)
        self._mc = -(-self.n_cols // t)
        self._chunks = []
        for d in manifests:
            for e in d.get("chunks", []):
                ids = _ids_from_intervals(e.get("iv", []))
                self._chunks.append(
                    (os.path.join(dir, e["file"]), ids, int(e["crc"])))

    def _coords(self, ids: np.ndarray):
        if self.symmetric:
            return mapping.job_coord_batch(self._m, ids)
        return ids // self._mc, ids % self._mc

    def _load(self, path: str, ids: np.ndarray, crc: int) -> np.ndarray:
        try:
            tiles = np.load(path)
        except (OSError, ValueError) as e:
            raise ValueError(
                f"shard chunk {path!r} unreadable ({e}) — re-run the "
                f"owning host with resume=True to recompute it") from None
        data = np.ascontiguousarray(tiles, dtype=np.float32)
        if (tiles.shape != (ids.size, self.t, self.t)
                or (zlib.crc32(data.tobytes()) & 0xFFFFFFFF) != crc):
            raise ValueError(
                f"shard chunk {path!r} fails its manifest CRC — refusing "
                f"corrupt data; re-run the owning host with resume=True to "
                f"recompute exactly this chunk")
        return data

    def _check_complete(self, need: np.ndarray) -> None:
        have = np.zeros(self.total_tiles, bool)
        for _, ids, _ in self._chunks:
            have[ids] = True
        missing = need & ~have
        if missing.any():
            ivs = _id_intervals(np.nonzero(missing)[0].astype(np.int64))
            raise ValueError(
                f"shards in {self._dir!r} are incomplete for the requested "
                f"rows: missing tile ids {ivs[:5]}{'...' if len(ivs) > 5 else ''}")

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """Assemble rows [lo, hi) of the result — the only materialised
        state is the (hi - lo, n_cols) output plus one chunk at a time."""
        if not 0 <= lo <= hi <= self.n_rows:
            raise ValueError(f"row range [{lo}, {hi}) outside "
                             f"[0, {self.n_rows})")
        t = self.t
        all_ids = np.arange(self.total_tiles, dtype=np.int64)
        ys_all, xs_all = self._coords(all_ids)
        hit = (ys_all * t < hi) & (ys_all * t + t > lo)
        if self.symmetric:
            hit |= (xs_all * t < hi) & (xs_all * t + t > lo)
        self._check_complete(hit)
        out = np.zeros((hi - lo, self.n_cols), np.float32)
        span = np.arange(t)
        for path, ids, crc in self._chunks:
            ys, xs = self._coords(ids)
            rel_y = (ys * t < hi) & (ys * t + t > lo)
            rel_x = (self.symmetric & (xs * t < hi) & (xs * t + t > lo)
                     & (ys != xs))
            if not (rel_y.any() or rel_x.any()):
                continue
            tiles = self._load(path, ids, crc)
            for pick, tv, rb, cb in (
                    (rel_y, tiles, ys, xs),
                    (rel_x, tiles.transpose(0, 2, 1), xs, ys)):
                if not pick.any():
                    continue
                sub = tv[pick]
                rows = (rb[pick, None] * t + span)[:, :, None]
                cols = (cb[pick, None] * t + span)[:, None, :]
                ok = (rows >= lo) & (rows < hi) & (cols < self.n_cols)
                okb = np.broadcast_to(ok, sub.shape)
                out[np.broadcast_to(rows - lo, sub.shape)[okb],
                    np.broadcast_to(cols, sub.shape)[okb]] = sub[okb]
        if self.clip_range is not None:
            np.clip(out, self.clip_range[0], self.clip_range[1], out=out)
        return out

    def full(self) -> np.ndarray:
        """The complete (n_rows, n_cols) matrix — bit-identical to a
        single-host DenseSink/HostSink run of the same plan."""
        return self.rows(0, self.n_rows)


def open_manifest(dir: str) -> ShardedMatrix:
    """Open a ShardedHostSink output directory for (lazy) reading."""
    manifests = []
    try:
        names = sorted(os.listdir(dir))
    except OSError as e:
        raise ValueError(f"cannot open shard directory {dir!r}: {e}") \
            from None
    for name in names:
        if name.startswith("manifest.h") and name.endswith(".json"):
            with open(os.path.join(dir, name)) as f:
                manifests.append(json.load(f))
    return ShardedMatrix(manifests, dir)


def assemble(dir: str) -> np.ndarray:
    """Assemble the full matrix from a (complete) set of host shards."""
    return open_manifest(dir).full()


class ReductionSink(TileSink):
    """Fold the tile stream through `fn(state, ids, tiles, ys, xs, plan)`.

    `tiles` is handed to the callback as host numpy (the transfer overlaps
    the next pass's device compute); (ys, xs) are the tile coordinates from
    the batched bijection.  State is whatever the callback returns —
    typically O(n) or O(1), which is the whole point.

    `init` may be the initial state value — deep-copied at open(), so a
    fold that mutates state in place cannot leak accumulation across runs
    of a reused sink — or a zero-argument factory called per open().
    """

    def __init__(self, fn: Callable, init):
        self._fn = fn
        self._init = init

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        self.state = (self._init() if callable(self._init)
                      else copy.deepcopy(self._init))

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        ys, xs = self.plan.workload.job_coord_batch(np.asarray(ids))
        self.state = self._fn(self.state, ids, np.asarray(tiles), ys, xs,
                              self.plan)

    def result(self):
        return self.state


class EdgeCountSink(TileSink):
    """Streaming thresholded-graph reduction: count edges with
    |similarity| >= threshold without ever materialising the matrix.

    State is O(n): total unordered edge count, per-node degrees, and — when
    per-node integer `labels` are given — intra- vs inter-label edge
    tallies (precision of planted-module recovery is intra/(intra+inter)).
    Each unordered pair is counted exactly once via the global strict-upper
    predicate row < col, which holds for every entry of an off-diagonal
    upper-triangle tile and selects the strict upper half of diagonal
    tiles; padding rows/cols (>= n) are masked out.
    """

    def __init__(self, threshold: float,
                 labels: Optional[np.ndarray] = None):
        self.threshold = float(threshold)
        self._labels = None if labels is None else np.asarray(labels)

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        if not plan.symmetric_problem:
            raise ValueError(
                "EdgeCountSink counts unordered pairs of one variable set — "
                "it requires a symmetric problem (corr(x) or masked "
                "corr(x, where=...)), not a rectangular X-vs-Y run")
        if self._labels is not None and self._labels.shape != (plan.n,):
            raise ValueError(
                f"labels shape {self._labels.shape} != (n={plan.n},)")
        self.edges = 0
        self.degrees = np.zeros(plan.n, np.int64)
        self.intra_edges = 0 if self._labels is not None else None

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        plan = self.plan
        t, n = plan.t, plan.n
        ys, xs = plan.workload.job_coord_batch(np.asarray(ids))
        vals = np.asarray(tiles)
        span = np.arange(t)
        rows = ys[:, None] * t + span          # (P, t) global row indices
        cols = xs[:, None] * t + span          # (P, t) global col indices
        hit = np.abs(vals) >= self.threshold
        valid = (rows[:, :, None] < n) & (cols[:, None, :] < n)
        strict = rows[:, :, None] < cols[:, None, :]
        count = hit & valid & strict
        self.edges += int(count.sum())
        np.add.at(self.degrees, np.broadcast_to(rows[:, :, None],
                                                count.shape)[count], 1)
        np.add.at(self.degrees, np.broadcast_to(cols[:, None, :],
                                                count.shape)[count], 1)
        if self._labels is not None:
            lab = self._labels
            lr = lab[np.minimum(rows, n - 1)]
            lc = lab[np.minimum(cols, n - 1)]
            same = lr[:, :, None] == lc[:, None, :]
            self.intra_edges += int((count & same).sum())

    def result(self) -> dict:
        out = {"edges": self.edges, "degrees": self.degrees}
        if self._labels is not None:
            out["intra_edges"] = self.intra_edges
            out["inter_edges"] = self.edges - self.intra_edges
        return out


class RowBlockSink(TileSink):
    """Assemble a grid workload's tiles directly into independent per-segment
    host arrays — the serving batcher's scatter (serving/batcher.py).

    One coalesced launch computes the stacked probe slabs of several
    requests against the corpus; this sink lands each request's rows in its
    own (m_i, n_cols) array as the tiles stream past, so no
    (rows_bucket, n_cols) intermediate is ever materialised and each
    result's lifetime is independent of its batch-mates (a request's future
    can release its rows without pinning the whole batch).

    `bounds` are half-open global row ranges [(lo, hi), ...] — typically
    the request boundaries of a stacked probe slab.  Ranges may straddle
    tile boundaries arbitrarily; rows outside every range (slab padding up
    to the plan's row bucket) are discarded.
    """

    def __init__(self, bounds):
        self._bounds = [(int(lo), int(hi)) for lo, hi in bounds]
        for lo, hi in self._bounds:
            if lo < 0 or hi < lo:
                raise ValueError(f"bad row range [{lo}, {hi})")

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        if plan.workload.needs_symmetrize:
            raise ValueError(
                "RowBlockSink assembles grid workloads (rectangular "
                "X-vs-Y); symmetric triangular runs mirror tiles across "
                "segments — use HostSink/DenseSink there")
        for lo, hi in self._bounds:
            if hi > plan.n_rows:
                raise ValueError(
                    f"row range [{lo}, {hi}) exceeds plan rows "
                    f"{plan.n_rows}")
        # padded column width: tiles write whole (t, t) blocks; result()
        # crops to the true column count
        self._outs = [np.zeros((hi - lo, self.plan.col_pad), np.float32)
                      for lo, hi in self._bounds]

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        plan = self.plan
        t = plan.t
        ys, xs = plan.workload.job_coord_batch(np.asarray(ids))
        vals = np.asarray(tiles)
        span = np.arange(t)
        for (lo, hi), out in zip(self._bounds, self._outs):
            pick = (ys * t < hi) & (ys * t + t > lo)
            if not pick.any():
                continue
            sub = vals[pick]
            rows = (ys[pick, None] * t + span)[:, :, None]    # (P, t, 1)
            cols = (xs[pick, None] * t + span)[:, None, :]    # (P, 1, t)
            ok = (rows >= lo) & (rows < hi)
            okb = np.broadcast_to(ok, sub.shape)
            out[np.broadcast_to(rows - lo, sub.shape)[okb],
                np.broadcast_to(cols, sub.shape)[okb]] = sub[okb]

    def result(self) -> list:
        meas = self.plan.measure
        outs = [o[:, : self.plan.n_cols] for o in self._outs]
        if self.plan.clip and meas.clip is not None:
            for o in outs:
                np.clip(o, meas.clip[0], meas.clip[1], out=o)
        return outs


class ExceedanceSink(TileSink):
    """Turn per-pass null-exceedance *count* tiles into p-value tiles and
    hand them to an inner TileSink — the significance workload's output leg
    (core/significance.py, paper SSIV).

    The significance executor accumulates, per pass, an int32 count tile
    buffer ``#{b : |R_b| >= |R_obs|}`` on device, reduced over the replica
    axis chunk by chunk — O(pass_tiles) int32 state, never a (B, n, n)
    array.  This sink receives that finished count buffer once per pass,
    applies the add-one estimator  p = (1 + count) / (1 + B)  (B from
    ``plan.replicas`` unless given explicitly), and delegates the resulting
    p-value tiles to ``inner`` (default DenseSink) — so p-values compose
    with every output mode the engine has: dense device matrix, host/memmap
    assembly with durable per-pass checkpoints, top-k, reductions.

    Symmetric workloads: the replica kernel's diagonal tiles are *not*
    internally symmetric (entry (i, j) compares against <U_i, pi(U_j)>,
    entry (j, i) against <U_j, pi(U_i)>).  The canonical output keeps the
    elementwise upper triangle — exactly what DenseSink's symmetrize does —
    so this sink mirrors each diagonal tile's upper half into its lower
    half *before* delegation, making every inner sink (including HostSink,
    which writes diagonal tiles verbatim) agree bit-for-bit.

    open() expects the significance plan handed down by the executor (its
    `measure` is the p-value pseudo-measure naming base measure, method and
    key, so HostSink checkpoint specs can never confuse a p-value memmap
    with an r memmap, or two different null distributions with each other).
    """

    def __init__(self, inner: Optional[TileSink] = None,
                 iterations: Optional[int] = None):
        self._inner = inner if inner is not None else DenseSink()
        self._iterations = iterations

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        b = (self._iterations if self._iterations is not None
             else plan.replicas)
        if b <= 0:
            raise ValueError(
                "ExceedanceSink needs the replica count: open it with a "
                "significance plan (ExecutionPlan.create(replicas=B)) or "
                "pass iterations= explicitly")
        self.iterations = int(b)
        self._inner.open(plan)

    def resume_pass(self) -> int:
        return getattr(self._inner, "resume_pass", lambda: 0)()

    def skip_passes(self) -> set:
        return getattr(self._inner, "skip_passes", set)()

    def covered(self):
        return getattr(self._inner, "covered", lambda: None)()

    def rebind(self, new_plan: ExecutionPlan) -> None:
        self.plan = new_plan
        getattr(self._inner, "rebind", lambda _p: None)(new_plan)

    def pass_complete(self, k: int) -> None:
        getattr(self._inner, "pass_complete", lambda _k: None)(k)

    def _pvals(self, content_ids: np.ndarray, counts) -> np.ndarray:
        c = np.asarray(counts).astype(np.float32)
        p = (1.0 + c) / np.float32(1.0 + self.iterations)
        if self.plan.workload.needs_symmetrize:
            ys, xs = self.plan.workload.job_coord_batch(
                np.asarray(content_ids))
            diag = ys == xs
            if diag.any():
                t = self.plan.t
                upper = np.triu(np.ones((t, t), bool))
                d = p[diag]
                p[diag] = np.where(upper, d, np.transpose(d, (0, 2, 1)))
        return p

    def consume(self, ids: np.ndarray, counts) -> None:
        self._inner.consume(ids, self._pvals(ids, counts))

    def consume_clamped(self, padded_ids: np.ndarray, sel: np.ndarray,
                        ids: np.ndarray, counts) -> None:
        # content is keyed by the clamped per-slot ids (duplicates carry
        # identical counts, so the diagonal mirror is idempotent over them)
        self._inner.consume_clamped(padded_ids, sel, ids,
                                    self._pvals(padded_ids, counts))

    def result(self):
        return self._inner.result()


def topk_merge_rows(vals: np.ndarray, idx: np.ndarray, r_ids: np.ndarray,
                    c_ids: np.ndarray, v: np.ndarray, k: int,
                    dedup: bool = False) -> None:
    """THE canonical per-row top-k merge, in place.

    ``vals``/``idx`` are (n_rows, k) running state (index -1 = empty slot);
    (r_ids, c_ids, v) are candidate triples.  Candidates merge under the
    canonical total order — |value| desc, then column asc — so the retained
    top-k is a *set function* of the candidates seen: independent of pass
    partitioning, merge order, and state capacity >= k, ties included.
    That invariant is what lets the serving batcher slice one
    TopKSink(k_max) run into per-request top-k lists bit-identical to
    standalone TopKSink(k) runs, what lets live corpora (serving/live.py)
    re-merge *delta* candidates into standing top-k results without
    replaying the passes that produced the state, and what makes per-host
    partial top-k states (the device-side epilogue, kernels/pcc_tile.py)
    merge into exactly the single-host answer.

    A row's candidate columns must be unique and must not duplicate
    columns already held for that row (duplicates would occupy two slots).
    ``dedup=True`` relaxes that: exact (column, value) duplicates — which a
    recovering executor produces when a retried pass re-delivers a device
    top-k state overlapping already-covered tiles — sort adjacent under the
    canonical order and all but the first are dropped before truncation.
    """
    order = np.argsort(r_ids, kind="stable")
    r_s, c_s, v_s = r_ids[order], c_ids[order], v[order]
    uniq, starts = np.unique(r_s, return_index=True)
    bounds = np.append(starts, len(r_s))
    for u, lo, hi in zip(uniq, bounds[:-1], bounds[1:]):
        cand_v = np.concatenate([vals[u], v_s[lo:hi]])
        cand_i = np.concatenate([idx[u], c_s[lo:hi]])
        key = np.abs(cand_v)
        key[cand_i < 0] = -np.inf  # empty slots lose to any candidate
        sel = np.lexsort((cand_i, -key))
        if dedup:
            ci, cv = cand_i[sel], cand_v[sel]
            keep = np.ones(sel.size, bool)
            keep[1:] = ~((ci[1:] == ci[:-1]) & (ci[1:] >= 0)
                         & (cv[1:] == cv[:-1]))
            sel = sel[keep]
        sel = sel[:k]
        vals[u] = cand_v[sel]
        idx[u] = cand_i[sel]


class TopKSink(TileSink):
    """Streaming per-row top-k neighbours: keep the k strongest-|r| partners
    of every row without materialising the matrix — O(n_rows * k) state.

    For symmetric workloads a tile (y, x) contributes its entries to the
    rows of block y *and* (mirrored) to the rows of block x, and self-pairs
    (row == col) are excluded; rectangular workloads rank each X row's
    neighbours among the Y rows.  Each pass merges its candidate
    (row, col, value) triples into the running per-row top-k (sorted by
    descending |value|, ties broken by ascending column index — a
    canonical order, so the kept set is independent of pass partitioning),
    so memory never exceeds the state plus one pass.

    result() is {"indices": (n_rows, k) int64, "values": (n_rows, k) f32};
    rows with fewer than k valid partners pad with index -1 / value 0.
    """

    def __init__(self, k: int):
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        self.k = int(k)

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        self.vals = np.zeros((plan.n_rows, self.k), np.float32)
        self.idx = np.full((plan.n_rows, self.k), -1, np.int64)

    def consume(self, ids: np.ndarray, tiles: Array) -> None:
        plan = self.plan
        t, n_r, n_c = plan.t, plan.n_rows, plan.n_cols
        ys, xs = plan.workload.job_coord_batch(np.asarray(ids))
        vals = np.asarray(tiles)
        span = np.arange(t)
        rows = (ys[:, None] * t + span)[:, :, None]  # (P, t, 1)
        cols = (xs[:, None] * t + span)[:, None, :]  # (P, 1, t)
        rows_g = np.broadcast_to(rows, vals.shape)
        cols_g = np.broadcast_to(cols, vals.shape)
        ok = (rows_g < n_r) & (cols_g < n_c)
        if plan.symmetric_problem:
            # row i's own column is not a neighbour (true for the triangle
            # AND for symmetric-grid masked runs, where the workload is a
            # full square but the diagonal is still self-vs-self)
            ok &= rows_g != cols_g
        r_ids, c_ids, v = rows_g[ok], cols_g[ok], vals[ok]
        if plan.workload.needs_symmetrize:
            # mirror off-diagonal tiles: entry (i, j) is also row j's
            # neighbour i.  Diagonal tiles already hold both orders, and
            # grid workloads (symmetric or not) carry every cell once.
            off = (ys != xs)[:, None, None] & ok
            r_ids = np.concatenate([r_ids, cols_g[off]])
            c_ids = np.concatenate([c_ids, rows_g[off]])
            v = np.concatenate([v, vals[off]])
        self._merge(r_ids, c_ids, v)

    def _merge(self, r_ids: np.ndarray, c_ids: np.ndarray,
               v: np.ndarray) -> None:
        topk_merge_rows(self.vals, self.idx, r_ids, c_ids, v, self.k)

    def result(self) -> dict:
        self.vals[self.idx < 0] = 0.0
        return {"indices": self.idx, "values": self.vals}


class DeviceTopKSink(TopKSink):
    """TopKSink fed by the device-side top-k epilogue
    (kernels/pcc_tile.pcc_topk_tiles): the executor streams per-row-block
    top-k *state* instead of tiles, so only O(n * k) crosses the
    device->host boundary per pass — the multi-host serving path, where
    shipping O(n^2 / hosts) of tiles would swamp the interconnect.

    ``wants_device_state`` routes the executor to the top-k kernel;
    ``merge_dedups`` tells the *recovering* executor that a retried pass
    may re-deliver candidates whose tiles are already covered — the
    canonical merge drops exact duplicates, so coverage filtering (which
    cannot subset a state-shaped buffer) is unnecessary.

    Because the in-kernel selection replicates topk_merge_rows' canonical
    order, result() is bit-identical to plain TopKSink(k) on the same
    plan — single-host or across any mesh partition.
    """

    wants_device_state = True
    merge_dedups = True

    @staticmethod
    def supports(plan: ExecutionPlan) -> bool:
        """Whether this plan can take the device-side top-k path (the
        predicate ``open()`` enforces) — callers that want a silent
        TopKSink fallback (serving/batcher.py) test this first."""
        from repro.core.plan import needs_row_scales
        return (plan.fused
                and plan.measure.tile_kernel is None
                and not plan.replicas
                and not needs_row_scales(plan.measure, plan.compute_dtype))

    def open(self, plan: ExecutionPlan) -> None:
        super().open(plan)
        if not plan.fused:
            raise ValueError(
                "DeviceTopKSink needs the fused epilogue: the in-kernel "
                "merge ranks *finalised* values (post div/clip), so an "
                "unfused plan would rank unscaled accumulator sums")
        if plan.measure.tile_kernel is not None:
            raise ValueError(
                f"DeviceTopKSink cannot run measure {plan.measure.name!r}: "
                f"custom tile kernels bypass the top-k epilogue — use "
                f"TopKSink")
        if plan.replicas:
            raise ValueError("DeviceTopKSink does not support replica "
                             "(significance) runs")
        from repro.core.plan import needs_row_scales
        if needs_row_scales(plan.measure, plan.compute_dtype):
            raise ValueError(
                "DeviceTopKSink does not support quantized scaled operands "
                "— the dequant outer product is not fused into the top-k "
                "merge; use TopKSink")

    def consume(self, ids: np.ndarray, state) -> None:
        """One pass's state stacks: (row_vals, row_cols) each (D * m, t, kk)
        with D devices' states stacked (D == 1 for local runs), plus for
        triangular runs (col_vals, col_cols) each (D * launch, t, kk) — one
        column-side state per tile slot — and the slots' clamped tile ids.
        `ids` is the pass's valid tile set — unused for content (the
        kernel's validity guard already excluded clamped slots) but part of
        the coverage contract."""
        del ids
        plan = self.plan
        t, n_r = plan.t, plan.n_rows
        m = plan.n_pad // t
        # slab j of each device's m-block row state is global row block j % m
        row_blocks = np.arange(np.shape(state[0])[0]) % m
        stacks = [(state[0], state[1], row_blocks)]
        if len(state) > 2:
            # a slot's column-side state ranks the rows of its column block
            _, slot_blocks = plan.workload.job_coord_batch(
                np.asarray(state[4], np.int64))
            stacks.append((state[2], state[3], slot_blocks))
        for sv, sc, blocks in stacks:
            sv, sc = np.asarray(sv), np.asarray(sc)
            rows = np.broadcast_to(
                (blocks[:, None] * t + np.arange(t))[:, :, None], sv.shape)
            ok = (sc >= 0) & (rows < n_r)
            if not ok.any():
                continue
            topk_merge_rows(self.vals, self.idx, rows[ok],
                            sc[ok].astype(np.int64), sv[ok], self.k,
                            dedup=True)


__all__ = [
    "TileSink",
    "DenseSink",
    "HostSink",
    "ShardedHostSink",
    "ShardedMatrix",
    "open_manifest",
    "assemble",
    "ReductionSink",
    "EdgeCountSink",
    "RowBlockSink",
    "ExceedanceSink",
    "TopKSink",
    "DeviceTopKSink",
    "topk_merge_rows",
    "scatter_tiles",
    "scatter_tiles_at",
    "place_tiles_host",
    "symmetrize",
]
