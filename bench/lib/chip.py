"""The device the benchmark runs on: the check that a chip is there, the
persistent compilation cache, compile counting and peak memory."""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, List

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """Raised where JAX finds no TPU, or fewer chips than the cell asks
    for: the run exits non-zero and prints no result."""


def enable_compile_cache(root: Path) -> str:
    """Keep every compiled program in `<checkout>/.jax_cache` (or where
    JAX_COMPILATION_CACHE_DIR says), so that only a cell's first run in a
    checkout compiles.  Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chip(chips: int) -> List:
    """The first `chips` TPU devices, or NoChip."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX found {devices[0].platform} "
                     f"devices")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts backend compilations and persistent-cache loads from JAX's
    monitoring events.  A compile request that the persistent cache
    answers is a load, not a compile."""

    _registered: List["CompileCounter"] = []

    def __init__(self):
        self.requests = 0
        self.loads = 0
        self.seconds = 0.0
        if not CompileCounter._registered:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            monitoring.register_event_listener(CompileCounter._on_event)
        CompileCounter._registered.append(self)

    @staticmethod
    def _on_duration(event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            for c in CompileCounter._registered:
                c.requests += 1
                c.seconds += secs

    @staticmethod
    def _on_event(event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            for c in CompileCounter._registered:
                c.loads += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.requests - self.loads, "loads": self.loads,
                "seconds": self.seconds}


def delta(after: Dict[str, float], before: Dict[str, float]
          ) -> Dict[str, float]:
    return {k: after[k] - before[k] for k in after}


def device_info(devices: List) -> dict:
    """What the result line says of the device: platform and kind as JAX
    reports them, the chips present, and the peak on the fullest chip."""
    import jax
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": max(peaks) if peaks else None}


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
