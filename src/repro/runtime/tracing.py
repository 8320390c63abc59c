"""Named host spans at the engine's layer boundaries.

``span("launch", call=3, **{"pass": 0})`` marks the host call that
dispatches one layer's device work as ``repro.launch`` in a
``jax.profiler`` trace, with its ids recorded as the event's stats.
Outside a profiler session a span records nothing; inside one, the
profiler keeps it in memory until the trace is written.
"""

from __future__ import annotations

import jax


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A ``repro.<name>`` trace annotation carrying `ids`."""
    return jax.profiler.TraceAnnotation("repro." + name, **ids)


__all__ = ["span"]
