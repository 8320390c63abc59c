"""repro.runtime — elastic re-meshing, fault injection, stragglers, HLO,
the compilation cache, trace spans.

Submodules and the re-exported train-loop names resolve lazily (PEP 562):
``repro.core`` imports the fault-injection harness (runtime/faults.py)
from its sink/executor hot paths, and an eager package import here would
both create a cycle (faults <- core.sinks <- core <- elastic <- core.plan)
and drag the whole train-loop stack into every engine import.
"""

_SUBMODULES = ("compile_cache", "elastic", "faults", "hlo", "straggler",
               "tracing", "train_loop")
_TRAIN_LOOP_NAMES = ("TrainLoop", "LoopConfig", "FailureInjected")

__all__ = [*_SUBMODULES, *_TRAIN_LOOP_NAMES]


def __getattr__(name):
    import importlib

    if name in _SUBMODULES:
        return importlib.import_module(f"repro.runtime.{name}")
    if name in _TRAIN_LOOP_NAMES:
        mod = importlib.import_module("repro.runtime.train_loop")
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.runtime' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
