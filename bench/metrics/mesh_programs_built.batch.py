"""Mesh pass programs (shard_map functions) the executor built per corr()
call, from the program's `executor_stats()` read once the run is over:
the window's solves and the set-up's warm solve alike.  One a call while
the mesh executor rebuilds its programs in every call; it falls toward 0
once they are kept across calls.  Nothing where the program has no such
counter."""


def read(rec):
    if rec.kind != "solves":
        return None
    try:
        from repro.core.api import executor_stats
    except ImportError:
        return None
    stats = executor_stats()
    if not stats["calls"]:
        return None
    return stats["mesh_programs_built"] / stats["calls"]
