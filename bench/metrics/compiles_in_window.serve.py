"""Programs compiled inside the window (persistent-cache loads excluded),
from JAX's compile events."""


def read(rec):
    if rec.kind != "search" or "compiles" not in rec.compiles:
        return None
    return rec.compiles["compiles"]
