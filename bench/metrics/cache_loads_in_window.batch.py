"""Programs loaded from the persistent compilation cache inside the window:
a call that traces and lowers its programs anew, and finds them on disk,
pays for it here and not in `compiles_in_window.batch`."""


def read(rec):
    if rec.kind != "solves" or "loads" not in rec.compiles:
        return None
    return rec.compiles["loads"]
