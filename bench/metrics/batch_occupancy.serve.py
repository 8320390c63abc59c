"""Mean share of real rows in the window's launches (real probe rows over
the bucketed launch rows), as the server reports it per answer."""


def read(rec):
    if not rec.occupancy:
        return None
    total = sum(w for _, w in rec.occupancy)
    return sum(o * w for o, w in rec.occupancy) / total
