"""Crowd spend of the sessions completed in the window over their
candidate pairs (the source paper's cost metric)."""


def read(rec):
    n = sum(s.n_pairs for s in rec.served)
    return sum(s.spent_cents for s in rec.served) / n if n else None
