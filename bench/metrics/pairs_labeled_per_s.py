"""Candidate pairs labelled by the sessions completed in the window, over
the window's time."""


def read(rec):
    if not rec.served:
        return None
    return sum(s.n_pairs for s in rec.served) / rec.window_s
