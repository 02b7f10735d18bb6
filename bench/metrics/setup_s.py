"""Process start to the start of the window: imports, session generation
and the warm-up pass over the pool."""


def read(rec):
    return rec.setup_s
