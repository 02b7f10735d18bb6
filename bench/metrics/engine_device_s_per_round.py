"""Device time of the round engine's programs (``jit_engine_*``) in the
traced window, over the labelling rounds of the sessions served in it.

The traced window's sessions are those after the measured window's: the
measured window starts at its first span and lasts ``window_s``; its
``run`` spans give how many sessions it served."""
from bench.spans import engine_device_s


def traced_sessions(rec):
    if not rec.spans:
        return []
    cut = min(t0 for _, t0, _, _ in rec.spans) + rec.window_s
    n = sum(k for name, t0, _, k in rec.spans if name == "run" and t0 < cut)
    return rec.served[n:]


def read(rec):
    if rec.trace is None or rec.failed:
        return None
    t = engine_device_s(rec.trace)
    rounds = sum(s.n_rounds for s in traced_sessions(rec))
    return t / rounds if t > 0 and rounds else None
