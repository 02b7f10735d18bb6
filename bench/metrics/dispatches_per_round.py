"""Engine dispatches (``engine_dispatches`` counter of
``core/jax_graph.py``) over the window, per labeling round served."""


def read(rec):
    rounds = sum(s.n_rounds for s in rec.served)
    return rec.dispatches / rounds if rounds else None
