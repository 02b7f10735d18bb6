"""Backend compiles inside the measured window (``jax.monitoring``)."""


def read(rec):
    return rec.compiles
