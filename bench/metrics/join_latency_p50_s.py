"""Median session latency: from the start of the session's own submit to
the return of the ``run()`` that delivered it."""
import statistics


def read(rec):
    lat = [s.latency_s for s in rec.served]
    return statistics.median(lat) if lat else None
