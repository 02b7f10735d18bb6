"""Median host span of a ``submit_embeddings`` call, which scores and
compacts the candidates before it returns."""
import statistics


def read(rec):
    spans = rec.span_durations("machine_phase")
    return statistics.median(spans) if spans else None
