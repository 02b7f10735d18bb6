"""Median over batches of the ``JoinService.run()`` host span divided by
the batch's sessions."""
import statistics


def read(rec):
    per = [(t1 - t0) / n for name, t0, t1, n in rec.spans
           if name == "run" and n]
    return statistics.median(per) if per else None
