"""Share of the roofline of the ``pair_scores_compact`` tile kernel: the
least time for the FLOPs and bytes of ``tiles_per_call`` (bn, bm) tiles
per call against the kernel's summed device time."""
from bench import roofline

MODULE = "jit_pair_scores_compact"


def read(rec):
    if rec.trace is None:
        return None
    calls = rec.trace.kernel_calls(MODULE)
    t = sum(op.dur for op in calls)
    if not calls or t <= 0:
        return None
    m = rec.cell.traffic["machine"]
    work = roofline.compact_work(m["tiles_per_call"], m["bn"], m["bm"],
                                 rec.cell.config["dim"])
    return roofline.share(work, len(calls), t, rec.device_kind)
