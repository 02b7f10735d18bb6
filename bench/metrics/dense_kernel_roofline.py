"""Share of the roofline of the dense ``pair_scores`` kernel: the least
time for 2*N*M*D FLOPs and 4*(N*D + M*D + N*M) bytes per call, over the
unpadded session shape, against the kernel's summed device time."""
from bench import roofline

MODULE = "jit__sharded_candidates_jit"


def read(rec):
    if rec.trace is None:
        return None
    calls = rec.trace.kernel_calls(MODULE)
    t = sum(op.dur for op in calls)
    if not calls or t <= 0:
        return None
    c = rec.cell.config
    work = roofline.dense_work(c["n_a"], c["n_b"], c["dim"])
    return roofline.share(work, len(calls), t, rec.device_kind)
