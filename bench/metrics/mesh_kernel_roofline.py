"""Share of the roofline of the dense ``pair_scores`` kernel on a mesh:
each of device 0's calls inside the sharded candidate program is charged
with one device's block, 2*n*m*D FLOPs and 4*(n*D + m*D + n*m) bytes for
n = N/dd and m = M/dm over the traffic's (dd, dm) mesh, against the calls'
summed device time."""
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
    dd, dm = rec.cell.traffic["machine"]["mesh"]
    work = roofline.dense_work(-(-c["n_a"] // dd), -(-c["n_b"] // dm),
                               c["dim"])
    return roofline.share(work, len(calls), t, rec.device_kind)
