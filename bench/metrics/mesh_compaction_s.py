"""Device 0's time in the sharded candidate program less its ``pair_scores``
kernel calls (the compaction of its block's candidates), per run of the
program in the traced window: one run per session's machine phase."""
MODULE = "jit__sharded_candidates_jit"


def read(rec):
    if rec.trace is None:
        return None
    runs = [secs for name, secs in rec.trace.modules if name == MODULE]
    if not runs:
        return None
    kernel = sum(op.dur for op in rec.trace.kernel_calls(MODULE))
    return (sum(runs) - kernel) / len(runs)
