#!/usr/bin/env python3
"""Readings a cell's limits are set from: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--seconds s]

For each seed, in one process: a short run of the cell (warm-up, a window
of ``--seconds`` seconds, the check) gives the program's reading of every
compared number; then the *control*, the plain reference with one
guarantee broken, is put in the program's place over the same sessions and
compared the same way:

* human phase: the reference labeler that never uses non-match edges
  (``positive_only``), so it asks the crowd pairs that deduction settles;
* machine phase: the dense candidates scored in three bf16 passes (what
  ``Precision.HIGH`` does on a TPU), the precision below the
  configuration's float32 at HIGHEST, handed on as the program hands them
  (likelihood ``(score + 1) / 2`` in float32).

Prints one JSON line per seed and side.  The benchmark's own runs never
run the control."""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def control_candidates(a, b, threshold: float, block: int = 512):
    """Dense candidates scored in three bf16 passes, as ``Precision.HIGH``
    computes a float32 product on a TPU: each operand split into a bf16
    head and a bf16 tail (``reduce_precision``, which the compiler keeps),
    and head*head + head*tail + tail*head summed in float32.  Written out,
    so that the CPU computes the same numbers as the chip.
    Returns (rows, cols, float32 likelihood)."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    def dot(x, y):
        return jnp.dot(x, y.T, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def scores(x, y):
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        y = y / jnp.linalg.norm(y, axis=1, keepdims=True)
        (xh, xl), (yh, yl) = split(x), split(y)
        return dot(xh, yh) + (dot(xh, yl) + dot(xl, yh))

    rows, cols, lik = [], [], []
    b = jnp.asarray(b, jnp.float32)
    for r0 in range(0, a.shape[0], block):
        s = np.asarray(scores(jnp.asarray(a[r0:r0 + block], jnp.float32),
                              b))
        r, c = np.nonzero(s >= threshold)
        rows.append(r + r0)
        cols.append(c)
        lik.append(((s[r, c] + np.float32(1.0)) / np.float32(2.0))
                   .astype(np.float32))
    return (np.concatenate(rows).astype(np.int32),
            np.concatenate(cols).astype(np.int32), np.concatenate(lik))


def control_served(cell, pool: list) -> list:
    """One served session per pool entry, produced by the control."""
    from bench import check, harness, reference

    out = []
    per_q = check.cents_per_question(cell)
    machine = cell.traffic.get("machine")
    for k, sess in enumerate(pool):
        cand = None
        if machine is not None:
            cand = control_candidates(np.asarray(sess["a"]),
                                      np.asarray(sess["b"]),
                                      sess["threshold"])
        u, v, lik, n = check.human_inputs(sess, cand)
        ref = reference.label_session(u, v, lik, n,
                                      check.session_answers(sess, cand),
                                      positive_only=True)
        out.append(harness.Served(
            pool_index=k, latency_s=0.0, n_pairs=len(u),
            labels=ref["labels"], crowdsourced=ref["crowdsourced"],
            round_sizes=ref["round_sizes"],
            n_rounds=len(ref["round_sizes"]),
            spent_cents=per_q * float(ref["crowdsourced"].sum()),
            candidates=cand))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench import check, harness

    cell = harness.Cell.find(args.workload)
    for seed in args.seeds:
        line = harness.run(cell, seed, args.seconds, False,
                           time.perf_counter())
        print(json.dumps({"side": "program", "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
        pool = harness.make_pool(cell, seed)
        checks = check.judge(cell, check.readings(cell, pool,
                                                  control_served(cell,
                                                                 pool)))
        print(json.dumps({"side": "control", "seed": seed,
                          "correct": all(c["ok"] for c in checks.values()),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
