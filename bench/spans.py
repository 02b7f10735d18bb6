#!/usr/bin/env python3
"""A cell served with the program's own spans on (``repro.obs``).

    python3 bench/spans.py --workload <cell> --seed <n> [--seconds <s>]
                           [--trace 0|1]
    python3 bench/spans.py --sample <path>

The first form builds and warms the cell as ``bench/run.py`` does, then
serves batches for ``--seconds`` with spans off and on in turn, and (with
``--trace 1``, the default) a traced window of ``harness.TRACE_SECONDS``
with spans on.  Its last line
on standard output is one JSON object: the cost of spans (median
``run()`` and batch time, off against on), the per-layer numbers the
spans and counters give, and each device idle gap of the traced window
credited to the innermost ``join.*`` span open over its midpoint
(:func:`idle_by_span`).  It needs a TPU, as ``bench/run.py`` does.

The second form records a small trace for ``bench/tests``: two tiny
sessions through ``JoinService`` with spans on, one answer of which the
crowd holds back 50 ms inside ``join.gateway.post``."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, trace  # noqa: E402

STAGE_PREFIX = "join."
OUTER = ("join.run", "join.submit")   # spans that hold every stage


# ---------------------------------------------------------------------------
# idle attribution
# ---------------------------------------------------------------------------
def host_spans(pd, prefix: str) -> List[Tuple[str, float, float]]:
    """(name, start, end) in seconds of every ``/host:CPU`` event whose
    name starts with ``prefix``, sorted by start."""
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix) and e.name != "join.clock":
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9))
    return sorted(out, key=lambda x: x[1])


def credit_gaps(gaps: List[Tuple[float, float]],
                spans: List[Tuple[str, float, float]]
                ) -> List[Tuple[str, float]]:
    """Each gap's length credited to the innermost span (``spans`` sorted
    by start) open over its midpoint, or to ``none``; summed by name,
    largest first."""
    starts = [s for _, s, _ in spans]
    agg: Dict[str, float] = {}
    for g0, g1 in gaps:
        name = trace._innermost(spans, starts, (g0 + g1) / 2) or "none"
        agg[name] = agg.get(name, 0.0) + (g1 - g0)
    return sorted(agg.items(), key=lambda kv: -kv[1])


def idle_by_span(pd) -> List[Tuple[str, float]]:
    """The idle gaps of device 0 in the traced window (the window and the
    device ops of ``bench/trace.py``), credited to ``join.*`` spans."""
    red = trace.reduce_profile(pd)
    bench_spans = host_spans(pd, "bench.")
    lo = min(s for _, s, _ in bench_spans)
    hi = max(e for _, _, e in bench_spans)
    ops = [(o.start, o.start + o.dur) for o in red.ops]
    return credit_gaps(trace.gaps(ops, lo, hi),
                       host_spans(pd, STAGE_PREFIX))


def stage_share(idle: List[Tuple[str, float]]) -> float:
    """Share of the idle time credited to a ``join.*`` stage below the
    outer ``join.run`` and ``join.submit``."""
    total = sum(s for _, s in idle)
    staged = sum(s for n, s in idle
                 if n.startswith(STAGE_PREFIX) and n not in OUTER)
    return staged / total if total > 0 else 0.0


# ---------------------------------------------------------------------------
# numbers of the program's spans and counters
# ---------------------------------------------------------------------------
def seconds_of(spans, prefix: str) -> float:
    return sum(s.end_ns - s.start_ns for s in spans
               if s.name.startswith(prefix)) * 1e-9


def gateway_s_per_session(spans, n_sessions: int) -> Optional[float]:
    """Summed ``join.gateway.*`` time over the sessions served."""
    t = seconds_of(spans, "join.gateway.")
    return t / n_sessions if n_sessions and t > 0 else None


def lsh_signatures_s(spans) -> Optional[float]:
    """Median over sessions of their summed ``join.machine.signatures``
    time."""
    per: Dict[int, float] = {}
    for s in spans:
        if s.name == "join.machine.signatures" and s.rid is not None:
            per[s.rid] = per.get(s.rid, 0.0) + (s.end_ns - s.start_ns) * 1e-9
    return statistics.median(per.values()) if per else None


def engine_device_s(red) -> float:
    """Device seconds of the round engine's programs (``jit_engine_*``)."""
    return sum(t for name, t in red.module_time().items()
               if name.startswith("jit_engine_"))


# ---------------------------------------------------------------------------
# a cell with spans on
# ---------------------------------------------------------------------------
def _batch(client, pool, idx, spans: list) -> Tuple[float, float, list]:
    """One batch: (run() seconds, batch seconds, sessions served)."""
    n0 = len(spans)
    got = client.serve_batch(pool, idx, spans)
    mine = spans[n0:]
    (run,) = [x for x in mine if x[0] == "run"]
    start = min(x[1] for x in mine)
    return run[2] - run[1], run[2] - start, got


def run_cell(cell, seed: int, seconds: float, require_chip: bool = True,
             trace_dir: Optional[str] = None) -> dict:
    import jax

    from repro import obs
    from repro.launch.compile_cache import use_compile_cache

    devs = harness.check_devices(cell, require_chip)
    use_compile_cache(harness.ROOT)
    pool = harness.make_pool(cell, seed)
    client = harness.Client(cell)
    sched = harness.batches(cell, seed)
    for _ in range(-(-cell.traffic["pool"] // cell.traffic["batch"])):
        client.serve_batch(pool, next(sched), [])
    # spans off and on, batch by batch
    obs.disable()
    obs.reset()
    times = {False: ([], []), True: ([], [])}
    served = {False: [], True: []}
    spans: list = []
    s0, d0 = obs.host_syncs.count, obs.engine_dispatches.count
    deadline = time.perf_counter() + seconds
    k = 0
    while time.perf_counter() < deadline or k < 4:
        on = bool(k % 2)
        if on:
            obs.enable()
        t_run, t_batch, got = _batch(client, pool, next(sched), spans)
        obs.disable()
        times[on][0].append(t_run)
        times[on][1].append(t_batch)
        served[on].extend(got)
        k += 1
    syncs = obs.host_syncs.count - s0
    dispatches = obs.engine_dispatches.count - d0
    on_spans = obs.spans()
    rounds = sum(s.n_rounds for s in served[False] + served[True])
    cost = {}
    for i, what in enumerate(("run_s", "batch_s")):
        off_m = statistics.median(times[False][i])
        on_m = statistics.median(times[True][i])
        cost[what] = {"off": times[False][i], "on": times[True][i],
                      "median_off": off_m, "median_on": on_m,
                      "on_over_off": on_m / off_m}
    metrics = {
        "gateway_s_per_session": gateway_s_per_session(
            on_spans, len(served[True])),
        "lsh_signatures_s": lsh_signatures_s(on_spans),
        "host_syncs_per_round": syncs / rounds if rounds else None,
        "dispatches_per_round": dispatches / rounds if rounds else None,
    }
    out = {"workload": cell.name, "seed": seed,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind},
           "batches": k, "spans_cost": cost, "metrics": metrics,
           "span_counts": _counts(on_spans)}
    if trace_dir is None:
        return out
    # the traced window, spans on from after the profiler starts
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    obs.reset()
    rec = harness.Record(cell=cell)
    jax.profiler.start_trace(trace_dir)
    obs.enable()
    harness.serve_window(client, pool, sched, harness.TRACE_SECONDS, rec)
    obs.disable()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace.find_xplane(trace_dir))
    red = trace.reduce_profile(pd)
    idle = idle_by_span(pd)
    t_rounds = sum(s.n_rounds for s in rec.served)
    metrics["engine_device_s_per_round"] = (
        engine_device_s(red) / t_rounds if t_rounds else None)
    out.update(traced={"window_s": red.window_s, "busy_s": red.busy_s,
                       "idle_s": sum(s for _, s in idle),
                       "stage_share": stage_share(idle),
                       "idle_by_span": [[n, s] for n, s in idle[:10]],
                       "breakdown": red.breakdown(),
                       "sessions": len(rec.served)})
    return out


def _counts(spans) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0) + 1
    return out


# ---------------------------------------------------------------------------
# the recorded sample for bench/tests
# ---------------------------------------------------------------------------
def record_sample(path: str) -> None:
    import tempfile

    import jax

    from repro import obs
    from repro.core import PerfectCrowd
    from repro.data.entities import make_session_pairsets
    from repro.serve.join_service import JoinService

    class HeldBack(PerfectCrowd):
        """Holds its first answer back 50 ms."""

        held = False

        def ask_ballot(self, *args, **kwargs):
            if not HeldBack.held:
                HeldBack.held = True
                time.sleep(0.05)
            return super().ask_ballot(*args, **kwargs)

    def serve(crowd_cls):
        svc = JoinService(lanes=2)
        for ps in make_session_pairsets(2, seed=5, n_objects=(10, 16),
                                        n_pairs=(20, 31), n_entities=4):
            svc.submit(ps, crowd_cls())
        svc.run()

    serve(PerfectCrowd)   # compiles outside the trace
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    obs.enable()
    with jax.profiler.TraceAnnotation("bench.run"):
        serve(HeldBack)
    obs.disable()
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(d), path)
    shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--sample")
    args = ap.parse_args(argv)
    if args.sample:
        record_sample(args.sample)
        print(json.dumps({"sample": args.sample,
                          "bytes": os.path.getsize(args.sample)}))
        return 0
    cell = harness.Cell.find(args.workload)
    trace_dir = (os.path.join(harness.ROOT, ".bench_out", "spans_trace")
                 if args.trace else None)
    try:
        out = run_cell(cell, args.seed, args.seconds, trace_dir=trace_dir)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
