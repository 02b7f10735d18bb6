"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

``--snapshot[=PATH]`` additionally writes a persisted perf snapshot
(default ``BENCH_join.json``, committed per PR so the trajectory of
candidate cells/s, rounds/s, and crowd cents per resolved pair is tracked
in-repo instead of evaporating with each CI run): the raw ``# JSON``
payloads each bench emits, plus a small derived ``trajectory`` block with
the headline numbers.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _trajectory(payloads: dict) -> dict:
    """Headline numbers distilled from the per-bench payloads — the fields
    the ROADMAP trajectory tracks across PRs.  Tolerant of missing benches
    (a partial ``--snapshot bench_blocking`` run snapshots what it ran)."""
    traj: dict = {}
    blocking = payloads.get("bench_blocking", {})
    if "blocked" in blocking:
        traj["candidate_cells_per_s"] = \
            blocking["blocked"]["candidate_cells_per_s"]
        traj["blocked_cells_saved_frac"] = \
            blocking["blocked"]["cells_saved_frac"]
        traj["blocker_recall"] = blocking["recall"]["recall"]
    svc = payloads.get("bench_join_service", {})
    if "machine" in svc:
        traj["dense_pairs_scored_per_s"] = svc["machine"]["pairs_scored_per_s"]
    if "engine_rounds" in svc:
        ms = svc["engine_rounds"]["mean_ms_per_round"]["incremental"]
        traj["rounds_per_s"] = 1000.0 / ms if ms else None
        fused = svc["engine_rounds"].get("fused")
        if fused:  # §13 on-device round engine headline numbers
            traj["fused_rounds_per_s"] = fused["rounds_per_s"]
            traj["fused_dispatches_per_round"] = fused["dispatches_per_round"]
            traj["fused_speedup_vs_per_lane"] = fused["speedup_vs_per_lane"]
    if "recovery" in svc:  # §16 durable serving headline numbers
        traj["recovery_restore_ms"] = svc["recovery"]["restore_ms"]
        traj["recovery_cents_saved_frac"] = svc["recovery"]["saved_frac"]
        traj["recovery_labels_identical"] = \
            svc["recovery"]["labels_identical"]
    if "human" in svc:
        traj["crowd_cents_per_resolved_pair"] = \
            svc["human"]["cents_per_resolved_pair"]
        traj["crowd_saved_frac"] = svc["human"]["saved_frac"]
    noise = payloads.get("noise_sweep", {})
    if "worker_quality" in noise:  # §15 worker-quality + cluster-task stage
        wq = noise["worker_quality"]
        traj["crowd_cents_per_resolved_pair_mixed"] = \
            wq["mixed"]["cents_per_resolved_pair"]
        traj["crowd_cents_per_resolved_pair_majority"] = \
            wq["majority"]["cents_per_resolved_pair"]
        traj["worker_quality_f_em"] = wq["em"]["f_measure"]
        traj["worker_quality_f_majority"] = wq["majority"]["f_measure"]
    plan = payloads.get("bench_plan", {})
    if "repeat" in plan:  # §14 plan layer + cluster cache headline numbers
        traj["plan_repeat_saved_frac"] = plan["repeat"]["saved_frac"]
        traj["plan_pushdown_reduction"] = \
            plan["pushdown"]["candidate_reduction"]
    return traj


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from . import (bench_blocking, bench_join_service, bench_plan,
                   bench_streaming, boruvka_parity, fig11_clusters,
                   fig12_transitive, fig13_orders, fig14_parallel,
                   fig16_optimizations, noise_sweep, table1_latency,
                   table2_quality)
    mods = [fig11_clusters, fig12_transitive, fig13_orders, fig14_parallel,
            fig16_optimizations, table1_latency, table2_quality,
            boruvka_parity, bench_join_service, bench_streaming,
            bench_blocking, bench_plan, noise_sweep]
    args = sys.argv[1:]
    snapshot_path = None
    for arg in list(args):
        if arg == "--snapshot" or arg.startswith("--snapshot="):
            snapshot_path = (arg.split("=", 1)[1] if "=" in arg
                             else "BENCH_join.json")
            args.remove(arg)
    only = args[0] if args else None
    print("name,us_per_call,derived")
    payloads: dict = {}
    t0 = time.time()
    for m in mods:
        name = m.__name__.split(".")[-1]
        if only and only not in name:
            continue
        for r in m.run():
            if r.startswith("# JSON "):
                payloads.update(json.loads(r[len("# JSON "):]))
            print(r, flush=True)
    print(f"# total {time.time()-t0:.1f}s", flush=True)
    if snapshot_path is not None:
        config = {"tiny": os.environ.get("BENCH_JOIN_TINY", "") not in
                  ("", "0")}

        def _write(path: str, snap: dict) -> None:
            with open(path, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"# snapshot written to {path}", flush=True)

        _write(snapshot_path, {
            "config": config,
            "trajectory": _trajectory(payloads),
            "benches": payloads,
        })
        # per-subsystem snapshots ride along in the same directory so the
        # streaming and blocking trajectories are tracked in-repo too
        outdir = os.path.dirname(snapshot_path)
        for bench, fname in (("bench_streaming", "BENCH_streaming.json"),
                             ("bench_blocking", "BENCH_blocking.json"),
                             ("bench_plan", "BENCH_plan.json")):
            if bench in payloads:
                _write(os.path.join(outdir, fname) if outdir else fname, {
                    "config": config,
                    "benches": {bench: payloads[bench]},
                })


if __name__ == "__main__":
    main()
