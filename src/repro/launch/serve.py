"""Serving launcher: the durable join service with kill/restore recovery
(DESIGN.md §16).

    # run with checkpoints, kill after N commits...
    PYTHONPATH=src python -m repro.launch.serve \
        --checkpoint-dir /tmp/join_ckpt --kill-after 2

    # ...then resume from the latest checkpoint and finish
    PYTHONPATH=src python -m repro.launch.serve \
        --checkpoint-dir /tmp/join_ckpt --resume
"""
from __future__ import annotations

import argparse

import numpy as np


def _join_workload(seed: int, n: int = 48, p: int = 160):
    from repro.core.pairs import PairSet
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, 8, n)
    u = rng.integers(0, n, p).astype(np.int32)
    v = rng.integers(0, n, p).astype(np.int32)
    keep = u != v
    u, v = u[keep], v[keep]
    truth = assign[u] == assign[v]
    lik = np.clip(rng.random(len(u)) * 0.5 + truth * 0.4, 0.0, 1.0)
    return PairSet(u=u, v=v, likelihood=lik.astype(np.float32),
                   truth=truth, n_objects=n)


def _join(args):
    """Durable join serving (DESIGN.md §16): fresh run with checkpoints —
    optionally killed after N commits — or `--resume` from the latest
    checkpoint in `--checkpoint-dir`.  Returns the results by request id,
    or None when killed."""
    from repro.core.crowd import NoisyCrowd
    from repro.serve.join_service import JoinService, ServiceKilled

    if args.resume:
        service = JoinService.restore(args.checkpoint_dir)
        info = service.last_recovery
        print(f"[serve] restored step {info['step']}: {info['n_lanes']} "
              f"lanes, {info['n_queued']} queued, {info['n_results']} "
              f"finished, {info['in_flight']} tickets in flight, "
              f"{info['spent_cents']:.1f} cents already committed")
    else:
        service = JoinService(lanes=args.lanes,
                              checkpoint_dir=args.checkpoint_dir,
                              checkpoint_every=args.checkpoint_every)
        for s in range(args.requests):
            service.submit(_join_workload(s), crowd=NoisyCrowd(seed=s))
        if args.kill_after:
            service._crash_after_checkpoints = args.kill_after
    try:
        results = service.run()
    except ServiceKilled as e:
        print(f"[serve] killed: {e}")
        print("[serve] re-run with --resume to recover")
        return None
    for rid in sorted(results):
        res = results[rid]
        f = (f", F={res.quality.f_measure:.3f}"
             if res.quality is not None else "")
        print(f"req {rid}: {len(res.labels)} pairs, "
              f"{res.n_crowdsourced} crowdsourced, "
              f"{res.n_spent_cents:.1f} cents{f}")
    print(f"[serve] {len(results)} join requests completed")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4)
    # recovery controls (DESIGN.md §16)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint serving state here")
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--kill-after", type=int, default=0,
                    help="die after N checkpoint commits")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --checkpoint-dir")
    args = ap.parse_args(argv)
    if (args.resume or args.kill_after) and not args.checkpoint_dir:
        ap.error("--resume/--kill-after require --checkpoint-dir")
    return _join(args)


if __name__ == "__main__":
    main()
