#!/usr/bin/env python3
"""Drive the join service's main path once on a TPU and check its answers.

    python chip_smoke.py [--seed N]      # one chip: phases A, B, C
    python chip_smoke.py --chips 4       # four chips: the sharded phase only

Phases, all data made from ``--seed`` at the full size of
``benchmarks/bench_blocking.py`` (16384 x 16384 rows, D = 16, 1,024
entities, tau = 0.9):

A. Dense machine phase: ``sharded_candidates`` on a 1x1 mesh, compared with
   the ``candidates_ref`` oracle on a 1,024-row sample.
B. Blocked machine phase: ``blocked_candidates`` at the benchmark's full
   config; blocked must be a subset of dense, with recall >= 0.95 on the
   same sample, and the kernel call must lower to a TPU custom call.
C. Human phase: one ``JoinService(lanes=4)`` serving the ``paper`` dataset
   under a perfect and a noisy crowd, the ``product`` dataset, and a blocked
   ``submit_embeddings`` session over 4,096 rows per side.  Perfect-crowd
   labels must equal the truth, the fused round engine must equal the
   legacy per-round path, and the integer-exact label digests must equal
   those recorded from a CPU run of the same seed.

Scores are compared with the oracle within ``BAND``: both sides compute the
same f32-precision dot products, but the tile shape sets the summation
order, so a score may move by a few ulps and a pair scored at the threshold
may fall on either side of it.

With ``--chips 4`` the script runs only the mesh-sharded dense machine
phase on a 2x2 (data, model) mesh and checks that its candidates are
identical to the same call on one device.

Any failed check raises, so the script exits non-zero; the last line of a
passing run is one JSON object naming the device.  It runs in one process
and refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

TAU = 0.9
BAND = 1e-5           # score agreement, and the membership band above tau
N_ROWS, N_ENTITIES, DIM, NOISE = 16384, 1024, 16, 0.12
SAMPLE = 1024         # oracle-checked rows
CAPACITY = 1 << 22    # candidate buffer (per device / per blocked call)
RECALL_FLOOR = 0.95
SERVICE_ROWS = 4096   # rows per side of the embedding session
# label digests of the PairSet-fed sessions, from a CPU run of each seed
# (tests/test_chip_smoke.py recomputes them on the CPU)
EXPECTED_DIGESTS = {
    0: {"paper/perfect": "98218338b70e2344", "paper/noisy": "836436976b37b34f",
        "product/perfect": "0a6b6f1a78728288"},
}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  ok: {what}")


class Phase:
    """Logs a phase's wall time, backend compiles and peak device memory —
    information only, never a metric (compiles and host work included)."""

    compiles = [0, 0.0]  # count, seconds, over the whole process

    @classmethod
    def listen(cls) -> None:
        import jax

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compiles[0] += 1
                cls.compiles[1] += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, title: str):
        self.title = title

    def __enter__(self):
        log(self.title)
        self.t0 = time.perf_counter()
        self.c0 = list(self.compiles)

    def __exit__(self, *exc):
        import jax

        if exc[0] is not None:
            return
        stats = jax.devices()[0].memory_stats() or {}
        log(f"  wall {time.perf_counter() - self.t0:.1f} s; "
            f"{self.compiles[0] - self.c0[0]} compiles "
            f"({self.compiles[1] - self.c0[1]:.1f} s); peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'n/a')}")


def blocking_config():
    """The full configuration of ``benchmarks/bench_blocking.py``."""
    from repro.kernels.pair_scores.blocking import BlockingConfig

    return BlockingConfig(n_bits=6, n_tables=8, bn=128, bm=128,
                          tiles_per_call=256, recall_floor=RECALL_FLOOR)


def make_corpus(seed: int, n_rows: int = N_ROWS):
    from benchmarks.bench_blocking import corpus

    return corpus(n_rows, N_ENTITIES, dim=DIM, noise=NOISE, seed=seed)


def check_against(got, ref, what: str, subset: bool = False) -> None:
    """Band comparison of two (rows, cols, scores) lists: scores agree
    within ``BAND``, and a pair in only one list scores within ``BAND``
    above tau.  With ``subset`` the pairs only in ``ref`` are not checked
    (blocking may miss pairs; it may not invent them)."""
    from repro.kernels.pair_scores.ref import candidate_diff

    dmax, extra, missing = candidate_diff(got, ref)
    log(f"  {what}: max|dscore|={dmax!r} membership diffs="
        f"{len(extra)} extra + {len(missing)} missing, band={BAND}")
    check(dmax <= BAND, f"{what}: scores agree within {BAND}")
    odd = extra if subset else np.concatenate([extra, missing])
    check(bool((odd < TAU + BAND).all()),
          f"{what}: every pair in one list only scores within {BAND} "
          f"above tau")


def lowers_to_kernel(fn, *args, **kwargs) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kwargs).as_text()


def phase_dense(a, b, sample):
    """A: the mesh-sharded dense machine phase against the oracle."""
    import jax

    from repro.kernels.pair_scores.blocking import _resolve_interpret
    from repro.kernels.pair_scores.ref import candidates_ref
    from repro.kernels.pair_scores.sharded import (_sharded_candidates_jit,
                                                   sharded_candidates)
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    spec = jax.ShapeDtypeStruct(a.shape, a.dtype)
    check(lowers_to_kernel(_sharded_candidates_jit, spec, spec,
                           threshold=TAU, capacity=CAPACITY, mesh=mesh,
                           interpret=_resolve_interpret("auto")),
          "sharded machine phase lowers to the Pallas TPU kernel")
    dense = sharded_candidates(a, b, TAU, mesh, capacity=CAPACITY,
                               normalize=False)
    log(f"  dense candidates: {len(dense)} of {a.shape[0] * b.shape[0]} "
        f"cells")
    check(dense.n_dropped == 0, "no candidate dropped")
    rr, rc, rs = candidates_ref(a[sample], b, TAU)
    keep = np.isin(dense.rows, sample)
    check_against((dense.rows[keep], dense.cols[keep], dense.scores[keep]),
                  (sample[rr], rc, rs), "dense vs oracle on the sample")
    return dense


def phase_blocked(a, b, sample, dense, cfg):
    """B: the blocked machine phase, a subset of dense at floor recall."""
    import jax

    from repro.kernels.pair_scores.blocking import (_resolve_interpret,
                                                    blocked_candidates,
                                                    blocker_recall)
    from repro.kernels.pair_scores.kernel import pair_scores_compact

    T = cfg.tiles_per_call
    f32 = jax.ShapeDtypeStruct((T * cfg.bn, a.shape[1]), a.dtype)
    ids = jax.ShapeDtypeStruct((T * cfg.bn, 1), np.int32)
    check(lowers_to_kernel(pair_scores_compact, f32, f32, ids, ids, TAU,
                           CAPACITY, cfg.bn, cfg.bm,
                           interpret=_resolve_interpret("auto")),
          "blocked machine phase lowers to the Pallas TPU kernel")
    blocked = blocked_candidates(a, b, TAU, cfg, capacity=CAPACITY,
                                 normalize=False)
    log(f"  blocked candidates: {len(blocked)}; cells scored "
        f"{blocked.cells_scored} of {blocked.dense_cells} "
        f"({blocked.n_tiles} tiles)")
    check(blocked.n_dropped == 0, "no candidate dropped")
    check_against((blocked.rows, blocked.cols, blocked.scores),
                  (dense.rows, dense.cols, dense.scores),
                  "blocked vs dense", subset=True)
    recall, n_dense = blocker_recall(blocked, a, b, TAU, row_sample=sample)
    log(f"  blocker recall {recall!r} over {n_dense} sampled dense pairs")
    check(recall >= RECALL_FLOOR, f"blocker recall >= {RECALL_FLOOR}")


def service_requests(seed: int):
    """The PairSet-fed sessions, with fresh crowds: (name, pairs, crowd,
    dataset-wide true matches)."""
    from repro.core import NoisyCrowd, PerfectCrowd
    from repro.data.entities import make_paper_dataset, make_product_dataset

    paper = make_paper_dataset(seed=seed)
    product = make_product_dataset(seed=seed + 1)
    pp = paper.pairs.above(0.3)
    return [
        ("paper/perfect", pp, PerfectCrowd(), paper.total_true_matches),
        ("paper/noisy", pp, NoisyCrowd(error_rate=0.1, seed=seed),
         paper.total_true_matches),
        ("product/perfect", product.pairs.above(0.3), PerfectCrowd(),
         product.total_true_matches),
    ]


def serve(seed: int, fused: bool, embeddings=None):
    """Serve the sessions through one ``JoinService(lanes=4)``; returns
    ``({name: result}, {name: pairs})``."""
    from repro.core import PerfectCrowd
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    svc = JoinService(lanes=4, fused_rounds=fused)
    rids, pairs = {}, {}
    for name, ps, crowd, true in service_requests(seed):
        rids[name] = svc.submit(ps, crowd, total_true_matches=true)
        pairs[name] = ps
    if embeddings is not None:
        ids_a, a, ids_b, b, cfg = embeddings
        rids["embeddings/perfect"] = svc.submit_embeddings(
            a, b, TAU, make_host_mesh(1, 1), crowd=PerfectCrowd(),
            truth_fn=lambda r, c: ids_a[r] == ids_b[c],
            capacity=CAPACITY, blocking=cfg)
    res = svc.run()
    return {name: res[rid] for name, rid in rids.items()}, pairs


def digest(res) -> str:
    h = hashlib.sha256(np.asarray(res.labels, np.uint8).tobytes())
    h.update(np.asarray(res.crowdsourced, np.uint8).tobytes())
    return h.hexdigest()[:16]


def phase_service(seed: int, embeddings):
    """C: the human phase through the service's normal entry points."""
    with Phase(" C.1: fused round engine"):
        fused, pairs = serve(seed, True, embeddings)
    with Phase(" C.2: legacy per-round path (fused_rounds=False)"):
        legacy, _ = serve(seed, False, embeddings)
    for name, r in fused.items():
        log(f"  {name}: {len(r.labels)} pairs, {r.n_crowdsourced} "
            f"crowdsourced in {r.n_rounds} rounds, "
            f"{r.n_spent_cents!r} cents, {r.quality.row()}")
    for name in ("paper/perfect", "product/perfect"):
        check(np.array_equal(fused[name].labels, pairs[name].truth),
              f"{name}: labels equal the truth")
    q = fused["embeddings/perfect"].quality
    check(q.fp == 0 and q.fn == 0,
          "embeddings/perfect: labels equal the truth on its candidates")
    for name, r in fused.items():
        lg = legacy[name]
        check(np.array_equal(r.labels, lg.labels)
              and r.round_sizes == lg.round_sizes
              and r.n_spent_cents == lg.n_spent_cents,
              f"{name}: fused engine equals the legacy path")
    return {name: digest(fused[name]) for name in pairs}


def four_chips(seed: int) -> None:
    """The mesh-sharded dense machine phase on a 2x2 mesh against the same
    call on one device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.pair_scores.sharded import sharded_candidates
    from repro.launch.mesh import make_host_mesh

    check(len(jax.devices()) >= 4, "four devices present")
    _, a, _, b = make_corpus(seed)
    mesh = make_host_mesh(2, 2)
    a4 = jax.device_put(a, NamedSharding(mesh, P("data", None)))
    b4 = jax.device_put(b, NamedSharding(mesh, P("model", None)))
    held = {s.device for x in (a4, b4) for s in x.addressable_shards}
    check(held == set(jax.devices()[:4]), "each of four devices holds a "
          "shard of the corpus")
    with Phase(" 4x.1: 2x2 mesh"):
        c4 = sharded_candidates(a4, b4, TAU, mesh, capacity=CAPACITY,
                                normalize=False)
    with Phase(" 4x.2: one device"):
        c1 = sharded_candidates(a, b, TAU, make_host_mesh(1, 1),
                                capacity=CAPACITY, normalize=False)
    log(f"  candidates: {len(c4)} on 2x2, {len(c1)} on one device")
    check(c4.n_dropped == 0 and c1.n_dropped == 0, "no candidate dropped")
    o4 = np.lexsort((c4.cols, c4.rows))
    o1 = np.lexsort((c1.cols, c1.rows))
    check(all(np.array_equal(x[o4], y[o1]) for x, y in
              ((c4.rows, c1.rows), (c4.cols, c1.cols),
               (c4.scores, c1.scores))),
          "2x2 and one-device (row, col, score) sets are identical")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if args.chips == 1 and args.seed not in EXPECTED_DIGESTS:
        sys.exit(f"chip_smoke: no CPU label digests recorded for seed "
                 f"{args.seed}; record them in EXPECTED_DIGESTS first")
    from repro.launch.compile_cache import use_compile_cache

    log(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{use_compile_cache(ROOT)}")
    # int64 is emulated on a TPU: pair keys stay int32, one word up to
    # 46,340 objects and two words past it
    check(not jax.config.jax_enable_x64, "x64 off: int32 pair keys")
    Phase.listen()
    if args.chips == 4:
        with Phase("phase 4x: sharded dense machine phase on a 2x2 mesh"):
            four_chips(args.seed)
    else:
        ids_a, a, ids_b, b = make_corpus(args.seed)
        rng = np.random.default_rng(args.seed + 1)
        sample = np.sort(rng.choice(N_ROWS, size=SAMPLE, replace=False))
        cfg = blocking_config()
        with Phase("phase A: dense machine phase"):
            dense = phase_dense(a, b, sample)
        with Phase("phase B: blocked machine phase"):
            phase_blocked(a, b, sample, dense, cfg)
        with Phase("phase C: human phase through JoinService"):
            k = SERVICE_ROWS
            digests = phase_service(
                args.seed, (ids_a[:k], a[:k], ids_b[:k], b[:k], cfg))
            for name, d in digests.items():
                log(f"  digest {name}: {d}")
            check(digests == EXPECTED_DIGESTS[args.seed],
                  "label digests equal the CPU run's")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
