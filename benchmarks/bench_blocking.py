"""Blocked + fused candidate generation at scale (DESIGN.md §12).

The headline demonstration for the blocking stage: a candidate workload in
the ~10M-cell class runs end-to-end through LSH bucketing + the fused
similarity/threshold/compaction kernel, while the dense path at the same
corpus size is infeasible on one device — the full 16384 x 16384 grid is
268M cells whose score matrix alone is a 1 GiB f32 transient (plus an
argsort over it for compaction), where the blocked path's working set is
the candidate buffer and one (tiles_per_call x bn x bm) chunk.

Reported per run:

* candidate cells/s through the blocked+fused path and the cell counts
  (genuine cells scored vs the dense grid — the CI smoke asserts blocked
  strictly fewer);
* measured blocker recall vs the dense oracle on a densely-checkable
  a-row subsample, against the configured floor (>= 0.95);
* tiny mode only: exact subset + bitwise score parity vs the full dense
  oracle, and a blocked JoinService join (machine -> crowd -> deduce) with
  crowd cents per resolved pair.

Set ``BENCH_JOIN_TINY=1`` for the seconds-scale CI configuration; the full
configuration holds the >= 10M-cell bar.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import row

RECALL_FLOOR = 0.95


def _tiny() -> bool:
    return os.environ.get("BENCH_JOIN_TINY", "") not in ("", "0")


def corpus(n_rows: int, n_entities: int, dim: int, noise: float, seed: int):
    """Entity-clustered normalized embeddings: within-entity cosine is high
    (real candidate structure at tau), cross-entity is near zero."""
    import jax.numpy as jnp

    from repro.kernels.pair_scores.ops import l2_normalize

    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_entities, dim))
    ids_a = rng.integers(0, n_entities, n_rows)
    ids_b = rng.integers(0, n_entities, n_rows)
    mk = lambda ids: (cents[ids] + noise * rng.normal(size=(n_rows, dim))
                      ).astype(np.float32)
    a = np.asarray(l2_normalize(jnp.asarray(mk(ids_a))))
    b = np.asarray(l2_normalize(jnp.asarray(mk(ids_b))))
    return ids_a, a, ids_b, b


def _bench_blocked_path(out: list, payload: dict):
    from repro.kernels.pair_scores.blocking import (BlockingConfig,
                                                    blocked_candidates,
                                                    blocker_recall)

    if _tiny():
        n_rows, n_entities, tau = 1024, 512, 0.9
        cfg = BlockingConfig.for_recall(RECALL_FLOOR, tau, n_bits=6,
                                        bn=64, bm=64, tiles_per_call=64)
        capacity = 1 << 16
        sample = 256
    else:
        n_rows, n_entities, tau = 16384, 1024, 0.9
        cfg = BlockingConfig(n_bits=6, n_tables=8, bn=128, bm=128,
                             tiles_per_call=256, recall_floor=RECALL_FLOOR)
        capacity = 1 << 22
        sample = 1024
    ids_a, a, ids_b, b = corpus(n_rows, n_entities, dim=16, noise=0.12,
                                seed=0)
    # compile the kernel on a sliver so the timed run measures execution
    blocked_candidates(a[:2 * cfg.bn], b[:2 * cfg.bm], tau, cfg,
                       capacity=256, normalize=False)
    t0 = time.perf_counter()
    cand = blocked_candidates(a, b, tau, cfg, capacity=capacity,
                              normalize=False)
    secs = time.perf_counter() - t0
    assert cand.n_dropped == 0, (
        f"bench capacity underprovisioned: {cand.n_dropped} dropped — "
        f"re-run with capacity={cand.suggested_capacity}")
    cells_per_s = cand.cells_scored / secs
    rng = np.random.default_rng(1)
    rows = np.sort(rng.choice(n_rows, size=sample, replace=False))
    recall, n_dense_sample = blocker_recall(cand, a, b, tau, row_sample=rows)
    payload["blocked"] = {
        "n": n_rows, "m": n_rows, "d": 16, "threshold": tau,
        "n_bits": cfg.n_bits, "n_tables": cfg.n_tables,
        "bn": cfg.bn, "bm": cfg.bm,
        "cells_scored": cand.cells_scored,
        "padded_cells": cand.padded_cells,
        "dense_cells": cand.dense_cells,
        "n_tiles": cand.n_tiles,
        "n_candidates": len(cand),
        "n_duplicates": cand.n_duplicates,
        "cells_saved_frac": cand.cells_saved_frac,
        "secs": secs,
        "candidate_cells_per_s": cells_per_s,
        "blocked_lt_dense": cand.cells_scored < cand.dense_cells,
    }
    payload["recall"] = {
        "floor": RECALL_FLOOR,
        "sample_rows": sample,
        "n_dense_in_sample": n_dense_sample,
        "recall": recall,
        "recall_ok": recall >= RECALL_FLOOR,
    }
    out.append(row(
        f"blocking/blocked_{n_rows}x{n_rows}", secs * 1e6,
        f"cells={cand.cells_scored:.3e} dense={cand.dense_cells:.3e} "
        f"cells_per_s={cells_per_s:.3e} cands={len(cand)} "
        f"recall={recall:.3f}"))
    return ids_a, a, ids_b, b, tau, cfg


def _bench_dense_parity(out: list, payload: dict, a, b, tau, cfg):
    """Tiny mode only: the corpus is small enough to score densely, so the
    full parity contract (subset + bitwise) is checked outright."""
    import jax.numpy as jnp

    from repro.kernels.pair_scores.blocking import blocked_candidates
    from repro.kernels.pair_scores.ref import candidates_ref

    cand = blocked_candidates(a, b, tau, cfg, normalize=False)
    rr, rc, rs = candidates_ref(jnp.asarray(a), jnp.asarray(b), tau)
    dense = set(zip(rr.tolist(), rc.tolist()))
    blocked = set(zip(cand.rows.tolist(), cand.cols.tolist()))
    ref_score = {(r, c): s for r, c, s in
                 zip(rr.tolist(), rc.tolist(), rs.tolist())}
    subset_ok = blocked <= dense
    bitwise_ok = all(
        np.float32(ref_score[(r, c)]) == np.float32(s)
        for r, c, s in zip(cand.rows.tolist(), cand.cols.tolist(),
                           cand.scores.tolist()))
    payload["parity"] = {
        "n_dense": len(dense), "n_blocked": len(blocked),
        "subset_ok": subset_ok, "bitwise_ok": bitwise_ok,
    }
    out.append(row(
        "blocking/dense_parity", 0.0,
        f"subset={subset_ok} bitwise={bitwise_ok} "
        f"blocked={len(blocked)} dense={len(dense)}"))


def _bench_service(out: list, payload: dict, ids_a, a, ids_b, b, tau, cfg):
    """Blocked machine phase feeding the full crowd/deduce loop, with the
    paper's money metric: crowd cents per resolved pair."""
    import jax.numpy as jnp

    from repro.core import PerfectCrowd
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    k = 256 if _tiny() else 512
    sa, sb = a[:k], b[:k]
    truth_fn = lambda r, c: np.asarray(ids_a[np.asarray(r)]
                                       == ids_b[np.asarray(c)])
    svc = JoinService(lanes=1)
    t0 = time.perf_counter()
    rid = svc.submit_embeddings(jnp.asarray(sa), jnp.asarray(sb), tau,
                                make_host_mesh(1, 1), crowd=PerfectCrowd(),
                                truth_fn=truth_fn, blocking=cfg)
    res = svc.run()[rid]
    secs = time.perf_counter() - t0
    n_pairs = len(res.labels)
    payload["service"] = {
        "rows_per_side": k,
        "pairs": n_pairs,
        "crowdsourced": res.n_crowdsourced,
        "saved_frac": 1.0 - res.n_crowdsourced / max(n_pairs, 1),
        "cost_cents": res.cost_cents,
        "cents_per_resolved_pair": res.cost_cents / max(n_pairs, 1),
        "precision": res.quality.precision if res.quality else None,
        "secs": secs,
    }
    out.append(row(
        f"blocking/service_{k}x{k}", secs * 1e6,
        f"pairs={n_pairs} crowdsourced={res.n_crowdsourced} "
        f"cents_per_pair={res.cost_cents / max(n_pairs, 1):.2f} "
        f"precision={payload['service']['precision']}"))


def run() -> list:
    out: list = []
    payload: dict = {"tiny": _tiny()}
    ids_a, a, ids_b, b, tau, cfg = _bench_blocked_path(out, payload)
    if _tiny():
        _bench_dense_parity(out, payload, a, b, tau, cfg)
    _bench_service(out, payload, ids_a, a, ids_b, b, tau, cfg)
    out.append("# JSON " + json.dumps({"bench_blocking": payload}))
    return out
