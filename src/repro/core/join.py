"""End-to-end crowdsourced join operator.

Composes the full hybrid human-machine pipeline of the paper: candidate pairs
(from the machine phase — the pair-scores kernel or a generative sim) →
sorting component → labeling component (sequential / parallel / JAX engine)
→ join result + quality + cost accounting.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from .cluster_graph import ClusterGraph, MATCH
from .crowd import CostModel, Crowd, PerfectCrowd
from .jax_graph import NEG, POS, label_parallel_jax
from .labeling import (LabelingResult, label_all_crowdsourced,
                       label_sequential, label_sequential_adaptive)
from .metrics import Quality, quality
from .pairs import PairSet
from .parallel import label_parallel, label_parallel_adaptive
from .sorting import get_order


@dataclasses.dataclass
class JoinResult:
    labels: np.ndarray           # (P,) bool over candidate pairs
    n_crowdsourced: int
    n_deduced: int
    n_iterations: int
    batch_sizes: list
    n_hits: int
    cost_cents: float
    quality: Optional[Quality]
    wall_seconds: float
    clusters: Optional[dict] = None
    n_conflicts: int = 0           # contradictory crowd answers dropped


def crowdsourced_join(
    candidates: PairSet,
    crowd: Optional[Crowd] = None,
    order: str = "expected",
    labeler: str = "parallel",       # sequential | parallel | jax | all
    cost: Optional[CostModel] = None,
    total_true_matches: Optional[int] = None,
    seed: int = 0,
) -> JoinResult:
    crowd = crowd or PerfectCrowd()
    cost = cost or CostModel()
    t0 = time.perf_counter()
    perm = get_order(candidates, order, seed=seed)
    adaptive = order == "adaptive"  # live re-ranking (DESIGN.md §10)

    if labeler == "sequential":
        res = (label_sequential_adaptive(candidates, crowd) if adaptive
               else label_sequential(candidates, perm, crowd))
    elif labeler == "parallel":
        res = (label_parallel_adaptive(candidates, crowd) if adaptive
               else label_parallel(candidates, perm, crowd))
    elif labeler == "all":
        res = label_all_crowdsourced(candidates, crowd)
    elif labeler == "jax":
        ordered = candidates.take(perm)

        def crowd_fn(idx):
            return np.array(
                [POS if crowd.ask(ordered, int(i)) == MATCH else NEG for i in idx],
                dtype=np.int32,
            )

        labels_j, crowdsourced_j, rounds, n_conf = label_parallel_jax(
            ordered.u, ordered.v, ordered.n_objects, crowd_fn,
            prior=ordered.likelihood if adaptive else None,
        )
        # map back to original indexing
        labels = np.zeros(len(candidates), dtype=bool)
        crowdsourced = np.zeros(len(candidates), dtype=bool)
        labels[perm] = labels_j == POS
        crowdsourced[perm] = crowdsourced_j
        res = LabelingResult(labels, crowdsourced, len(rounds), rounds,
                             n_conflicts=n_conf)
    else:
        raise ValueError(labeler)

    wall = time.perf_counter() - t0
    q = None
    if candidates.truth is not None:
        ttm = total_true_matches
        if ttm is None:
            ttm = int(candidates.truth.sum())
        q = quality(candidates, res.labels, ttm)

    # final entity clusters from the matching labels
    g = ClusterGraph(candidates.n_objects)
    for i in np.nonzero(res.labels)[0]:
        g.add_label(int(candidates.u[i]), int(candidates.v[i]), MATCH)

    return JoinResult(
        n_conflicts=res.n_conflicts,
        labels=res.labels,
        n_crowdsourced=res.n_crowdsourced,
        n_deduced=res.n_deduced,
        n_iterations=res.n_iterations,
        batch_sizes=res.batch_sizes,
        n_hits=cost.n_hits(res.n_crowdsourced),
        cost_cents=cost.cost_cents(res.n_crowdsourced),
        quality=q,
        wall_seconds=wall,
        clusters=None,
    )
