"""Sorting component (§3.1, §4) — labeling orders.

* ``order_optimal``  — Theorem 1: all matching pairs first (needs ground truth;
  usable only in simulation, exactly as the paper's "Optimal Order").
* ``order_expected`` — the practical heuristic (§4.2): descending likelihood.
* ``order_random``   — seeded shuffle.
* ``order_worst``    — all non-matching pairs first (paper's "Worst Order").
* ``order_adaptive`` — the initial permutation of the posterior-refreshed
  adaptive order (DESIGN.md §10; the live re-ranking is ``core/ordering.py``).

Plus the *exact* expected-crowdsourced-pairs enumerator of §4.2 / Example 4
(exponential; for tiny instances + tests only): all 2^n labelings are filtered
to transitively-consistent worlds, prior probabilities renormalized over those
worlds, and the sequential labeler counted per world.
"""
from __future__ import annotations

import itertools
from typing import Sequence

import numpy as np

from .cluster_graph import ClusterGraph, MATCH, NON_MATCH
from .pairs import PairSet


# --------------------------------------------------------------------------
# Orders: each returns an index permutation into the PairSet.
# --------------------------------------------------------------------------
def order_expected(pairs: PairSet) -> np.ndarray:
    # stable descending-likelihood (ties broken by index, matching the paper's
    # running example p_1..p_8 numbering)
    return np.argsort(-pairs.likelihood, kind="stable")


def order_optimal(pairs: PairSet) -> np.ndarray:
    # ValueError (not assert) so the guard survives ``python -O``
    if pairs.truth is None:
        raise ValueError(
            "optimal order needs ground truth: it sorts matching pairs "
            "first (Theorem 1), which only a simulation can know")
    lik = pairs.likelihood
    # matching first; within each group keep descending likelihood (any
    # within-group order is equivalent by Lemma 3)
    key = np.where(pairs.truth, 1.0, 0.0) * 10.0 + lik
    return np.argsort(-key, kind="stable")


def order_worst(pairs: PairSet) -> np.ndarray:
    if pairs.truth is None:
        raise ValueError(
            "worst order needs ground truth: it sorts non-matching pairs "
            "first, which only a simulation can know")
    lik = pairs.likelihood
    key = np.where(pairs.truth, 0.0, 1.0) * 10.0 + lik
    return np.argsort(-key, kind="stable")


def order_random(pairs: PairSet, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.permutation(len(pairs))


def order_adaptive(pairs: PairSet) -> np.ndarray:
    """Initial permutation of the *adaptive* order (DESIGN.md §10): before
    any label lands, every cluster is a singleton, so the live
    expected-deduction gain reduces to the clipped likelihood and the
    adaptive order coincides with the §4.2 heuristic.  The adaptivity — the
    posterior-refreshed re-ranking between rounds — lives in
    ``core/ordering.py`` and runs inside the labelers/serving layer."""
    return order_expected(pairs)


ORDERS = {
    "optimal": order_optimal,
    "expected": order_expected,
    "worst": order_worst,
    "adaptive": order_adaptive,
}


def validate_order(name: str) -> str:
    """Raise a ValueError listing the valid order names for anything
    unknown; returns the name unchanged otherwise (single home for the
    check — the serving layer validates at submit time with it)."""
    if name != "random" and name not in ORDERS:
        raise ValueError(
            f"unknown labeling order {name!r}: valid orders are "
            f"{sorted([*ORDERS, 'random'])}")
    return name


def get_order(pairs: PairSet, name: str, seed: int = 0) -> np.ndarray:
    validate_order(name)
    if name == "random":
        return order_random(pairs, seed)
    return ORDERS[name](pairs)


# --------------------------------------------------------------------------
# Exact E[C(w)] of §4.2 (Example 4) — tiny instances only.
# --------------------------------------------------------------------------
def _consistent(n_objects: int, u, v, labels: Sequence[bool]) -> bool:
    """A labeling is realizable by some entity partition iff no non-matching
    pair joins two objects connected by matching pairs."""
    g = ClusterGraph(n_objects)
    for i, m in enumerate(labels):
        if m:
            g._union(g.find(int(u[i])), g.find(int(v[i])))
    for i, m in enumerate(labels):
        if not m and g.connected(int(u[i]), int(v[i])):
            return False
    return True


def count_crowdsourced(pairs: PairSet, order: np.ndarray,
                       labels: Sequence[bool]) -> int:
    """Sequential labeler (§3.2) crowdsourced-pair count for a known world."""
    g = ClusterGraph(pairs.n_objects)
    n = 0
    for i in order:
        o, o2 = int(pairs.u[i]), int(pairs.v[i])
        if g.deduce(o, o2) is None:
            n += 1
            g.add_label(o, o2, MATCH if labels[i] else NON_MATCH)
        # deduced pairs add no information to the ClusterGraph
    return n


def expected_crowdsourced(pairs: PairSet, order: np.ndarray) -> float:
    """E[C(w)] under the per-pair matching probabilities, conditioned on
    transitive consistency (exactly the §4.2 / Example 4 computation)."""
    n = len(pairs)
    assert n <= 16, "exact enumeration is exponential; tiny instances only"
    p = pairs.likelihood.astype(np.float64)
    total_prob = 0.0
    exp_count = 0.0
    counts = []
    for world in itertools.product([True, False], repeat=n):
        if not _consistent(pairs.n_objects, pairs.u, pairs.v, world):
            continue
        prob = 1.0
        for i in range(n):
            prob *= p[i] if world[i] else (1.0 - p[i])
        c = count_crowdsourced(pairs, order, world)
        counts.append(c)
        total_prob += prob
        exp_count += prob * c
    # a weighted mean lies within its values; keep float rounding of the
    # two sums from pushing it out (a star crowdsources every pair in
    # every world, so E[C] is exactly its pair count)
    return min(max(exp_count / total_prob, min(counts)), max(counts))
