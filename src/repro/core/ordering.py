"""Adaptive labeling order (DESIGN.md §10) — posterior-refreshed priorities.

The paper's practical heuristic (§4.2) sorts candidate pairs by machine
likelihood **once** and never revisits that order, yet every crowd answer
changes the expected-deduction value of the remaining pairs.  *The Expected
Optimal Labeling Order Problem for Crowdsourced Joins and Entity Resolution*
(Wang et al., 2014) formalizes the gap: orders that track the live cluster
structure dominate static likelihood sorting, because labeling a pair that
merges two large components deduces every cross pair between them for free
(the component-growth argument behind Theorem 1's matching-first optimality).

This module turns :class:`~repro.core.jax_graph.SessionState.priority` into
that live quantity.  Per pending pair ``(u, v)`` with machine prior ``p``:

* ``du``/``dv`` — live negative degrees of the two clusters: the number of
  *distinct* clusters each is negatively adjacent to, counted from the
  union-find ``roots`` and the sorted ``neg_keys`` index (duplicate keys —
  deduced NEGs — count once, so the host oracle's ``ClusterGraph.neg``
  sets agree exactly);
* **posterior / gain** ``p / (1 + NEG_DAMP * (du + dv))`` — the prior
  damped by the accumulated negative evidence around the pair's clusters:
  a cluster the crowd keeps separating from its neighbours is a
  well-delineated entity, so an unlabeled edge into it is less likely to
  match than the machine score alone suggests.

Ranking by this posterior is the component-growth argument in heuristic
form: Theorem 1 says *matching pairs first* is optimal (each match grows a
component, compounding future deductions), and the §4.2 likelihood sort is
its deployable surrogate; the live posterior is a strictly better match-
probability estimate than the frozen prior, so ranking on it moves the
order closer to true matching-first as evidence accumulates.  Explicit
structure bonuses were measured and *hurt*: boosting by cluster size or by
cluster-pair candidate multiplicity promotes probable non-matches ahead of
probable matches, which breaks exactly the property Theorem 1 needs
(on the Cora-like benchmark: posterior 1571 crowdsourced pairs vs 1611
static expected vs 1523 ground-truth optimal; size/multiplicity variants
1738-2518).

``priority = -gain`` (the frontier selects minimum priority), refreshed only
on *pending* pairs (UNKNOWN and not in flight): published and labeled pairs
keep their old priority, and since the frontier never selects either, a
refresh can never revive them (property-tested).  The formula is pure f32
mul/add/div — no transcendentals — so the device (XLA) and host (NumPy)
paths produce bit-identical scores and therefore identical rankings.

With no negative evidence yet (round 1) the gain reduces to the clipped
prior, so adaptive ordering starts as the §4.2 likelihood-descending
heuristic and diverges only once structure accumulates.

The ordering also steers mixed scheduling (DESIGN.md §15): the cluster-task
planner grows its multi-pair tasks around the objects of the
frontier-selected pairs and values a candidate task only by the *frontier*
pairs it covers — harvested off-frontier pairs ride along at zero credited
value, since deduction would have labeled most of them for free.  A better
frontier therefore concentrates cluster tasks where the next round's
information actually is.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .cluster_graph import ClusterGraph, UNKNOWN
from .jax_graph import (SessionState, _decompose_keys, _key_starts,
                        engine_dispatches, engine_jit)

# Damping per unit of negative degree around the pair's clusters.  0.25 is a
# power of two, so `1 + NEG_DAMP * k` is exact in f32 and the host/device
# score parity stays bitwise.
NEG_DAMP = 0.25

# Priors are clipped away from {0, 1}: a 0-likelihood pair still in the
# candidate set must keep a total order under the stable rank tie-break.
PRIOR_FLOOR = 1e-4


# ---------------------------------------------------------------------------
# Device path (jit / vmap over SessionState)
# ---------------------------------------------------------------------------
def _neg_degree_impl(state: SessionState) -> jax.Array:
    """Distinct negative degree per root, f32 (n,)."""
    n = state.n_objects
    lo, hi, is_pad = _decompose_keys(state.neg_keys, n)
    # neg_keys is sorted, so duplicates (deduced NEGs) are adjacent: count
    # each distinct cluster-pair key once, matching ClusterGraph.neg sets
    first = _key_starts(state.neg_keys)
    w = jnp.where(is_pad | ~first, 0.0, 1.0).astype(jnp.float32)
    return jnp.zeros((n,), jnp.float32).at[lo].add(w).at[hi].add(w)


def _gains_impl(state: SessionState, prior: jax.Array) -> jax.Array:
    """Posterior match probability / expected-deduction gain per pair
    (f32 (P,)); meaningful on pending pairs, computed everywhere (callers
    mask)."""
    negdeg = _neg_degree_impl(state)
    ru, rv = state.roots[state.u], state.roots[state.v]
    p = jnp.clip(prior.astype(jnp.float32), PRIOR_FLOOR, 1.0 - PRIOR_FLOOR)
    damp = 1.0 + NEG_DAMP * (negdeg[ru] + negdeg[rv])
    return p / damp


def _refresh_impl(state: SessionState, prior: jax.Array) -> SessionState:
    """Fold refreshed priorities into the state: pending pairs get
    ``-gain`` (highest gain labels first), published/labeled pairs keep
    their old priority — they are out of the frontier's reach either way,
    so a refresh can never revive them."""
    gain = _gains_impl(state, prior)
    pending = (state.labels == UNKNOWN) & ~state.published
    prio = jnp.where(pending, -gain, state.priority)
    return dataclasses.replace(state, priority=prio)


def _refresh_masked_impl(state: SessionState, prior: jax.Array,
                         enable: jax.Array) -> SessionState:
    """Batched helper: refresh only where the per-session ``enable`` scalar
    holds (lanes serving a static order keep positional priorities)."""
    refreshed = _refresh_impl(state, prior)
    prio = jnp.where(enable, refreshed.priority, state.priority)
    return dataclasses.replace(state, priority=prio)


_session_gains_jit = engine_jit("gains", _gains_impl)
_session_gains_batch_jit = engine_jit("gains_batch", jax.vmap(_gains_impl))
# refresh is state-in/state-out: donate the state so the priority write is
# in place and the untouched fields alias straight through (DESIGN.md §13)
_session_refresh_jit = engine_jit("refresh", _refresh_impl,
                                  donate_argnums=(0,))
_session_refresh_batch_jit = engine_jit("refresh_batch",
                                        jax.vmap(_refresh_masked_impl),
                                        donate_argnums=(0,))


def session_gains(state: SessionState, prior) -> jax.Array:
    """(P,) f32 expected-deduction gains from the live state (one dispatch).
    The budget scheduler ranks crowd slots across sessions on these."""
    engine_dispatches.add()
    return _session_gains_jit(state, prior)


def session_gains_batch(state: SessionState, prior) -> jax.Array:
    """(B, P) stacked gains, one dispatch for B sessions."""
    engine_dispatches.add()
    return _session_gains_batch_jit(state, prior)


def session_refresh_priorities(state: SessionState, prior) -> SessionState:
    """Refresh pending-pair priorities from the live posterior (DESIGN.md
    §10); published/labeled pairs are untouched.  One dispatch."""
    engine_dispatches.add()
    return _session_refresh_jit(state, prior)


def session_refresh_priorities_batch(state: SessionState, prior,
                                     enable) -> SessionState:
    """Batched refresh over stacked states; ``enable`` is a (B,) bool mask
    of sessions whose order is adaptive (the rest keep their priorities)."""
    engine_dispatches.add()
    return _session_refresh_batch_jit(state, prior, jnp.asarray(enable))


# ---------------------------------------------------------------------------
# Host oracle (ClusterGraph): the same scores from the pointer-chasing graph
# ---------------------------------------------------------------------------
def adaptive_gains_host(graph: ClusterGraph, u: np.ndarray, v: np.ndarray,
                        likelihood: np.ndarray) -> np.ndarray:
    """Expected-deduction gains from a live :class:`ClusterGraph` — the host
    mirror of :func:`session_gains`, op-for-op in f32 so rankings agree with
    the device path bit-for-bit.  O(n + P) per call: roots materialize once,
    the per-pair math is vectorized."""
    n = len(graph.parent)
    roots_all = np.fromiter((graph.find(i) for i in range(n)), np.int64, n)
    negdeg = np.zeros(n, np.float32)
    for r, enemies in graph.neg.items():
        negdeg[r] = len(enemies)  # keys are live roots (maintained on union)
    ru = roots_all[np.asarray(u, np.int64)]
    rv = roots_all[np.asarray(v, np.int64)]
    p = np.clip(np.asarray(likelihood, np.float32),
                np.float32(PRIOR_FLOOR), np.float32(1.0 - PRIOR_FLOOR))
    damp = np.float32(1.0) + np.float32(NEG_DAMP) * (negdeg[ru] + negdeg[rv])
    return p / damp


def expected_rank(likelihood: np.ndarray) -> np.ndarray:
    """Each pair's position in the static expected (likelihood-descending)
    order — the tie-break key of the adaptive ranking, mirroring the
    engine's stable rank tie-break over pairs stored in expected order."""
    n = len(likelihood)
    rank = np.empty(n, np.int64)
    rank[np.argsort(-np.asarray(likelihood), kind="stable")] = np.arange(n)
    return rank


def adaptive_order_host(gains: np.ndarray, erank: np.ndarray,
                        idx: np.ndarray) -> np.ndarray:
    """Order the pair indices ``idx`` by descending live gain, ties broken
    by the static expected rank — the one ranking both host adaptive
    labelers share (keeping them in lockstep with each other and with the
    engine's tie-break)."""
    return idx[np.lexsort((erank[idx], -gains[idx]))]
