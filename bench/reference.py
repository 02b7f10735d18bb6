"""Plain references the benchmark compares the served results with.

Nothing here imports the program under test.  Both references are
straightforward host code over numpy:

* :func:`label_rounds` — the human phase: round-barrier parallel labeling
  (the source paper's Algorithm 2) with the selection the served engine
  states (parallel Algorithm 3 as priority-Boruvka rounds, negative edges
  judged against the current components), a sequential answer fold with
  the "drop" conflict policy, and a deduction sweep (Algorithm 1) after
  every fold.  Pairs carry their priority as their position in the
  labeling order.
* :func:`dense_candidates` — the machine phase: every (a-row, b-row) pair
  whose cosine similarity reaches the threshold, scored in float64 (a
  float32 pass of plain ``jnp.dot`` shortlists the pairs near or above
  it).

Each also has a *control*: the same reference with one guarantee broken,
which the comparison must refuse (see ``bench/check.py``)."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

UNKNOWN, NEG, POS = -1, 0, 1


class UnionFind:
    """Union by size with path compression over object ids."""

    def __init__(self, n: int):
        self.parent = list(range(n))
        self.size = [1] * n

    def find(self, x: int) -> int:
        p = self.parent
        root = x
        while p[root] != root:
            root = p[root]
        while p[x] != root:
            p[x], x = root, p[x]
        return root

    def union(self, a: int, b: int) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return
        if self.size[ra] < self.size[rb]:
            ra, rb = rb, ra
        self.parent[rb] = ra
        self.size[ra] += self.size[rb]

    def roots(self) -> np.ndarray:
        return np.fromiter((self.find(x) for x in range(len(self.parent))),
                           np.int64, len(self.parent))


def _keys(ra: np.ndarray, rb: np.ndarray, n: int) -> np.ndarray:
    lo, hi = np.minimum(ra, rb), np.maximum(ra, rb)
    return lo.astype(np.int64) * n + hi


class _Graph:
    """Match clusters and cluster-level non-match edges of the labels so
    far (the source paper's ClusterGraph): ``neg`` keeps every non-match
    label as given, ``adj`` each cluster root's set of non-matching
    cluster roots."""

    def __init__(self, n: int):
        self.n = n
        self.uf = UnionFind(n)
        self.neg: List[Tuple[int, int]] = []
        self.adj: Dict[int, set] = {}

    def deduce(self, a: int, b: int) -> int:
        ra, rb = self.uf.find(a), self.uf.find(b)
        if ra == rb:
            return POS
        return NEG if rb in self.adj.get(ra, ()) else UNKNOWN

    def add_match(self, a: int, b: int) -> None:
        ra, rb = self.uf.find(a), self.uf.find(b)
        self.uf.union(a, b)
        keep = self.uf.find(a)
        gone = rb if keep == ra else ra
        if ra == rb or gone not in self.adj:
            return
        mine = self.adj.setdefault(keep, set())
        mine.discard(gone)
        for x in self.adj.pop(gone):
            if x in (gone, keep):  # a non-match inside the merged cluster
                mine.add(keep)
                continue
            self.adj[x].discard(gone)
            self.adj[x].add(keep)
            mine.add(x)

    def add_non_match(self, a: int, b: int) -> None:
        self.neg.append((a, b))
        ra, rb = self.uf.find(a), self.uf.find(b)
        self.adj.setdefault(ra, set()).add(rb)
        self.adj.setdefault(rb, set()).add(ra)

    def neg_keys(self, roots: np.ndarray) -> np.ndarray:
        if not self.neg:
            return np.zeros(0, np.int64)
        e = np.asarray(self.neg, np.int64)
        return np.unique(_keys(roots[e[:, 0]], roots[e[:, 1]], self.n))


def _frontier(u, v, n, graph: _Graph, undecided: np.ndarray,
              positive_only: bool) -> np.ndarray:
    """Pairs one round publishes: with every undecided pair assumed to
    match, each component's lowest-priority incident pair that joins two
    components not separated by a non-match edge, repeated over the merged
    components until none is left."""
    P = len(u)
    prio = np.arange(P)
    roots = graph.uf.roots()
    neg = np.asarray(graph.neg, np.int64).reshape(-1, 2)
    na, nb = roots[neg[:, 0]], roots[neg[:, 1]]
    merged = UnionFind(n)
    frontier = np.zeros(P, bool)
    undecided = undecided.copy()
    while True:
        ru, rv = roots[u], roots[v]
        cand = undecided & (ru != rv)
        if not positive_only and len(na):
            cand &= ~np.isin(_keys(ru, rv, n), _keys(na, nb, n))
        undecided &= cand
        if not cand.any():
            return frontier
        p = np.where(cand, prio, P)
        best = np.full(n, P)
        np.minimum.at(best, ru, p)
        np.minimum.at(best, rv, p)
        win = cand & ((best[ru] == prio) | (best[rv] == prio))
        frontier |= win
        undecided &= ~win
        for i in np.nonzero(win)[0]:
            merged.union(int(ru[i]), int(rv[i]))
        top = merged.roots()
        roots, na, nb = top[roots], top[na], top[nb]


def label_rounds(u: np.ndarray, v: np.ndarray, n_objects: int,
                 answers: np.ndarray, positive_only: bool = False
                 ) -> Dict[str, object]:
    """Label every pair, in labeling order (position = priority).

    ``answers`` is the crowd's POS/NEG answer per pair.  With
    ``positive_only`` (the control) non-match edges are never used: a pair
    that only a non-match edge settles is put to the crowd again.

    Returns labels (P,) bool, crowdsourced (P,) bool, round_sizes and
    n_conflicts."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    P = len(u)
    labels = np.full(P, UNKNOWN, np.int64)
    crowdsourced = np.zeros(P, bool)
    round_sizes: List[int] = []
    graph = _Graph(n_objects)
    n_conflicts = 0
    while (labels == UNKNOWN).any():
        front = _frontier(u, v, n_objects, graph, labels == UNKNOWN,
                          positive_only)
        if not front.any():
            raise RuntimeError("reference stuck: no frontier")
        round_sizes.append(int(front.sum()))
        crowdsourced |= front
        for i in np.nonzero(front)[0]:
            a, b, lab = int(u[i]), int(v[i]), int(answers[i])
            d = graph.deduce(a, b) if not positive_only else UNKNOWN
            if d != UNKNOWN and d != lab:
                n_conflicts += 1  # dropped; deduction labels the pair
                continue
            labels[i] = lab
            if lab == POS:
                graph.add_match(a, b)
            else:
                graph.add_non_match(a, b)
        roots = graph.uf.roots()
        pend = labels == UNKNOWN
        ru, rv = roots[u], roots[v]
        labels[pend & (ru == rv)] = POS
        if not positive_only:
            hit = np.isin(_keys(ru, rv, n_objects), graph.neg_keys(roots))
            labels[pend & (ru != rv) & hit] = NEG
    return {"labels": labels == POS, "crowdsourced": crowdsourced,
            "round_sizes": round_sizes, "n_conflicts": n_conflicts}


def expected_order(likelihood: np.ndarray) -> np.ndarray:
    """Descending likelihood, ties by pair index (source paper §4.2)."""
    return np.argsort(-np.asarray(likelihood), kind="stable")


def label_session(u, v, likelihood, n_objects: int, answers,
                  positive_only: bool = False) -> Dict[str, object]:
    """:func:`label_rounds` in expected order, results in the caller's pair
    order."""
    order = expected_order(likelihood)
    out = label_rounds(np.asarray(u)[order], np.asarray(v)[order], n_objects,
                       np.asarray(answers)[order], positive_only)
    labels = np.zeros(len(order), bool)
    crowd = np.zeros(len(order), bool)
    labels[order] = out["labels"]
    crowd[order] = out["crowdsourced"]
    return {**out, "labels": labels, "crowdsourced": crowd}


def unit_rows(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def dense_candidates(a: np.ndarray, b: np.ndarray, threshold: float,
                     margin: float = 1e-4, block: int = 512
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Every (i, j) with cos(a_i, b_j) >= threshold, row-major, with its
    float64 cosine.  A float32 product at full precision over row blocks
    of ``a`` (on the default JAX device) shortlists the pairs that score
    within ``margin`` of the threshold or above; float32 errs by about
    1e-6 here, far inside the margin.  Each shortlisted pair is then scored
    in float64 on the host."""
    import jax
    import jax.numpy as jnp

    a64, b64 = unit_rows(a), unit_rows(b)
    b32 = jnp.asarray(b64, jnp.float32)
    near = jax.jit(lambda x, y: jnp.dot(
        x, y.T, precision=jax.lax.Precision.HIGHEST) >= threshold - margin)
    rows, cols = [], []
    for r0 in range(0, len(a64), block):
        r, c = np.nonzero(np.asarray(near(
            jnp.asarray(a64[r0:r0 + block], jnp.float32), b32)))
        rows.append(r + r0)
        cols.append(c)
    rows = np.concatenate(rows).astype(np.int64)
    cols = np.concatenate(cols).astype(np.int64)
    exact = np.einsum("nd,nd->n", a64[rows], b64[cols])
    keep = exact >= threshold
    return rows[keep], cols[keep], exact[keep]


def candidate_gaps(got: Tuple[np.ndarray, np.ndarray, np.ndarray],
                   ref: Tuple[np.ndarray, np.ndarray, np.ndarray],
                   a: np.ndarray, b: np.ndarray, threshold: float
                   ) -> Dict[str, float]:
    """Compare a served candidate list (rows, cols, scores) with the
    reference's, ``a`` and ``b`` being the embeddings both scored.

    Returns ``score_gap``, the widest gap between a served score and the
    float64 score of the same pair; ``extra_below_tau``, how far below the
    threshold the worst served pair lies that the reference does not hold;
    ``missed_above_tau``, how far above it the worst reference pair lies
    that was not served; and ``recall``, the share of reference pairs
    served."""
    gr, gc, gs = (np.asarray(x) for x in got)
    rr, rc, rs = (np.asarray(x) for x in ref)
    a, b = unit_rows(a), unit_rows(b)
    exact = np.einsum("nd,nd->n", a[gr], b[gc])
    diff = gs.astype(np.float64) - exact
    gap = float(np.abs(diff).max(initial=0.0))
    n_cols = np.int64(len(b))
    kg = gr.astype(np.int64) * n_cols + gc
    kr = rr.astype(np.int64) * n_cols + rc
    only_got = ~np.isin(kg, kr)
    only_ref = ~np.isin(kr, kg)
    return {
        "score_gap": gap,
        "extra_below_tau": float((threshold - exact[only_got]).max(
            initial=0.0)),
        "missed_above_tau": float((rs[only_ref] - threshold).max(
            initial=0.0)),
        "recall": 1.0 - only_ref.sum() / len(kr) if len(kr) else 1.0,
    }
