"""Cora-shaped self-join: the source paper's Paper dataset (section 6).

A copy of the program's ``make_paper_dataset`` generator (same rng draws,
so the same pairs at equal seeds): 997 records in heavy-tailed entity
clusters, one of 102, with Beta-mixture machine likelihoods over every
record pair.  The benchmark keeps its own copy so that a change to the
program's generator cannot move the yardstick."""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

_WORDS = (
    "apple ipad iphone galaxy pixel thinkpad core ultra pro max mini air "
    "gen nd rd th edition series model black white silver gb tb wifi lte "
    "camera lens speaker dock hub charger cable adapter mount stand case "
    "paper learning entity resolution crowd database query join index "
    "neural transitive relation cluster graph parallel label order"
).split()


def _corrupt(rng: np.random.Generator, s: str) -> str:
    toks = s.split()
    ops = rng.integers(0, 4)
    for _ in range(ops):
        k = rng.integers(0, 4)
        if k == 0 and len(toks) > 1:
            toks.pop(int(rng.integers(len(toks))))
        elif k == 1:
            i = int(rng.integers(len(toks)))
            toks[i] = toks[i][: max(2, len(toks[i]) - 2)]
        elif k == 2:
            if len(toks) > 1:
                i = int(rng.integers(len(toks) - 1))
                toks[i], toks[i + 1] = toks[i + 1], toks[i]
        else:
            toks.insert(int(rng.integers(len(toks) + 1)),
                        _WORDS[int(rng.integers(len(_WORDS)))])
    return " ".join(toks)


def _make_records(rng: np.random.Generator, sizes: np.ndarray
                  ) -> Tuple[np.ndarray, List[str]]:
    entity_of = []
    records: List[str] = []
    for eid, s in enumerate(sizes):
        n_tok = int(rng.integers(3, 7))
        canon = " ".join(_WORDS[int(rng.integers(len(_WORDS)))]
                         for _ in range(n_tok))
        for _ in range(int(s)):
            entity_of.append(eid)
            records.append(_corrupt(rng, canon))
    return np.asarray(entity_of, np.int32), records


def _likelihoods(rng, entity_of, match_beta, non_beta, min_lik,
                 hard_neg_frac, hard_neg_beta):
    n = len(entity_of)
    iu, ju = np.triu_indices(n, k=1)
    truth = entity_of[iu] == entity_of[ju]
    lik = np.empty(len(iu), np.float32)
    nm = int(truth.sum())
    lik[truth] = rng.beta(*match_beta, size=nm)
    non = rng.beta(*non_beta, size=len(iu) - nm)
    # confusability belongs to entity pairs: every record pair of two
    # confusable entities draws from the hard-negative Beta
    eu = entity_of[iu[~truth]].astype(np.int64)
    ev = entity_of[ju[~truth]].astype(np.int64)
    elo, ehi = np.minimum(eu, ev), np.maximum(eu, ev)
    ekey = elo * (int(entity_of.max()) + 1) + ehi
    uniq, inv = np.unique(ekey, return_inverse=True)
    hard = (rng.random(len(uniq)) < hard_neg_frac)[inv]
    non[hard] = rng.beta(*hard_neg_beta, size=int(hard.sum()))
    lik[~truth] = non
    keep = lik >= min_lik
    return iu[keep], ju[keep], lik[keep], truth[keep], nm


def generate(spec: dict, seed) -> dict:
    """One draw of the collection's candidate pairs: u, v (int32),
    likelihood (float32), truth (bool), n_objects, total_true_matches."""
    rng = np.random.default_rng(seed)
    n_records = spec["n_records"]
    sizes = [spec["largest_cluster"]]
    remaining = n_records - sizes[0]
    for s in (74, 61, 52, 47, 40, 35, 31, 27, 24, 21, 19, 17, 15, 13, 12,
              11, 10, 9, 8, 8, 7, 7, 6, 6, 5, 5, 5, 4, 4, 4, 3, 3, 3, 3):
        if remaining - s < 0:
            break
        sizes.append(s)
        remaining -= s
    while remaining > 0:
        s = min(int(rng.integers(1, 4)), remaining)
        sizes.append(s)
        remaining -= s
    entity_of, _ = _make_records(rng, np.asarray(sizes))
    u, v, lik, truth, total = _likelihoods(
        rng, entity_of, (6.0, 2.5), (1.0, 24.0), min_lik=0.1,
        hard_neg_frac=0.04, hard_neg_beta=(2.2, 4.0))
    keep = lik >= spec["likelihood_threshold"]
    return {"u": u[keep].astype(np.int32), "v": v[keep].astype(np.int32),
            "likelihood": lik[keep], "truth": truth[keep],
            "n_objects": n_records, "total_true_matches": total}


def pool(spec: dict, seed: int, n: int) -> list:
    """``n`` sessions over the fixed draws ``spec["instance_seeds"]``, each
    with its records relabelled and its candidate list reordered from
    ``seed``: every seed serves the same work under other ids."""
    out = []
    for k in range(n):
        base = generate(spec, spec["instance_seeds"][k % len(
            spec["instance_seeds"])])
        rng = np.random.default_rng([seed, k])
        ids = rng.permutation(base["n_objects"]).astype(np.int32)
        order = rng.permutation(len(base["u"]))
        out.append({**base, "u": ids[base["u"][order]],
                    "v": ids[base["v"][order]],
                    "likelihood": base["likelihood"][order],
                    "truth": base["truth"][order]})
    return out
