"""Decide ``correct``: compare what the window served with the plain
references of ``bench/reference.py``.

Human phase, every cell: each served session's labels, crowdsourced set,
round sizes and spend against :func:`reference.label_session` over the
same candidate pairs, with the crowd's answers taken from the generator's
entity ids (never from the program).  These comparisons are exact.

Machine phase, cells that submit embeddings: each served candidate list
against :func:`reference.dense_candidates`, the float64 dense oracle over
the same embeddings.  ``score_gap`` is the widest gap between a served
score and the float64 score of that pair; ``extra_below_tau`` how far
below the threshold the worst served pair lies that the oracle does not
hold (no pair below the threshold is served, to rounding); on the dense
path ``missed_above_tau`` is how far above the threshold the worst oracle
pair lies that was not served; on the blocked path ``recall`` is held to
the traffic's recall floor.  The worst session's reading of each is
compared.

``score_gap``'s limit is the configuration's ``limits``; ``extra_below_tau``
and ``missed_above_tau`` take the same band, and ``recall`` the traffic's
``recall_floor``.  ``PERF.md`` gives the readings each limit was set
from."""
from __future__ import annotations

import hashlib
from typing import Dict, List

import numpy as np

from bench import reference

EXACT = ("label_mismatches", "crowdsourced_mismatches", "round_mismatches",
         "cents_mismatch")


def session_answers(sess: dict, cand=None) -> np.ndarray:
    """A perfect crowd's answer per candidate pair, from the generator's
    entity ids."""
    if cand is None:
        return np.where(sess["truth"], reference.POS, reference.NEG)
    rows, cols, _ = cand
    return np.where(sess["ent_a"][rows] == sess["ent_b"][cols],
                    reference.POS, reference.NEG)


def human_inputs(sess: dict, cand=None):
    """(u, v, likelihood, n_objects) the human phase was given."""
    if cand is None:
        return sess["u"], sess["v"], sess["likelihood"], sess["n_objects"]
    rows, cols, lik = cand
    n_a = int(sess["a"].shape[0])
    return rows, cols + n_a, lik, n_a + int(sess["b"].shape[0])


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def human_readings(served: List, pool: list, cents_per_question: float
                   ) -> Dict[str, float]:
    """Exact comparison numbers of the human phase over served sessions.
    Also marks each session's ``ok``."""
    refs: Dict[str, dict] = {}
    out = dict.fromkeys(EXACT, 0.0)
    for s in served:
        sess = pool[s.pool_index]
        u, v, lik, n = human_inputs(sess, s.candidates)
        answers = session_answers(sess, s.candidates)
        key = _digest(u, v, lik, answers)
        if key not in refs:
            refs[key] = reference.label_session(u, v, lik, n, answers)
        ref = refs[key]
        lab = int((s.labels != ref["labels"]).sum())
        crowd = int((s.crowdsourced != ref["crowdsourced"]).sum())
        rounds = int(s.round_sizes != ref["round_sizes"])
        cents = abs(s.spent_cents - cents_per_question
                    * float(ref["crowdsourced"].sum()))
        out["label_mismatches"] += lab
        out["crowdsourced_mismatches"] += crowd
        out["round_mismatches"] += rounds
        out["cents_mismatch"] += cents
        s.ok = not (lab or crowd or rounds or cents)
    return out


def machine_readings(served: List, pool: list, path: str
                     ) -> Dict[str, float]:
    """Machine-phase comparison numbers over served embedding sessions:
    the worst session's reading of each."""
    refs: Dict[int, tuple] = {}
    out: Dict[str, float] = {"score_gap": 0.0, "extra_below_tau": 0.0}
    if path == "dense":
        out["missed_above_tau"] = 0.0
    else:
        out["recall"] = 1.0
    for s in served:
        sess = pool[s.pool_index]
        if s.pool_index not in refs:
            a, b = np.asarray(sess["a"]), np.asarray(sess["b"])
            refs[s.pool_index] = (a, b, reference.dense_candidates(
                a, b, sess["threshold"]))
        a, b, ref = refs[s.pool_index]
        rows, cols, lik = s.candidates
        got = (rows, cols, 2.0 * np.asarray(lik, np.float64) - 1.0)
        g = reference.candidate_gaps(got, ref, a, b, sess["threshold"])
        out["score_gap"] = max(out["score_gap"], g["score_gap"])
        out["extra_below_tau"] = max(out["extra_below_tau"],
                                     g["extra_below_tau"])
        if path == "dense":
            out["missed_above_tau"] = max(out["missed_above_tau"],
                                          g["missed_above_tau"])
        else:
            out["recall"] = min(out["recall"], g["recall"])
    return out


def limits_of(cell) -> Dict[str, tuple]:
    """name -> (limit, True if the reading must stay at or below it)."""
    lim = {k: (0.0, True) for k in EXACT}
    m = cell.traffic.get("machine")
    if m is not None:
        band = cell.config["limits"]["score_gap"]
        lim["score_gap"] = (band, True)
        lim["extra_below_tau"] = (band, True)
        if m["path"] == "dense":
            lim["missed_above_tau"] = (band, True)
        if m["path"] == "blocked":
            lim["recall"] = (m["recall_floor"], False)
    return lim


def cents_per_question(cell) -> float:
    votes = cell.traffic["crowd"].get("n_assignments", 1)
    return cell.config["cost"]["cents_per_assignment"] * votes


def readings(cell, pool: list, served: List) -> Dict[str, float]:
    out = human_readings(served, pool, cents_per_question(cell))
    m = cell.traffic.get("machine")
    if m is not None:
        out.update(machine_readings(served, pool, m["path"]))
    return out


def judge(cell, values: Dict[str, float]) -> Dict[str, dict]:
    checks = {}
    for name, (limit, upper) in limits_of(cell).items():
        v = values[name]
        ok = v <= limit if upper else v >= limit
        checks[name] = {"value": v, "limit": limit, "ok": bool(ok)}
    return checks


def check_run(cell, pool: list, rec) -> Dict[str, dict]:
    """Judge every session served in the window; machine-phase failures
    mark the sessions of that pool entry wrong too."""
    checks = judge(cell, readings(cell, pool, rec.served))
    if not all(c["ok"] for n, c in checks.items() if n not in EXACT):
        for s in rec.served:
            s.ok = False
    return checks
