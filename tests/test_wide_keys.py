"""Two-word pair keys (DESIGN.md §8): object universes past 46,340 ids,
whose ``lo * n + hi`` keys overflow int32, carry each negative-edge key as
two int32 words.  Sessions of such universes must label exactly as the
``ClusterGraph`` oracle does, on the fused and the per-round path, fold
noisy streams with the same conflicts, survive a checkpoint, and give what
the same session relabelled into a small universe gives; universes whose
keys fit keep one-word keys.

The oracle is the round-barrier reference of ``bench/reference.py``:
Algorithm 2 with priority-Boruvka selection over a plain union-find
ClusterGraph, which imports nothing of the engine.  (The sequential
Algorithm 3 scan of ``label_parallel`` may crowdsource other pairs in other
rounds, whatever the key width: the engine judges negative edges against
the components of the round's start.)"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (NoisyCrowd, PerfectCrowd, UNKNOWN, make_session_state,
                        pair_keys_fit, session_fold_answers,
                        session_from_labels)
from repro.core.cluster_graph import ClusterGraph, MATCH, NON_MATCH
from repro.core.jax_graph import NEG, POS
from repro.core.pairs import PairSet
from repro.serve.join_service import (JoinService, ServiceKilled,
                                      _object_bucket)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import reference  # noqa: E402


def _wide_pairs(seed, n_objects, n_pairs=300, consistent=True):
    """A few hundred distinct pairs over a dense cluster of ids scattered
    through an ``n_objects`` universe (so roots and keys use ids past
    46,340); answers follow a random partition (``consistent``) or are
    random per pair."""
    rng = np.random.default_rng(seed)
    ids = np.sort(rng.choice(n_objects, n_pairs // 6, replace=False))
    ids[-1] = n_objects - 1
    u, v = rng.choice(ids, n_pairs), rng.choice(ids, n_pairs)
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    _, first = np.unique(lo.astype(np.int64) * n_objects + hi,
                         return_index=True)
    first = np.sort(first)
    u, v = lo[first].astype(np.int32), hi[first].astype(np.int32)
    if consistent:
        cluster = rng.integers(0, len(ids) // 8, n_objects)
        truth = cluster[u] == cluster[v]
    else:
        truth = rng.random(len(u)) < 0.4
    lik = rng.random(len(u)).astype(np.float32)
    return PairSet(u=u, v=v, likelihood=lik, truth=truth,
                   n_objects=n_objects)


def _relabel(ps: PairSet) -> PairSet:
    """The same session over the ids it uses, renumbered in order."""
    used, inv = np.unique(np.concatenate([ps.u, ps.v]), return_inverse=True)
    return PairSet(u=inv[:len(ps)].astype(np.int32),
                   v=inv[len(ps):].astype(np.int32),
                   likelihood=ps.likelihood, truth=ps.truth,
                   n_objects=len(used))


def _serve(ps, fused=True):
    svc = JoinService(lanes=1, fused_rounds=fused)
    rid = svc.submit(ps, PerfectCrowd())
    return svc.run()[rid]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per_round"])
@pytest.mark.parametrize("consistent", [True, False],
                         ids=["partition", "random"])
@pytest.mark.parametrize("seed,n_objects", [(0, 46341), (1, 52000),
                                            (2, 70000)])
def test_wide_session_matches_cluster_graph_oracle(seed, n_objects,
                                                   consistent, fused):
    assert not pair_keys_fit(n_objects)
    ps = _wide_pairs(seed, n_objects, consistent=consistent)
    ref = reference.label_session(ps.u, ps.v, ps.likelihood, n_objects,
                                  np.where(ps.truth, reference.POS,
                                           reference.NEG))
    w0 = obs.wide_key_lanes.count
    got = _serve(ps, fused)
    assert obs.wide_key_lanes.count == w0 + 1
    np.testing.assert_array_equal(got.labels, ref["labels"])
    np.testing.assert_array_equal(got.crowdsourced, ref["crowdsourced"])
    assert list(got.round_sizes) == ref["round_sizes"]
    assert len(ref["round_sizes"]) > 2 and got.n_deduced > 0


@pytest.mark.parametrize("seed", [3, 4])
def test_wide_session_equals_relabelled_narrow(seed):
    ps = _wide_pairs(seed, 60000)
    small = _relabel(ps)
    assert pair_keys_fit(small.n_objects)
    w0 = obs.wide_key_lanes.count
    narrow = _serve(small)
    assert obs.wide_key_lanes.count == w0
    wide = _serve(ps)
    assert obs.wide_key_lanes.count == w0 + 1
    np.testing.assert_array_equal(wide.labels, narrow.labels)
    np.testing.assert_array_equal(wide.crowdsourced, narrow.crowdsourced)
    assert list(wide.round_sizes) == list(narrow.round_sizes)


@pytest.mark.parametrize("n_objects,cap,words", [
    (1000, 1024, 1), (24628, 32768, 1),
    # the 65,536 bucket would need two words: keep one at the raw size
    (46000, 46000, 1), (46340, 46340, 1),
    (46341, 65536, 2), (100000, 131072, 2)])
def test_keys_that_fit_stay_one_word(n_objects, cap, words):
    assert _object_bucket(n_objects) == cap
    st = make_session_state(np.array([0, 1], np.int32),
                            np.array([2, n_objects - 1], np.int32),
                            n_objects, pair_capacity=8, object_capacity=cap)
    want = (8,) if words == 1 else (2, 8)
    assert st.neg_keys.shape == want and st.neg_keys.dtype == jnp.int32


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_wide_noisy_fold_matches_cluster_graph(seed):
    """Noisy answer chunks folded into a two-word state, in lockstep with
    ``ClusterGraph.add_label``: same labels, same conflicts, and the state
    equals a from-scratch rebuild after every fold (the §8 invariant)."""
    rng = np.random.default_rng(seed)
    ps = _wide_pairs(seed, 64000)
    u, v, n, m = ps.u, ps.v, ps.n_objects, len(ps)
    truth = np.where(ps.truth, POS, NEG)
    state = make_session_state(u, v, n)
    assert state.neg_keys.ndim == 2
    g = ClusterGraph(n)
    labels = np.full(m, UNKNOWN, np.int32)
    order = rng.permutation(m)
    while (labels == UNKNOWN).any():
        # the next unlabeled pairs, answered in pair-index order
        chunk = np.sort([i for i in order if labels[i] == UNKNOWN][:40])
        ans = np.where(rng.random(len(chunk)) < 0.3, POS + NEG - truth[chunk],
                       truth[chunk]).astype(np.int32)
        upd = np.full(m, UNKNOWN, np.int32)
        upd[chunk] = ans
        state, _ = session_fold_answers(state, jnp.asarray(upd))
        for i, a in zip(chunk, ans):  # the oracle, one answer at a time
            if g.add_label(int(u[i]), int(v[i]),
                           MATCH if a == POS else NON_MATCH):
                labels[i] = a
        for i in range(m):  # deduction sweep
            if labels[i] == UNKNOWN:
                d = g.deduce(int(u[i]), int(v[i]))
                if d is not None:
                    labels[i] = POS if d == MATCH else NEG
        np.testing.assert_array_equal(np.asarray(state.labels), labels)
        assert int(np.asarray(state.conflicts).sum()) == g.n_conflicts
        ref = session_from_labels(u, v, labels, np.zeros(m, bool), n)
        for f in ("roots", "neg_keys"):
            np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                          np.asarray(getattr(ref, f)))
    assert g.n_conflicts > 0


def test_wide_lane_checkpoint_restore_parity(tmp_path):
    """A two-word lane checkpointed mid-run comes back two-word through
    ``serve/recovery.py`` and finishes exactly as an uninterrupted run."""
    pss = [_wide_pairs(s, 58000, n_pairs=150) for s in (8, 9)]

    def crowd(s):
        return NoisyCrowd(error_rate=0.2, qualification=False, seed=s)

    base_svc = JoinService(lanes=2)
    rids = [base_svc.submit(ps, crowd(s)) for s, ps in enumerate(pss)]
    base = base_svc.run()
    svc = JoinService(lanes=2, checkpoint_dir=str(tmp_path))
    [svc.submit(ps, crowd(s)) for s, ps in enumerate(pss)]
    svc._crash_after_checkpoints = 2
    with pytest.raises(ServiceKilled):
        svc.run()
    restored = JoinService.restore(str(tmp_path))
    lanes, _ = restored._resume
    assert lanes and all(l.state.neg_keys.ndim == 2 for l in lanes)
    rec = restored.run()
    for r in rids:
        np.testing.assert_array_equal(base[r].labels, rec[r].labels)
        np.testing.assert_array_equal(base[r].crowdsourced,
                                      rec[r].crowdsourced)
        assert base[r].n_spent_cents == pytest.approx(rec[r].n_spent_cents)
        assert base[r].n_conflicts == rec[r].n_conflicts
