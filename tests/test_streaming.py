"""Streaming ingest (DESIGN.md §11): grow live sessions with new objects
and pairs.

Three layers of evidence:

* engine — ``session_grow`` / ``session_append_pairs`` are pad-preserving
  and *exact*: a grown+appended state is bit-identical to
  ``make_session_state`` built from the concatenated pairs, through noisy
  (conflicting) answer replays, unbatched and batched (property-tested);
* kernels — ``StreamingCandidateIndex`` returns exactly the candidates a
  full re-score would add, while scoring strictly fewer grid cells;
* serving — the **differential harness**: a k-epoch ``submit_stream`` with
  a ``PerfectCrowd`` must match a single-shot batch ``submit`` of the
  concatenated pairs label-for-label, root-for-root, and
  crowdsourced-pair-for-pair, under BOTH serving disciplines — any defect
  in growth, re-bucketing, neg-key re-encoding, or priority merging makes
  the two runs diverge.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (ClusterGraph, LatencyModel, MATCH, NEG, NON_MATCH,
                        PerfectCrowd, POS, UNKNOWN, make_session_state,
                        make_session_state_batch, pack_sessions,
                        session_append_pairs, session_append_pairs_batch,
                        session_apply_answers, session_fold_answers,
                        session_grow, session_grow_batch)
from repro.core.pairs import PairSet


# ---------------------------------------------------------------------------
# helpers (the epoch splitter is shared with benchmarks/bench_streaming.py)
# ---------------------------------------------------------------------------
from benchmarks.common import split_epochs as _split_epochs  # noqa: E402


def _roots_from_labels(ps: PairSet, labels: np.ndarray) -> np.ndarray:
    """Canonical cluster roots implied by a labeling of the pair set."""
    g = ClusterGraph(ps.n_objects)
    for i in np.nonzero(labels)[0]:
        g.add_label(int(ps.u[i]), int(ps.v[i]), MATCH)
    return np.array([g.find(i) for i in range(ps.n_objects)])


def _epoch_worlds(world_builder, seed: int):
    """A random world split into epochs plus the concatenated reference."""
    rng = np.random.default_rng(seed)
    n, u, v, truth = world_builder(rng)
    k = int(rng.integers(2, 4))
    m = len(u)
    cut = sorted(rng.choice(np.arange(1, m), size=min(k - 1, m - 1),
                            replace=False).tolist())
    bounds = [0, *cut, m]
    epochs = [(u[a:b], v[a:b]) for a, b in zip(bounds, bounds[1:])]
    return n, u, v, truth, epochs, rng


# ---------------------------------------------------------------------------
# engine: grow/append exactness
# ---------------------------------------------------------------------------
def test_grown_fresh_state_equals_make_session_state():
    """Growing a fresh state is bit-identical to building it at the larger
    capacities — priorities, pad labels, roots, sentinel padding, all of it."""
    u = np.array([0, 1, 2], np.int32)
    v = np.array([1, 2, 3], np.int32)
    small = make_session_state(u, v, 4, pair_capacity=4, object_capacity=4)
    grown = session_grow(small, 16, 8)
    ref = make_session_state(u, v, 4, pair_capacity=16, object_capacity=8)
    for f in ("u", "v", "labels", "published", "roots", "neg_keys",
              "rounds", "conflicts", "priority"):
        np.testing.assert_array_equal(
            np.asarray(getattr(grown, f)), np.asarray(getattr(ref, f)), f)
    assert grown.n_objects == 8


def test_session_grow_rejects_shrink_and_key_overflow():
    u = np.array([0], np.int32)
    v = np.array([1], np.int32)
    st_ = make_session_state(u, v, 2, pair_capacity=8, object_capacity=8)
    with pytest.raises(ValueError, match="shrink pair"):
        session_grow(st_, 4, 8)
    with pytest.raises(ValueError, match="shrink object"):
        session_grow(st_, 8, 4)
    import jax
    if not jax.config.jax_enable_x64:
        # 46341**2 >= 2**31: growing past one-word keys re-encodes the
        # index in two words, equal to a state built at the larger size
        upd = jnp.full((8,), UNKNOWN, jnp.int32).at[0].set(NEG)
        grown, _ = session_fold_answers(st_, upd)
        grown = session_grow(grown, 8, 46341)
        fresh = make_session_state(u, v, 2, pair_capacity=8,
                                   object_capacity=46341)
        fresh, _ = session_fold_answers(fresh, upd)
        assert grown.neg_keys.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(grown.neg_keys)[:, 0],
                                      [0, 1])
        for f in ("labels", "roots", "neg_keys"):
            np.testing.assert_array_equal(np.asarray(getattr(grown, f)),
                                          np.asarray(getattr(fresh, f)))


def _noisy_stream_parity(world_builder, seed: int, flip: float = 0.35):
    """The satellite property: fold-after-grow is bit-identical to
    from-scratch ``make_session_state`` on the concatenated pairs, conflict
    counts included, under a noisy replay.

    Stage 1 applies noisy answers for epoch-1 pairs to (a) a state holding
    only epoch 1 and (b) the reference state built with every epoch's pairs
    from the start.  The epoch-1 state then grows and appends the remaining
    epochs — after which the two states must agree bit-for-bit — and stage 2
    folds noisy answers for the remaining pairs through both."""
    n, u, v, truth, epochs, rng = _epoch_worlds(world_builder, seed)
    m = len(u)
    p_cap, n_cap = 32, 16
    u1, v1 = epochs[0]
    p1 = len(u1)
    state = make_session_state(u1, v1, n, pair_capacity=8,
                               object_capacity=n)
    ref = make_session_state(u, v, n, pair_capacity=p_cap,
                             object_capacity=n_cap)

    def noisy(idx):
        return np.where(rng.random(len(idx)) < flip, NEG + POS - truth[idx],
                        truth[idx]).astype(np.int32)

    # stage 1: noisy answers over a random half of epoch 1, on both states
    take1 = rng.permutation(p1)[:max(p1 // 2, 1)]
    ans1 = noisy(take1)
    # an epoch of more than 8 pairs gets a state that large
    upd_small = np.full(int(state.u.shape[0]), UNKNOWN, np.int32)
    upd_small[take1] = ans1
    upd_ref = np.full(p_cap, UNKNOWN, np.int32)
    upd_ref[take1] = ans1
    state, cm_s = session_apply_answers(state, jnp.asarray(upd_small))
    ref, cm_r = session_apply_answers(ref, jnp.asarray(upd_ref))
    np.testing.assert_array_equal(np.asarray(cm_s)[:p1],
                                  np.asarray(cm_r)[:p1])

    # grow to the reference capacities and append the remaining epochs
    state = session_grow(state, p_cap, n_cap)
    off = p1
    for ue, ve in epochs[1:]:
        au = np.zeros(p_cap, np.int32)
        av = np.zeros(p_cap, np.int32)
        mask = np.zeros(p_cap, bool)
        au[off:off + len(ue)] = ue
        av[off:off + len(ue)] = ve
        mask[off:off + len(ue)] = True
        state = session_append_pairs(state, au, av, mask)
        off += len(ue)
    for f in ("u", "v", "labels", "published", "roots", "neg_keys",
              "rounds", "conflicts", "priority"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(getattr(ref, f)), f)

    # stage 2: noisy fold (apply + deduce) over every still-unknown pair
    pending = np.nonzero(np.asarray(state.labels)[:m] == UNKNOWN)[0]
    if len(pending):
        ans2 = noisy(pending)
        upd = np.full(p_cap, UNKNOWN, np.int32)
        upd[pending] = ans2
        state, cm_s = session_fold_answers(state, jnp.asarray(upd))
        ref, cm_r = session_fold_answers(ref, jnp.asarray(upd))
        np.testing.assert_array_equal(np.asarray(cm_s), np.asarray(cm_r))
    for f in ("labels", "roots", "neg_keys", "conflicts", "rounds"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(getattr(ref, f)), f)
    return int(np.asarray(state.conflicts).sum())


@pytest.mark.parametrize("seed", range(6))
def test_fold_after_grow_bit_identical(make_random_world, seed):
    _noisy_stream_parity(make_random_world, seed)


def test_fold_after_grow_conflicts_actually_exercised(make_random_world):
    """The seeded parity runs must include real rejected answers, or the
    conflict-count clause is vacuous."""
    assert sum(_noisy_stream_parity(make_random_world, seed)
               for seed in range(6)) > 0


@given(st.integers(0, 10**6))
def test_fold_after_grow_bit_identical_property(make_random_world, seed):
    _noisy_stream_parity(make_random_world, seed)


def test_grow_append_batched_matches_unbatched(make_random_world):
    """The vmapped grow/append transforms agree with the per-session ones."""
    rngs = [np.random.default_rng(200 + b) for b in range(3)]
    worlds = [make_random_world(r) for r in rngs]
    sessions = [(u[:3], v[:3], n) for n, u, v, _ in worlds]
    U, V, labels0, valid, n_cap = pack_sessions(sessions)
    batch = make_session_state_batch(U, V, labels0, n_cap)
    batch = session_grow_batch(batch, 16, n_cap + 4)
    AU = np.zeros((3, 16), np.int32)
    AV = np.zeros((3, 16), np.int32)
    AM = np.zeros((3, 16), bool)
    for b, (n, u, v, _) in enumerate(worlds):
        extra = min(len(u) - 3, 4)
        AU[b, 3:3 + extra] = u[3:3 + extra]
        AV[b, 3:3 + extra] = v[3:3 + extra]
        AM[b, 3:3 + extra] = True
    batch = session_append_pairs_batch(batch, AU, AV, AM)
    for b, (n, u, v, _) in enumerate(worlds):
        one = make_session_state(u[:3], v[:3], n, pair_capacity=len(u[:3]),
                                 object_capacity=n_cap)
        one = session_grow(one, 16, n_cap + 4)
        one = session_append_pairs(one, AU[b], AV[b], AM[b])
        for f in ("u", "v", "labels", "published", "roots", "neg_keys",
                  "conflicts", "priority"):
            np.testing.assert_array_equal(
                np.asarray(getattr(batch, f))[b],
                np.asarray(getattr(one, f)), f)


# ---------------------------------------------------------------------------
# kernels: incremental candidate generation
# ---------------------------------------------------------------------------
def test_streaming_candidate_index_matches_batch(entity_embeddings):
    """Across mixed arrival epochs the union of incremental candidates must
    equal one batch score of the final corpora, with strictly less
    pair-score work."""
    from repro.kernels.pair_scores.sharded import (StreamingCandidateIndex,
                                                   sharded_candidates)
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(7)
    mesh = make_host_mesh(1, 1)
    _, a, cents = entity_embeddings(rng, 8, 28)
    _, b, _ = entity_embeddings(rng, 8, 22, centroids=cents)
    idx = StreamingCandidateIndex(0.6, mesh, impl="interpret")
    got = {}
    for ea, eb in ((a[:10], b[:8]), (a[10:18], None), (None, b[8:15]),
                   (a[18:], b[15:])):
        c = idx.append(ea, eb)
        for r, col, s in zip(c.rows, c.cols, c.scores):
            assert (r, col) not in got  # each new cell reported exactly once
            got[(int(r), int(col))] = float(s)
    full = sharded_candidates(jnp.asarray(a), jnp.asarray(b), 0.6, mesh,
                              impl="interpret")
    want = {(int(r), int(c)): float(s)
            for r, c, s in zip(full.rows, full.cols, full.scores)}
    assert set(got) == set(want)
    for key, s in got.items():
        assert abs(s - want[key]) < 1e-6
    assert idx.pairs_scored < idx.full_rescore_pairs
    assert idx.n_a == 28 and idx.n_b == 22


def test_streaming_candidate_index_rejects_nonpositive_threshold():
    from repro.kernels.pair_scores.sharded import StreamingCandidateIndex
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError, match="threshold"):
        StreamingCandidateIndex(0.0, make_host_mesh(1, 1))


# ---------------------------------------------------------------------------
# serving: the differential batch-vs-stream harness
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("async_mode", [False, True], ids=["barrier", "async"])
@pytest.mark.parametrize("order", ["expected", "adaptive"])
def test_streaming_differential_matches_batch(session_pairsets, async_mode,
                                              order):
    """k-epoch submit_stream with a PerfectCrowd == single-shot batch submit:
    labels, cluster roots, n_crowdsourced, and round sizes all identical,
    under both serving disciplines."""
    from repro.serve.join_service import JoinService

    for seed in (0, 1):
        pairsets = session_pairsets(3, seed=seed)
        svc_b = JoinService(lanes=2, async_mode=async_mode, order=order)
        rids_b = [svc_b.submit(ps, PerfectCrowd()) for ps in pairsets]
        res_b = svc_b.run()
        svc_s = JoinService(lanes=2, async_mode=async_mode, order=order)
        rids_s = [
            svc_s.submit_stream(_split_epochs(ps, 3, seed=7 + i),
                                PerfectCrowd())
            for i, ps in enumerate(pairsets)
        ]
        res_s = svc_s.run()
        for rb, rs, ps in zip(rids_b, rids_s, pairsets):
            batch, stream = res_b[rb], res_s[rs]
            np.testing.assert_array_equal(batch.labels, stream.labels)
            np.testing.assert_array_equal(batch.labels, ps.truth)
            np.testing.assert_array_equal(
                _roots_from_labels(ps, batch.labels),
                _roots_from_labels(ps, stream.labels))
            assert batch.n_crowdsourced == stream.n_crowdsourced
            assert batch.round_sizes == stream.round_sizes


def test_streaming_differential_async_latency_model(session_pairsets):
    """Same differential under the simulated asynchronous platform (worker
    pool + lognormal latency + NF steering): identical states mean identical
    gateway call sequences, so even the simulated clock agrees."""
    from repro.serve.join_service import JoinService

    pairsets = session_pairsets(2, seed=5)
    mk = lambda: JoinService(lanes=2, async_mode=True, nf=True,
                             latency=LatencyModel(n_workers=6, seed=3))
    svc_b = mk()
    rids_b = [svc_b.submit(ps, PerfectCrowd()) for ps in pairsets]
    res_b = svc_b.run()
    svc_s = mk()
    rids_s = [svc_s.submit_stream(_split_epochs(ps, 3, seed=i),
                                  PerfectCrowd())
              for i, ps in enumerate(pairsets)]
    res_s = svc_s.run()
    for rb, rs in zip(rids_b, rids_s):
        np.testing.assert_array_equal(res_b[rb].labels, res_s[rs].labels)
        assert res_b[rb].n_crowdsourced == res_s[rs].n_crowdsourced
        assert res_b[rb].sim_minutes == res_s[rs].sim_minutes


@pytest.mark.parametrize("async_mode", [False, True], ids=["barrier", "async"])
def test_streaming_interleaved_arrivals_label_correctly(session_pairsets,
                                                        async_mode):
    """Interleaved epochs land while earlier crowd work is in flight; the
    schedule differs from batch, but every pair must still label to truth
    and the in-flight/budget machinery must carry across the growth."""
    from repro.serve.join_service import JoinService

    pairsets = session_pairsets(3, seed=3)
    svc = JoinService(lanes=2, async_mode=async_mode)
    rids = [
        svc.submit_stream(_split_epochs(ps, 4, seed=i), PerfectCrowd(),
                          interleave=True)
        for i, ps in enumerate(pairsets)
    ]
    res = svc.run()
    for rid, ps in zip(rids, pairsets):
        np.testing.assert_array_equal(res[rid].labels, ps.truth)
        assert res[rid].n_crowdsourced + res[rid].n_deduced == len(ps)


def test_streaming_budget_carries_over_epochs(session_pairsets):
    """A budgeted streaming session keeps one spend ledger across every
    epoch: the total never exceeds the budget even though arrivals landed
    after the first publishes."""
    from repro.serve.join_service import JoinService

    ps = session_pairsets(1, seed=11, n_objects=(20, 24),
                          n_pairs=(50, 60))[0]
    svc = JoinService(lanes=1)
    rid = svc.submit_stream(_split_epochs(ps, 3, seed=0), PerfectCrowd(),
                            budget_cents=8.0, cost_per_assignment=2.0,
                            interleave=True)
    res = svc.run()[rid]
    assert res.stopped_on_budget
    assert 0 < res.n_spent_cents <= 8.0
    assert res.n_crowdsourced <= 4


def test_append_validation_and_empty_epochs(session_pairsets):
    from repro.serve.join_service import JoinService

    ps = session_pairsets(1, seed=2)[0]
    empty = PairSet(np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, np.float32), np.zeros(0, bool), n_objects=4)
    svc = JoinService(lanes=1)
    with pytest.raises(ValueError, match="unknown rid"):
        svc.append(99, ps)
    rid = svc.submit(ps, PerfectCrowd())
    svc.append(rid, empty)  # no-op, must not wedge the run
    res = svc.run()
    np.testing.assert_array_equal(res[rid].labels, ps.truth)
    with pytest.raises(ValueError, match="already finished"):
        svc.append(rid, ps)
    with pytest.raises(ValueError, match="at least one epoch"):
        svc.submit_stream([], PerfectCrowd())


def test_pairset_concat_rejects_mixed_truth():
    a = PairSet(np.array([0], np.int32), np.array([1], np.int32),
                np.array([0.5], np.float32), np.array([True]))
    b = PairSet(np.array([1], np.int32), np.array([2], np.int32),
                np.array([0.5], np.float32), None)
    with pytest.raises(ValueError, match="truth"):
        a.concat(b)
    both = a.concat(a)
    assert len(both) == 2 and both.n_objects == 2


# ---------------------------------------------------------------------------
# satellite regressions: overflow reporting + key-range re-check on growth
# ---------------------------------------------------------------------------
def test_submit_embeddings_overflow_reports_post_growth_capacity(
        entity_embeddings):
    """The overflow error must name the per-device capacity a (streaming)
    caller should come back with — and that capacity must actually fit."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(5)
    _, ea, cents = entity_embeddings(rng, 4, 24, noise=0.1)
    _, eb, _ = entity_embeddings(rng, 4, 20, noise=0.1, centroids=cents)
    svc = JoinService(lanes=1)
    mesh = make_host_mesh(1, 1)
    with pytest.raises(RuntimeError, match=r"re-submit with capacity=\d+"):
        svc.submit_embeddings(jnp.asarray(ea), jnp.asarray(eb), 0.5, mesh,
                              capacity=2, impl="interpret")
    # the suggested capacity is sufficient by construction
    from repro.kernels.pair_scores.sharded import sharded_candidates
    small = sharded_candidates(jnp.asarray(ea), jnp.asarray(eb), 0.5, mesh,
                               capacity=2, impl="interpret")
    retry = sharded_candidates(jnp.asarray(ea), jnp.asarray(eb), 0.5, mesh,
                               capacity=small.suggested_capacity,
                               impl="interpret")
    assert retry.n_dropped == 0
    assert len(retry) == len(small) + small.n_dropped


def test_pair_keys_refit_checked_after_growth():
    """Regression (DESIGN.md §11): an arrival pushing the object universe
    past one-word pair keys must re-encode the grown lane's neg-key index
    in two words at ingest — never let it wrap — and the stream must then
    label exactly as a single-shot submit of the concatenated pairs."""
    import jax

    from repro.serve.join_service import JoinService

    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled — int32 boundary not in effect")
    n0 = 46340  # last universe whose n*n fits below 2**31
    ps1 = PairSet(np.array([0, 1, 0], np.int32),
                  np.array([n0 - 1, n0 - 2, 1], np.int32),
                  np.array([0.9, 0.8, 0.75], np.float32),
                  np.array([True, True, False]), n_objects=n0)
    ps2 = PairSet(np.array([2, n0 - 1, n0 - 2], np.int32),
                  np.array([46341, 1, 0], np.int32),
                  np.array([0.7, 0.6, 0.5], np.float32),
                  np.array([False, False, False]))
    svc = JoinService(lanes=1)
    rid = svc.submit_stream([ps1, ps2], PerfectCrowd())
    got = svc.run()[rid]
    ref_svc = JoinService(lanes=1)
    ref_rid = ref_svc.submit(ps1.concat(ps2), PerfectCrowd())
    ref = ref_svc.run()[ref_rid]
    np.testing.assert_array_equal(got.labels, ref.labels)
    np.testing.assert_array_equal(got.crowdsourced, ref.crowdsourced)
    assert got.round_sizes == ref.round_sizes
    assert got.n_deduced > 0  # a two-word neg key did the deducing


def test_streaming_embeddings_end_to_end(entity_embeddings):
    """Machine-phase streaming: cached index + append_embeddings feeds the
    live session; appended rows get fresh object ids and the join finishes
    with perfect precision and real transitivity savings."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(3)
    ids_a, ea, cents = entity_embeddings(rng, 10, 24)
    ids_b, eb, _ = entity_embeddings(rng, 10, 20, centroids=cents)
    all_a, all_b = list(ids_a), list(ids_b)
    truth_fn = lambda r, c: np.asarray(all_a)[r] == np.asarray(all_b)[c]
    svc = JoinService(lanes=1)
    mesh = make_host_mesh(1, 1)
    rid = svc.submit_embeddings(jnp.asarray(ea), jnp.asarray(eb), 0.8, mesh,
                                crowd=PerfectCrowd(), truth_fn=truth_fn,
                                impl="interpret", streaming=True)
    for _ in range(2):
        na, ea_new, _ = entity_embeddings(rng, 10, 8, centroids=cents)
        nb, eb_new, _ = entity_embeddings(rng, 10, 6, centroids=cents)
        all_a += list(na)
        all_b += list(nb)
        svc.append_embeddings(rid, jnp.asarray(ea_new), jnp.asarray(eb_new))
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0
    assert res.n_deduced > 0
    # the cached index is dropped once the request finalizes
    with pytest.raises(ValueError, match="no cached embedding index"):
        svc.append_embeddings(rid, jnp.asarray(ea[:1]), None)


def test_append_embeddings_overflow_rolls_back_the_epoch(entity_embeddings):
    """A rejected arrival epoch must leave the stream usable: the cached
    index forgets the failed rows (no ghost corpus entries desyncing the
    row -> object-id maps) and a smaller retry epoch still ingests."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(13)
    ids_a, ea, cents = entity_embeddings(rng, 6, 10, noise=0.1)
    ids_b, eb, _ = entity_embeddings(rng, 6, 8, noise=0.1, centroids=cents)
    all_a, all_b = list(ids_a), list(ids_b)
    truth_fn = lambda r, c: np.asarray(all_a)[r] == np.asarray(all_b)[c]
    svc = JoinService(lanes=1)
    mesh = make_host_mesh(1, 1)
    rid = svc.submit_embeddings(jnp.asarray(ea), jnp.asarray(eb), 0.5, mesh,
                                crowd=PerfectCrowd(), truth_fn=truth_fn,
                                capacity=64, impl="interpret",
                                streaming=True)
    stream = svc._streams[rid]
    _, big, _ = entity_embeddings(rng, 6, 80, noise=0.1, centroids=cents)
    with pytest.raises(RuntimeError, match="rolled back"):
        svc.append_embeddings(rid, jnp.asarray(big), None)
    # the failed rows are gone from the index; maps stay in sync
    assert stream.index.n_a == len(stream.ids_a) == 10
    ids_small, small, _ = entity_embeddings(rng, 6, 3, noise=0.1,
                                            centroids=cents)
    all_a += list(ids_small)
    svc.append_embeddings(rid, jnp.asarray(small), None)
    assert stream.index.n_a == len(stream.ids_a) == 13
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0


def test_append_embeddings_requires_streaming_submit(entity_embeddings):
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(9)
    _, ea, cents = entity_embeddings(rng, 6, 12)
    _, eb, _ = entity_embeddings(rng, 6, 10, centroids=cents)
    svc = JoinService(lanes=1)
    mesh = make_host_mesh(1, 1)
    rid = svc.submit_embeddings(jnp.asarray(ea), jnp.asarray(eb), 0.8, mesh,
                                crowd=PerfectCrowd(), impl="interpret")
    with pytest.raises(ValueError, match="streaming=True"):
        svc.append_embeddings(rid, jnp.asarray(ea[:2]), None)
