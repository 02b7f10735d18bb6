"""Crowd simulators and the CrowdGateway transport (DESIGN.md §8, §15).

NoisyCrowd's empirical majority-vote error must match its analytic
``pair_error_rate``; the gateway must deliver every posted answer with a
monotonic simulated clock, respect the worker pool, and steer
non-matching-first when asked; and a NoisyCrowd end-to-end JoinService run
must degrade quality in a bounded way, not collapse.

The §15 reliability model contracts: the streaming Dawid–Skene estimates
must converge to the simulated per-worker error rates, EM aggregation must
label no worse than majority at equal assignments, requeries must route to
fresh workers (with exhaustion semantics unchanged), and cluster-task
decoding must be conflict-screen-identical to submitting the same pairs
individually."""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (MATCH, NEG, POS, CrowdGateway, LatencyModel,
                        NoisyCrowd, PerfectCrowd)
from repro.core.crowd import WorkerModel
from repro.core.pairs import PairSet


def _truth_pairs(n_pairs: int, all_match: bool = True) -> PairSet:
    u = np.arange(n_pairs, dtype=np.int32)
    v = u + n_pairs
    truth = np.full(n_pairs, all_match, bool)
    lik = np.linspace(0.9, 0.1, n_pairs).astype(np.float32)
    return PairSet(u, v, lik, truth, n_objects=2 * n_pairs)


# ---------------------------------------------------------------------------
# NoisyCrowd: empirical vs analytic majority-vote error
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("error_rate,n_assignments", [(0.2, 3), (0.1, 5)])
def test_noisy_crowd_empirical_matches_analytic(error_rate, n_assignments):
    crowd = NoisyCrowd(error_rate=error_rate, n_assignments=n_assignments,
                       qualification=False, seed=3)
    pairs = _truth_pairs(1)
    n_asks = 20_000
    wrong = sum(crowd.ask(pairs, 0) != MATCH for _ in range(n_asks))
    empirical = wrong / n_asks
    analytic = crowd.pair_error_rate()
    # ~4.6 sigma of a binomial at p≈0.1 over 20k draws is under 0.01
    assert abs(empirical - analytic) < 0.01, (empirical, analytic)
    assert crowd.n_asked == n_asks


def test_noisy_crowd_qualification_reduces_error():
    base = NoisyCrowd(error_rate=0.1, qualification=False)
    qual = NoisyCrowd(error_rate=0.1, qualification=True)
    assert qual.pair_error_rate() < base.pair_error_rate()


# ---------------------------------------------------------------------------
# CrowdGateway transport
# ---------------------------------------------------------------------------
def test_gateway_immediate_mode_batches_and_returns_all():
    gw = CrowdGateway()
    pairs = _truth_pairs(6)
    crowd = PerfectCrowd()
    ticket = gw.post(rid=7, pairs=pairs, indices=[0, 2, 5], crowd=crowd)
    assert ticket.rid == 7 and ticket.indices == (0, 2, 5)
    assert gw.in_flight == 3
    answers = gw.poll()
    assert gw.in_flight == 0 and len(answers) == 3
    assert {a.index for a in answers} == {0, 2, 5}
    assert all(a.label == POS and a.rid == 7 and a.minutes == 0.0
               for a in answers)
    assert gw.poll() == []
    assert crowd.n_asked == 3  # the per-pair loop lives in the gateway


def test_gateway_latency_mode_worker_pool_and_clock():
    lat = LatencyModel(n_workers=2, mean_minutes=10.0, sigma=0.5, seed=1)
    gw = CrowdGateway(latency=lat)
    pairs = _truth_pairs(5)
    gw.post(rid=0, pairs=pairs, indices=list(range(5)), crowd=PerfectCrowd())
    # only n_workers assignments can run at once; the rest wait
    assert gw.in_flight == 5
    got, last_t = [], 0.0
    while gw.in_flight:
        answers = gw.poll()
        assert answers, "in-flight pairs must eventually complete"
        for a in answers:
            assert a.minutes >= last_t - 1e-9  # monotonic simulated clock
            last_t = a.minutes
            got.append(a.index)
    assert sorted(got) == list(range(5))
    assert gw.now_minutes > 0.0
    assert gw.n_posted == gw.n_answered == 5


def test_gateway_nf_steers_low_likelihood_first():
    """With one worker, nf=True must process pairs in ascending likelihood
    order regardless of posting order."""
    lat = LatencyModel(n_workers=1, mean_minutes=5.0, sigma=0.1, seed=2)
    gw = CrowdGateway(latency=lat, nf=True)
    pairs = _truth_pairs(4)   # likelihood descending in index
    gw.post(rid=0, pairs=pairs, indices=[0, 1, 2, 3], crowd=PerfectCrowd())
    seen = []
    while gw.in_flight:
        seen.extend(a.index for a in gw.poll())
    assert seen == [3, 2, 1, 0]  # lowest likelihood first


# ---------------------------------------------------------------------------
# NoisyCrowd end to end through the service: degraded but bounded
# ---------------------------------------------------------------------------
def test_join_service_noisy_quality_degraded_but_bounded():
    from repro.data.entities import make_session_pairsets
    from repro.serve.join_service import JoinService

    ps = make_session_pairsets(1, seed=11, n_objects=(40, 41),
                               n_pairs=(160, 161), n_entities=8,
                               likelihood=(0.75, 0.35, 0.2))[0]

    svc = JoinService(lanes=2)
    rid_perfect = svc.submit(ps, PerfectCrowd())
    rid_noisy = svc.submit(ps, NoisyCrowd(error_rate=0.05, seed=4))
    res = svc.run()
    q_perfect = res[rid_perfect].quality
    q_noisy = res[rid_noisy].quality
    assert q_perfect.f_measure == 1.0
    # noise degrades quality, but a 5% per-assignment error under 3-way
    # majority vote must stay usable, not collapse
    assert q_noisy.f_measure <= 1.0
    assert q_noisy.f_measure >= 0.6, q_noisy
    assert res[rid_noisy].n_crowdsourced + res[rid_noisy].n_deduced \
        == len(ps)


# ---------------------------------------------------------------------------
# §15 WorkerModel: EM estimates converge to the simulated worker pool, and
# EM aggregation labels no worse than majority at equal assignments.
# ---------------------------------------------------------------------------
def _random_truth_pairs(m: int, seed: int) -> PairSet:
    rng = np.random.default_rng(seed)
    u = np.arange(m, dtype=np.int32)
    truth = rng.random(m) < 0.5
    lik = np.linspace(0.9, 0.1, m).astype(np.float32)
    return PairSet(u, u + m, lik, truth, n_objects=2 * m)


def _pool_ballots(seed: int, m: int = 400):
    """One heterogeneous pool labeling ``m`` pairs: returns the crowd, the
    pairs, the fitted WorkerModel, and (em_correct, majority_correct)."""
    crowd = NoisyCrowd(error_rate=0.2, n_assignments=3, qualification=False,
                       seed=seed, n_workers=12, worker_concentration=3.0)
    pairs = _random_truth_pairs(m, seed)
    wm = WorkerModel()
    em_ok = maj_ok = 0
    for i in range(m):
        ballot = crowd.ask_ballot(pairs, i)
        truth = POS if pairs.truth[i] else NEG
        em_ok += wm.record(ballot.votes, ballot.workers) == truth
        maj_ok += (ballot.label == MATCH) == bool(pairs.truth[i])
    wm.refit()
    return crowd, pairs, wm, em_ok, maj_ok


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_worker_model_estimates_converge_to_simulated_errors(seed):
    """After a few hundred ballots + refit, the per-worker error estimates
    must recover the NoisyCrowd's drawn worker_errors: small mean absolute
    error and near-perfect worker ranking (the signal cluster routing and
    weighted voting actually consume)."""
    crowd, _, wm, _, _ = _pool_ballots(seed)
    true_errs = crowd.worker_errors
    est = np.array([wm.error_rate(w) for w in range(crowd.n_workers)])
    # estimates clip at max_error=0.45, so near-coin-flip workers contribute
    # an irreducible ~0.04; measured MAE is 0.03-0.05 across these seeds
    assert np.abs(est - true_errs).mean() < 0.08, (true_errs, est)
    rank_true = np.argsort(np.argsort(true_errs))
    rank_est = np.argsort(np.argsort(est))
    assert np.corrcoef(rank_true, rank_est)[0, 1] > 0.8
    # the routing queries agree: best_workers leads with truly good workers
    best = wm.best_workers(limit=3)
    assert best and all(true_errs[w] < float(np.median(true_errs))
                        for w in best)


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_em_aggregation_no_worse_than_majority_equal_assignments(seed):
    """Tentpole acceptance: on a heterogeneous pool, reliability-weighted
    aggregation must label no worse than naive majority from the SAME
    ballots (equal assignments, equal spend).  Measured margin is +12..+17
    correct out of 400 on these seeds."""
    _, _, _, em_ok, maj_ok = _pool_ballots(seed)
    assert em_ok >= maj_ok, (em_ok, maj_ok)


def test_worker_model_uninformed_reduces_to_majority():
    """With no history every weight is equal, so aggregation must reduce to
    the unweighted majority — EM can only start helping once it has
    evidence, never hurt before."""
    wm = WorkerModel()
    assert wm.aggregate((POS, POS, NEG), (0, 1, 2)) == POS
    assert wm.aggregate((NEG, NEG, POS), (3, 4, 5)) == NEG


def test_worker_model_rejects_uninformative_prior():
    with pytest.raises(ValueError, match="prior_error"):
        WorkerModel(prior_error=0.5)


# ---------------------------------------------------------------------------
# §15 requery routing: escalations go to fresh workers; exhaustion keeps
# the §9 semantics (max_requeries, then the graph outvotes).
# ---------------------------------------------------------------------------
def test_requery_routes_to_fresh_workers():
    crowd = NoisyCrowd(error_rate=0.2, n_assignments=3, qualification=False,
                       seed=3, n_workers=20)
    pairs = _truth_pairs(2)
    gw = CrowdGateway(aggregation="em")
    gw.post(0, pairs, [0], crowd)
    (first,) = gw.poll()
    seen = set(gw.seen_workers(0, 0))
    assert seen == set(first.workers) and len(seen) == 3
    ticket, exhausted = gw.requery(0, pairs, [0], crowd)
    assert ticket.indices == (0,) and exhausted == []
    (second,) = gw.poll()
    # 5 fresh workers: the pool has 17 unseen, so zero overlap is required
    assert second.n_assignments == 5
    assert not seen & set(second.workers), (seen, second.workers)
    assert set(gw.seen_workers(0, 0)) == seen | set(second.workers)
    # exhaustion semantics unchanged by worker routing: attempt 2 is past
    # max_requeries=1, so the pair comes back exhausted, not re-posted
    ticket2, exhausted2 = gw.requery(0, pairs, [0], crowd)
    assert ticket2.indices == () and exhausted2 == [0]
    assert gw.in_flight == 0


def test_requery_small_pool_tops_up_without_deadlock():
    """When fewer unseen workers remain than the escalated ballot needs,
    seen workers top the ballot up — escalation must never deadlock on a
    small pool."""
    crowd = NoisyCrowd(error_rate=0.2, n_assignments=3, qualification=False,
                       seed=4, n_workers=5)
    pairs = _truth_pairs(1)
    gw = CrowdGateway()
    gw.post(0, pairs, [0], crowd)
    (first,) = gw.poll()
    gw.requery(0, pairs, [0], crowd)
    (second,) = gw.poll()
    assert second.n_assignments == 5  # full escalated ballot despite pool
    # the 2 unseen workers must all serve before any repeat
    unseen = set(range(5)) - set(first.workers)
    assert unseen <= set(second.workers)


# ---------------------------------------------------------------------------
# §15 cluster tasks: decoding a cluster task must be conflict-screen
# identical to submitting the same pairs individually.
# ---------------------------------------------------------------------------
def _cluster_vs_pairs_parity(seed: int) -> None:
    """One random world, answered twice from identical truth: once as one
    cluster task, once as individual pair posts.  Labels, conflict masks,
    and gateway counters must agree (PerfectCrowd: both channels emit truth,
    so the conflict screen sees the same consistent stream)."""
    import itertools

    import jax.numpy as jnp

    from repro.core import (UNKNOWN, make_session_state,
                            session_fold_answers)

    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 12))
    ent = rng.integers(0, 3, n)
    all_e = list(itertools.combinations(range(n), 2))
    m = int(rng.integers(3, min(20, len(all_e)) + 1))
    sel = rng.permutation(len(all_e))[:m]
    u = np.array([all_e[i][0] for i in sel], np.int32)
    v = np.array([all_e[i][1] for i in sel], np.int32)
    truth = ent[u] == ent[v]
    pairs = PairSet(u, v, np.linspace(0.9, 0.1, m).astype(np.float32),
                    truth, n_objects=n)

    def fold(answers):
        state = make_session_state(u, v, n)
        upd = np.full(m, UNKNOWN, np.int32)
        for a in answers:
            upd[a.index] = a.label
        state, _ = session_fold_answers(state, jnp.asarray(upd))
        return (np.asarray(state.labels).copy(),
                np.asarray(state.conflicts).copy())

    gw_cluster = CrowdGateway()
    gw_cluster.post_cluster(0, pairs, list(range(m)), PerfectCrowd(),
                            cents=1.0, n_assignments=2)
    cluster_answers = gw_cluster.poll()
    gw_pairs = CrowdGateway()
    gw_pairs.post(0, pairs, list(range(m)), PerfectCrowd())
    pair_answers = gw_pairs.poll()

    assert {a.index for a in cluster_answers} == set(range(m))
    assert {(a.index, a.label) for a in cluster_answers} \
        == {(a.index, a.label) for a in pair_answers}
    labels_c, conflicts_c = fold(cluster_answers)
    labels_p, conflicts_p = fold(pair_answers)
    np.testing.assert_array_equal(labels_c, labels_p)
    np.testing.assert_array_equal(conflicts_c, conflicts_p)
    assert not conflicts_c.any()  # truth is transitive: nothing screened out
    # gateway accounting: all m verdicts agreed, none escalated
    assert gw_cluster.cluster_pairs(0) == m
    assert gw_cluster.n_posted == gw_pairs.n_posted == m


@pytest.mark.parametrize("seed", range(6))
def test_cluster_decode_matches_individual_pairs(seed):
    _cluster_vs_pairs_parity(seed)


@given(st.integers(0, 10**6))
def test_cluster_decode_matches_individual_pairs_property(seed):
    _cluster_vs_pairs_parity(seed)


def test_cluster_disagreement_escalates_to_pair_ballots():
    """A wrong single-worker partition is coherent — only a second
    assignment can catch it.  Disagreed verdicts must escalate to ordinary
    pair ballots so every covered index is answered exactly once."""
    crowd = NoisyCrowd(error_rate=0.35, n_assignments=3, qualification=False,
                       seed=2, n_workers=20)
    pairs = _truth_pairs(8)
    gw = CrowdGateway()
    gw.post_cluster(0, pairs, list(range(8)), crowd, cents=2.0,
                    n_assignments=2, pair_cents_per_assignment=0.1)
    answers = gw.drain()
    assert {a.index for a in answers} == set(range(8))  # each answered once
    agreed = [a for a in answers if a.n_assignments == 2]
    escalated = [a for a in answers if a.n_assignments == 3]
    assert len(agreed) + len(escalated) == 8
    assert escalated, "0.35-error partitions never disagreed — dead test"
    assert gw.cluster_pairs(0) == len(agreed)
    # escalations billed at the pair rate; agreed pairs rode the task price
    assert gw.spent_cents(0) == pytest.approx(2.0 + 0.3 * len(escalated))
    assert gw.assignments_posted(0) == 2 + 3 * len(escalated)


# ---------------------------------------------------------------------------
# columnar posts (DESIGN.md §8): a deterministic crowd's batch held as arrays
# must leave exactly what the per-pair ballot loop leaves
# ---------------------------------------------------------------------------
class _PerPair(PerfectCrowd):
    """A PerfectCrowd with its ballot hook overridden, so the gateway asks
    it pair by pair: the reference for the columnar path."""

    calls = 0

    def ask_ballot(self, *args, **kwargs):
        self.calls += 1
        return super().ask_ballot(*args, **kwargs)


def _gateway_script(seed: int, rate: float, columnar: bool):
    """Drive one gateway through random posts, per-pair posts, requeries,
    polls, seen-worker reads and snapshots; return what was observed.
    ``columnar`` gives the main crowd bulk answers; otherwise it is asked
    per pair.  A second crowd is always asked per pair."""
    from repro import obs

    rng = np.random.default_rng(seed)
    n = 48
    truth = rng.random(n) < 0.4
    pairs = PairSet(np.arange(n, dtype=np.int32),
                    np.arange(n, 2 * n, dtype=np.int32),
                    rng.random(n).astype(np.float32), truth, n_objects=2 * n)
    answers = PerfectCrowd().precomputed_answers(pairs)
    main = PerfectCrowd() if columnar else _PerPair()
    other = _PerPair()
    gw = CrowdGateway(max_requeries=2)
    seen: list = []
    bulk0 = obs.gateway_bulk_pairs.count

    def look():
        seen.append(("ledger", gw.in_flight, gw.n_posted, gw.n_votes,
                     gw.n_minority_votes, main.state_dict(),
                     other.state_dict(),
                     [(gw.spent_cents(r).hex(), gw.assignments_posted(r))
                      for r in range(3)]))

    for _ in range(40):
        op = rng.integers(7)
        rid = int(rng.integers(3))
        idx = rng.choice(n, size=int(rng.integers(0, 12)),
                         replace=bool(rng.integers(2)))
        if op <= 1:
            t = gw.post(rid, pairs, idx, main, cents_per_assignment=rate,
                        answers=answers[idx] if op == 1 else None)
            seen.append(("post", t))
        elif op == 2:
            seen.append(("post", gw.post(rid, pairs, list(idx), other,
                                         cents_per_assignment=rate)))
        elif op == 3:
            seen.append(("requery", gw.requery(rid, pairs, idx, main,
                                               cents_per_assignment=rate)))
        elif op == 4:
            seen.append(("poll", gw.poll()))
        elif op == 5:
            seen.append(("seen", [gw.seen_workers(rid, int(i))
                                  for i in idx]))
        else:
            seen.append(("state", gw.state_dict()))
        look()
    # a snapshot with posts in flight restores to the same answers
    back = CrowdGateway(max_requeries=2)
    back.load_state_dict(gw.state_dict())
    seen.append(("restored", back.drain(), back.state_dict()))
    seen.append(("drain", gw.drain(), gw.state_dict()))
    look()
    return seen, obs.gateway_bulk_pairs.count - bulk0


@pytest.mark.parametrize("rate", [0.1, 2.0])
@pytest.mark.parametrize("seed", range(4))
def test_columnar_post_matches_per_pair_loop(seed, rate):
    got, n_bulk = _gateway_script(seed, rate, columnar=True)
    want, n_bulk_ref = _gateway_script(seed, rate, columnar=False)
    assert n_bulk > 0 and n_bulk_ref == 0  # the two paths were both taken
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g == w


def test_columnar_spend_is_in_order_addition():
    """0.1 has no exact binary form: n in-order additions differ from
    ``0.1 * n`` and from a pairwise sum; the ledger keeps the loop's."""
    pairs = _truth_pairs(1000)
    gw = CrowdGateway()
    gw.post(0, pairs, np.arange(1000), PerfectCrowd(),
            cents_per_assignment=0.1)
    ref = 0.0
    for _ in range(1000):
        ref += 0.1
    assert gw.spent_cents(0).hex() == ref.hex()
    assert ref != 0.1 * 1000


@pytest.mark.parametrize("hook", ["ask", "ask_votes", "ask_ballot"])
def test_overridden_ballot_hooks_keep_the_per_pair_loop(hook):
    from repro import obs

    calls = []

    def spy(self, pairs, i, *args, **kwargs):
        calls.append(i)
        return getattr(PerfectCrowd, hook)(self, pairs, i, *args, **kwargs)

    crowd = type("Spy", (PerfectCrowd,), {hook: spy})()
    b0, p0 = obs.gateway_bulk_pairs.count, obs.gateway_ballot_pairs.count
    gw = CrowdGateway()
    gw.post(0, _truth_pairs(5), [4, 0, 2], crowd)
    assert calls == [4, 0, 2]
    assert obs.gateway_bulk_pairs.count == b0
    assert obs.gateway_ballot_pairs.count == p0 + 3
    assert [a.index for a in gw.drain()] == [4, 0, 2]


@pytest.mark.parametrize("gateway", [
    lambda: CrowdGateway(latency=LatencyModel(n_workers=3, seed=1)),
    lambda: CrowdGateway(aggregation="em"),
], ids=["latency", "em"])
def test_columnar_post_needs_immediate_majority(gateway):
    from repro import obs

    b0 = obs.gateway_bulk_pairs.count
    gw = gateway()
    crowd = PerfectCrowd()
    gw.post(0, _truth_pairs(6), range(6), crowd, cents_per_assignment=2.0)
    assert sorted(a.index for a in gw.drain()) == list(range(6))
    assert obs.gateway_bulk_pairs.count == b0 and crowd.n_asked == 6
    assert gw.spent_cents(0) == 12.0


def test_columnar_answers_must_align_with_indices():
    with pytest.raises(ValueError, match="align"):
        CrowdGateway().post(0, _truth_pairs(4), [0, 1, 2], PerfectCrowd(),
                            answers=np.array([POS, NEG], np.int32))


def test_drain_without_collect_consumes_and_counts():
    gw = CrowdGateway()
    pairs = _truth_pairs(5)
    gw.post(0, pairs, [0, 1, 2], PerfectCrowd())
    gw.post(1, pairs, [3], _PerPair())
    assert gw.in_flight == 4
    assert gw.drain(collect=False) == []
    assert gw.in_flight == 0 and gw.n_answered == 4 and gw.poll() == []
