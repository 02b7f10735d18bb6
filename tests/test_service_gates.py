"""Count gates on the join path, at tiny size, through the program's own
API: the round engine amortizes its dispatches (DESIGN.md §13), the
adaptive order saves questions (§10), streaming saves questions over
resubmits (§11), the plan layer's cache, pushdown and leg order pay off
(§14), and EM aggregation and cluster tasks keep quality and cut cost
(§15).  Every number here is a count or a quality, never a timing."""
import itertools

import numpy as np
import pytest

from repro.core import (NEG, POS, UNKNOWN, CostModel, NoisyCrowd, PairSet,
                        PerfectCrowd, crowdsourced_join, engine_dispatches,
                        make_session_state_batch, pack_sessions,
                        session_run_rounds_batch)
from repro.data.entities import make_paper_dataset, make_session_pairsets
from repro.serve.join_service import JoinService


# -- §13: the round engine ----------------------------------------------------
def test_round_engine_amortizes_under_one_dispatch_per_round():
    """Sixteen lanes advanced to the end by ``session_run_rounds_batch``,
    eight rounds a dispatch, with the crowd's answers uploaded once: fewer
    dispatches than labelling rounds, and every lane labelled exactly."""
    sets = make_session_pairsets(16, seed=0, n_objects=(10, 16),
                                 n_pairs=(20, 31), n_entities=None)
    U, V, labels0, valid, n = pack_sessions(
        [(ps.u, ps.v, ps.n_objects) for ps in sets])
    state = make_session_state_batch(U, V, labels0, n)
    answers = np.full(U.shape, UNKNOWN, np.int32)
    for b, ps in enumerate(sets):
        answers[b, :len(ps)] = np.where(ps.truth, POS, NEG)
    engine_dispatches.reset()
    engine_dispatches.add()  # the answers' one upload
    rounds = np.zeros(len(sets), np.int64)
    while True:
        state, _, _, done, _ = session_run_rounds_batch(state, answers, 8)
        rounds += np.asarray(done)
        labels = np.asarray(state.labels)
        if not (labels[valid] == UNKNOWN).any():
            break
    dispatches = engine_dispatches.count
    engine_dispatches.reset()
    assert rounds.max() >= 3
    assert dispatches / rounds.max() < 1.0, (dispatches, rounds.max())
    for b, ps in enumerate(sets):
        np.testing.assert_array_equal(labels[b, :len(ps)] == POS, ps.truth)


# -- §10: labelling orders through the service --------------------------------
@pytest.fixture(scope="module")
def cora_orders():
    """One Cora-like session (300 records, candidates at >= 0.3) served
    under each labelling order with a perfect crowd."""
    cand = make_paper_dataset(seed=0, n_records=300).pairs.above(0.3)
    out = {}
    for order in ("expected", "adaptive", "random"):
        svc = JoinService(lanes=1, order=order)
        rid = svc.submit(cand, PerfectCrowd())
        out[order] = svc.run()[rid]
    return cand, out


def test_adaptive_order_crowdsources_fewer_than_random(cora_orders):
    _, res = cora_orders
    assert res["adaptive"].n_crowdsourced < res["random"].n_crowdsourced


def test_adaptive_order_crowdsources_no_more_than_expected(cora_orders):
    _, res = cora_orders
    assert res["adaptive"].n_crowdsourced <= res["expected"].n_crowdsourced


def test_every_order_labels_the_truth(cora_orders):
    """The order moves the spend, never the answer."""
    cand, res = cora_orders
    for order, r in res.items():
        np.testing.assert_array_equal(r.labels, cand.truth, err_msg=order)
        assert r.n_crowdsourced + r.n_deduced == len(cand)


def test_sequential_adaptive_beats_expected_on_cora():
    """Through the sequential oracle every pick re-ranks, so the posterior
    refresh wins outright over the static expected order on the full
    Cora-like dataset (1,571 against 1,611 questions)."""
    cand = make_paper_dataset(seed=0).pairs.above(0.3)
    got = {order: crowdsourced_join(cand, PerfectCrowd(), order=order,
                                    labeler="sequential").n_crowdsourced
           for order in ("expected", "adaptive")}
    assert got["adaptive"] < got["expected"], got


# -- §11: streaming against resubmitting --------------------------------------
def _epochs(ps: PairSet, k: int):
    """``k`` contiguous arrival epochs of a session's pairs."""
    bounds = np.linspace(0, len(ps), k + 1).astype(int)
    return [PairSet(ps.u[a:b], ps.v[a:b], ps.likelihood[a:b], ps.truth[a:b])
            for a, b in zip(bounds, bounds[1:])]


def test_streaming_saves_crowd_questions_over_resubmitting():
    """Keeping a join fresh across three arrival epochs: ``submit_stream``
    pays each pair once, where resubmitting everything seen so far after
    every epoch pays again for what was already labelled."""
    sets = make_session_pairsets(2, seed=2, n_objects=(20, 30),
                                 n_pairs=(60, 90))
    svc = JoinService(lanes=2)
    rids = [svc.submit_stream(_epochs(ps, 3), PerfectCrowd()) for ps in sets]
    res = svc.run()
    streamed = sum(res[r].n_crowdsourced for r in rids)
    resubmitted = 0
    for ps in sets:
        seen = None
        for chunk in _epochs(ps, 3):
            seen = chunk if seen is None else seen.concat(chunk)
            again = JoinService(lanes=1)
            rid = again.submit(seen, PerfectCrowd())
            resubmitted += again.run()[rid].n_crowdsourced
    for r, ps in zip(rids, sets):
        np.testing.assert_array_equal(res[r].labels, ps.truth)
    assert 0 < streamed < resubmitted, (streamed, resubmitted)


# -- §14: the plan layer ------------------------------------------------------
def _catalogs(seed, sizes, n_ent=12, dim=16):
    from repro.plan import Collection

    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_ent, dim))
    out = []
    for name, n in zip("abcde", sizes):
        ids = rng.integers(0, n_ent, n)
        emb = (cents[ids] + 0.05 * rng.normal(size=(n, dim))
               ).astype(np.float32)
        out.append(Collection(name, emb,
                              attrs={"sku": np.arange(n),
                                     "price": rng.integers(5, 100, n),
                                     "region": ids % 3},
                              entities=ids))
    return out


def _filtered_join(colls):
    from repro.plan import Cmp, Filter, MultiJoin, Scan

    join = MultiJoin([Scan(c) for c in colls], threshold=0.80)
    return Filter(Cmp(f"{colls[0].name}.price", "<", 70),
                  Filter(Cmp(f"{colls[1].name}.region", "==", 0), join))


def test_filtered_repeat_query_over_the_cache_saves_questions():
    """The same filtered three-way join twice over one ``ClusterCache``: the
    warm run asks at most 60 % of the cold run's questions, pays no more,
    and returns the same result."""
    from repro.plan import ClusterCache, PlanExecutor

    plan = _filtered_join(_catalogs(3, (24, 20, 18)))
    cache = ClusterCache()
    cold = PlanExecutor(cache=cache).execute(plan)
    warm = PlanExecutor(cache=cache).execute(plan)
    assert cold.n_crowdsourced > 0
    assert warm.n_crowdsourced <= 0.6 * cold.n_crowdsourced
    assert warm.spent_cents <= cold.spent_cents
    assert warm.signature() == cold.signature()


def test_filter_pushdown_sends_strictly_fewer_candidates():
    from repro.plan import ClusterCache, PlanExecutor

    plan = _filtered_join(_catalogs(4, (24, 20, 18)))
    raw = PlanExecutor(cache=ClusterCache(),
                       optimize_plans=False).execute(plan)
    opt = PlanExecutor(cache=ClusterCache()).execute(plan)
    assert opt.signature() == raw.signature()
    assert opt.n_candidates < raw.n_candidates, (opt.n_candidates,
                                                 raw.n_candidates)


def test_greedy_leg_order_costs_no_more_than_the_worst_order():
    """The optimizer's leg order of a four-way join, priced by its own
    sampled-selectivity model against every enumerated order."""
    from repro.plan import MultiJoin, Scan, expected_crowd_cost, optimize
    from repro.plan.optimizer import _pair_selectivity, _sample_rows

    colls = _catalogs(5, (24, 20, 18, 16))
    opt = optimize(MultiJoin([Scan(c) for c in colls], threshold=0.80))
    names = [c.name for c in colls]
    order = [names.index(next(iter(kid.collections())))
             for kid in opt.children()]
    assert sorted(order) == list(range(4))
    sampled = [_sample_rows(c.embeddings, np.ones(len(c), bool), 64, i)
               for i, c in enumerate(colls)]
    sel = np.zeros((4, 4))
    for i, j in itertools.combinations(range(4), 2):
        sel[i, j] = sel[j, i] = _pair_selectivity(sampled[i], sampled[j],
                                                  0.80)
    sizes = [len(c) for c in colls]
    costs = [expected_crowd_cost(sizes, sel, list(p))
             for p in itertools.permutations(range(4))]
    greedy = expected_crowd_cost(sizes, sel, order)
    assert greedy <= max(costs)
    assert max(costs) > min(costs)   # the order matters on this workload


# -- §15: worker quality on the Cora-like data --------------------------------
# the service's cents per resolved pair before EM aggregation and cluster
# tasks existed (``crowd_cents_per_resolved_pair`` of BENCH_join.json's
# trajectory): the bar mixed scheduling was built to clear
PAIRS_ONLY_CENTS_PER_PAIR = 0.017000478138447645


@pytest.fixture(scope="module")
def worker_quality():
    """One 400-record Cora-like session over one heterogeneous 30-worker
    pool, billed at a 20-pair HIT's per-pair quantum, under three serving
    configurations."""
    cost = CostModel()
    quantum = cost.cents_per_assignment / cost.pairs_per_hit
    ds = make_paper_dataset(seed=0, n_records=400)
    pairs = ds.pairs.above(0.3)
    configs = {"majority": {}, "em": {"aggregation": "em"},
               "mixed": {"aggregation": "em", "cluster_tasks": True,
                         "cluster_size": 8}}
    out = {}
    for name, kw in configs.items():
        svc = JoinService(lanes=1, **kw)
        rid = svc.submit(pairs, NoisyCrowd(error_rate=0.1, n_assignments=3,
                                           seed=7, n_workers=30,
                                           worker_concentration=3.0,
                                           qualification=False),
                         cost_per_assignment=quantum,
                         total_true_matches=ds.total_true_matches)
        r = svc.run()[rid]
        out[name] = {"f": r.quality.f_measure, "cents": r.n_spent_cents,
                     "cpp": r.n_spent_cents / len(pairs),
                     "tasks": r.n_cluster_tasks}
    return out


def test_em_aggregation_spends_what_majority_spends(worker_quality):
    """Same ballots, same assignments: EM only changes which label they
    collapse to, so spend stays within 2 %."""
    em, maj = worker_quality["em"], worker_quality["majority"]
    assert abs(em["cents"] - maj["cents"]) <= 0.02 * maj["cents"]


def test_em_aggregation_labels_no_worse_than_majority(worker_quality):
    assert worker_quality["em"]["f"] >= worker_quality["majority"]["f"]


def test_mixed_scheduling_cheaper_than_majority_at_its_quality(
        worker_quality):
    mixed, maj = worker_quality["mixed"], worker_quality["majority"]
    assert mixed["cpp"] < maj["cpp"]
    assert mixed["f"] >= maj["f"] - 0.005


def test_mixed_scheduling_beats_the_pairs_only_spend(worker_quality):
    assert worker_quality["mixed"]["cpp"] < PAIRS_ONLY_CENTS_PER_PAIR


def test_mixed_scheduling_posts_cluster_tasks(worker_quality):
    assert worker_quality["mixed"]["tasks"] > 0
    assert worker_quality["majority"]["tasks"] == 0
