"""Scale-out join pipeline (DESIGN.md §7, §8): sharded candidate generation
must match the single-device kernel, the batched multi-session engine must
match the per-session engine pair-for-pair, and the async gateway serving
path must beat the round barrier in simulated platform minutes."""
import itertools
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (NEG, POS, LatencyModel, NoisyCrowd, PerfectCrowd,
                        crowdsourced_join, engine_dispatches,
                        label_parallel_jax, label_parallel_jax_batch)
from repro.core.pairs import PairSet


def _random_sessions(seed: int, n_sessions: int = 6):
    """Randomized ragged join sessions with consistent ground truth."""
    rng = np.random.default_rng(seed)
    sessions, truths = [], []
    for _ in range(n_sessions):
        n = int(rng.integers(4, 16))
        ent = rng.integers(0, 4, n)
        all_e = list(itertools.combinations(range(n), 2))
        m = int(rng.integers(3, min(24, len(all_e)) + 1))
        sel = rng.permutation(len(all_e))[:m]
        u = np.array([all_e[i][0] for i in sel], np.int32)
        v = np.array([all_e[i][1] for i in sel], np.int32)
        truth = np.where(ent[u] == ent[v], POS, NEG).astype(np.int32)
        sessions.append((u, v, n))
        truths.append(truth)
    return sessions, truths


# ---------------------------------------------------------------------------
# batched multi-session engine vs per-session engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_batched_engine_matches_per_session(seed):
    sessions, truths = _random_sessions(seed)
    batch = label_parallel_jax_batch(
        sessions, lambda b, idx: truths[b][idx])
    for b, (u, v, n) in enumerate(sessions):
        labels, cs, rounds, n_conf = label_parallel_jax(
            u, v, n, lambda idx: truths[b][idx])
        bl, bcs, brounds, bconf = batch[b]
        np.testing.assert_array_equal(bl, labels)
        np.testing.assert_array_equal(bcs, cs)
        assert brounds == rounds
        assert bconf == n_conf == 0  # consistent truth never conflicts
        np.testing.assert_array_equal(bl, truths[b])  # and both are correct


def test_batched_engine_capacity_padding_is_inert():
    """Explicit capacities (stable jit shapes) must not change any result."""
    sessions, truths = _random_sessions(7)
    a = label_parallel_jax_batch(sessions, lambda b, idx: truths[b][idx])
    b = label_parallel_jax_batch(sessions, lambda b_, idx: truths[b_][idx],
                                 pair_capacity=64, object_capacity=32)
    for (la, ca, ra, fa), (lb, cb, rb, fb) in zip(a, b):
        np.testing.assert_array_equal(la, lb)
        np.testing.assert_array_equal(ca, cb)
        assert ra == rb
        assert fa == fb


# ---------------------------------------------------------------------------
# sharded pair scoring vs the single-device kernel (host-local mesh)
# ---------------------------------------------------------------------------
def test_sharded_pair_scores_matches_single_device():
    from repro.kernels.pair_scores.ops import pair_scores
    from repro.kernels.pair_scores.sharded import sharded_pair_scores
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(100, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(70, 32)), jnp.float32)
    mesh = make_host_mesh(1, 1)
    s1, c1 = pair_scores(a, b, 0.3, impl="interpret")
    s2, c2 = sharded_pair_scores(a, b, 0.3, mesh, impl="interpret")
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_sharded_candidates_exact_set_and_overflow_accounting():
    from repro.kernels.pair_scores.ops import pair_scores
    from repro.kernels.pair_scores.sharded import sharded_candidates
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    mesh = make_host_mesh(1, 1)
    s, _ = pair_scores(a, b, 0.4, impl="interpret")
    want = set(zip(*np.nonzero(np.asarray(s) >= 0.4)))
    cand = sharded_candidates(a, b, 0.4, mesh, impl="interpret")
    assert set(zip(cand.rows.tolist(), cand.cols.tolist())) == want
    assert cand.n_dropped == 0
    # scores come back with the candidates
    ref = np.asarray(s)
    for r, c, sc in zip(cand.rows, cand.cols, cand.scores):
        assert abs(ref[r, c] - sc) < 1e-6
    # capacity overflow is reported, never silent
    small = sharded_candidates(a, b, 0.4, mesh, capacity=3, impl="interpret")
    assert small.n_dropped == len(want) - len(small)
    with pytest.raises(ValueError):
        sharded_candidates(a, b, -0.1, mesh)  # padding would alias tau <= 0


SUB_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.kernels.pair_scores.ops import pair_scores
    from repro.kernels.pair_scores.sharded import (sharded_candidates,
                                                  sharded_pair_scores)

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(103, 32)), jnp.float32)  # ragged vs 4
    b = jnp.asarray(rng.normal(size=(66, 32)), jnp.float32)   # ragged vs 2
    mesh = make_host_mesh(4, 2)
    s1, c1 = pair_scores(a, b, 0.3, impl="interpret")
    s2, c2 = sharded_pair_scores(a, b, 0.3, mesh, impl="interpret")
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    cand = sharded_candidates(a, b, 0.3, mesh, impl="interpret")
    got = set(zip(cand.rows.tolist(), cand.cols.tolist()))
    want = set(zip(*np.nonzero(np.asarray(s1) >= 0.3)))
    assert got == want and cand.n_dropped == 0
    print("MESH_SHARDED_OK", len(cand))
""")


def test_sharded_pair_scores_8_device_mesh():
    """Same parity on a real 4x2 host mesh (subprocess sets XLA_FLAGS)."""
    r = subprocess.run([sys.executable, "-c", SUB_MESH], capture_output=True,
                       text=True, cwd=str(Path(__file__).parent.parent),
                       timeout=900)
    assert "MESH_SHARDED_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-2500:]


# ---------------------------------------------------------------------------
# JoinService: lane-batched sessions == single-session joins
# ---------------------------------------------------------------------------
def _session_pairsets(seed: int, n_sessions: int = 5):
    sessions, truths = _random_sessions(seed, n_sessions)
    out = []
    for (u, v, n), truth in zip(sessions, truths):
        P = len(u)
        lik = np.linspace(0.9, 0.2, P).astype(np.float32)
        out.append(PairSet(u, v, lik, truth == POS, n_objects=n))
    return out


@pytest.mark.parametrize("crowd_factory", [
    lambda: PerfectCrowd(),
    lambda: NoisyCrowd(error_rate=0.1, seed=5),
], ids=["perfect", "noisy"])
def test_join_service_matches_single_session(crowd_factory):
    from repro.serve.join_service import JoinService

    pairsets = _session_pairsets(11)
    svc = JoinService(lanes=2)  # fewer lanes than sessions -> refill path
    rids = [svc.submit(ps, crowd_factory()) for ps in pairsets]
    res = svc.run()
    assert set(res) == set(rids)
    for rid, ps in zip(rids, pairsets):
        ref = crowdsourced_join(ps, crowd_factory(), order="expected",
                                labeler="jax")
        got = res[rid]
        np.testing.assert_array_equal(got.labels, ref.labels)
        assert got.n_crowdsourced == ref.n_crowdsourced
        assert got.round_sizes == ref.batch_sizes
        assert got.n_hits == ref.n_hits
        assert got.cost_cents == ref.cost_cents
        # device-side fold counter agrees with the host round accounting
        assert got.fold_rounds == got.n_rounds


def test_fused_replay_posts_as_arrays_with_the_per_pair_ledger():
    """The fused path replays a PerfectCrowd's waves through the gateway's
    columnar post; a crowd whose ballot hook is overridden is asked pair by
    pair.  Both serve the same labels, rounds, spend (to the bit) and
    question counts."""
    from repro import obs
    from repro.serve.join_service import JoinService

    class PerPair(PerfectCrowd):
        def ask_ballot(self, *args, **kwargs):
            return super().ask_ballot(*args, **kwargs)

    def serve(crowd_cls):
        pairsets = _session_pairsets(11)
        svc = JoinService(lanes=2)
        crowds = [crowd_cls() for _ in pairsets]
        rids = [svc.submit(ps, c) for ps, c in zip(pairsets, crowds)]
        b0 = obs.gateway_bulk_pairs.count
        res = svc.run()
        return ([res[r] for r in rids], [c.n_asked for c in crowds],
                obs.gateway_bulk_pairs.count - b0)

    got, asked, bulk = serve(PerfectCrowd)
    want, asked_ref, bulk_ref = serve(PerPair)
    assert bulk == sum(int(r.crowdsourced.sum()) for r in got) > 0
    assert bulk_ref == 0 and asked == asked_ref
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.labels, w.labels)
        np.testing.assert_array_equal(g.crowdsourced, w.crowdsourced)
        assert g.round_sizes == w.round_sizes
        assert g.n_spent_cents.hex() == w.n_spent_cents.hex()


def test_join_service_streaming_submit_between_runs():
    from repro.serve.join_service import JoinService

    pairsets = _session_pairsets(13, n_sessions=4)
    svc = JoinService(lanes=3)
    first = svc.submit(pairsets[0], PerfectCrowd())
    svc.run()
    later = [svc.submit(ps, PerfectCrowd()) for ps in pairsets[1:]]
    res = svc.run()
    assert set(res) == {first, *later}  # results accumulate across runs
    for rid, ps in zip([first, *later], pairsets):
        ref = crowdsourced_join(ps, PerfectCrowd(), order="expected",
                                labeler="jax")
        np.testing.assert_array_equal(res[rid].labels, ref.labels)


def test_join_service_zero_pair_request():
    """A request whose machine phase found no candidates completes with an
    empty result instead of wedging the engine."""
    from repro.serve.join_service import JoinService

    svc = JoinService(lanes=2)
    empty = PairSet(np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, np.float32), np.zeros(0, bool), n_objects=4)
    r_empty = svc.submit(empty, PerfectCrowd())
    r_real = svc.submit(_session_pairsets(17, 1)[0], PerfectCrowd())
    res = svc.run()
    assert len(res[r_empty].labels) == 0
    assert res[r_empty].n_crowdsourced == 0 and res[r_empty].n_rounds == 0
    assert len(res[r_real].labels) > 0  # the real session still completes


def _latency_sessions(seed: int, n_sessions: int = 4):
    """Sessions whose likelihoods correlate with truth (the machine-phase
    assumption), so non-matching-first steering has something to steer on."""
    from repro.data.entities import make_session_pairsets

    return make_session_pairsets(n_sessions, seed=seed, n_objects=(12, 24),
                                 n_pairs=(20, 60))


def test_async_gateway_beats_round_barrier_sim_minutes():
    """Figure 16 semantics in the serving path (DESIGN.md §8): with the same
    latency-modeled platform, the event-driven ID/NF discipline must finish
    the workload in fewer simulated minutes than the round barrier, with
    identical final labels."""
    from repro.serve.join_service import JoinService

    pairsets = _latency_sessions(0)
    latency = lambda: LatencyModel(n_workers=6, mean_minutes=30.0, sigma=1.0,
                                   seed=7)
    svc_b = JoinService(lanes=2, latency=latency(), async_mode=False)
    rids_b = [svc_b.submit(ps, PerfectCrowd()) for ps in pairsets]
    res_b = svc_b.run()
    barrier_min = max(res_b[r].sim_minutes for r in rids_b)

    svc_a = JoinService(lanes=2, latency=latency(), async_mode=True, nf=True)
    rids_a = [svc_a.submit(ps, PerfectCrowd()) for ps in pairsets]
    res_a = svc_a.run()
    async_min = max(res_a[r].sim_minutes for r in rids_a)

    for rb, ra, ps in zip(rids_b, rids_a, pairsets):
        np.testing.assert_array_equal(res_b[rb].labels, ps.truth)
        np.testing.assert_array_equal(res_a[ra].labels, ps.truth)
    assert barrier_min > 0 and async_min > 0
    assert async_min < barrier_min, (async_min, barrier_min)


def test_incremental_service_dispatches_less_than_from_scratch():
    """Per round, the persistent-state serving path must issue fewer
    host->device dispatches than an old-style from-scratch round loop over
    the same sessions (DESIGN.md §8).  The from-scratch baseline is the
    benchmark's, so the test asserts exactly what the bench reports."""
    from benchmarks.bench_join_service import _run_from_scratch_rounds
    from repro.serve.join_service import JoinService

    # uniform size range so all lanes share one (p_cap, n_cap) bucket group
    from repro.data.entities import make_session_pairsets
    pairsets = make_session_pairsets(4, seed=19, n_objects=(10, 16),
                                     n_pairs=(20, 31), n_entities=4)

    # incremental: the JoinService path
    engine_dispatches.reset()
    svc = JoinService(lanes=4)
    rids = [svc.submit(ps, PerfectCrowd()) for ps in pairsets]
    res = svc.run()
    rounds_inc = max(res[r].n_rounds for r in rids)
    d_inc = engine_dispatches.count

    # from-scratch: the benchmark's pre-§8 round loop (re-pack + rebuild)
    from repro.core import get_order
    perms = [get_order(ps, "expected") for ps in pairsets]
    ordered = [ps.take(p) for ps, p in zip(pairsets, perms)]
    sessions = [(np.asarray(o.u), np.asarray(o.v), o.n_objects)
                for o in ordered]
    truths = [np.where(o.truth, POS, NEG).astype(np.int32) for o in ordered]
    labels_fs, _, dispatches_fs = _run_from_scratch_rounds(sessions, truths)
    rounds_fs, d_fs = len(dispatches_fs), sum(dispatches_fs)

    assert rounds_fs > 0 and rounds_inc > 0
    # normalize per round: the incremental path must dispatch strictly less
    assert d_inc / max(rounds_inc, 1) < d_fs / rounds_fs, (d_inc, d_fs)
    # and both paths agree on the labels
    for b, (rid, ps) in enumerate(zip(rids, pairsets)):
        want = np.zeros(len(ps), bool)
        want[perms[b]] = labels_fs[b, :len(ps)] == POS
        np.testing.assert_array_equal(res[rid].labels, want)


def test_submit_embeddings_capacity_overflow_reports_details():
    """Candidate overflow must surface the observed drop count and the
    per-device capacity actually used, not an opaque error."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(5)
    cents = rng.normal(size=(4, 16))
    ids_a = rng.integers(0, 4, 24)
    ids_b = rng.integers(0, 4, 20)
    ea = jnp.asarray(cents[ids_a] + 0.1 * rng.normal(size=(24, 16)),
                     jnp.float32)
    eb = jnp.asarray(cents[ids_b] + 0.1 * rng.normal(size=(20, 16)),
                     jnp.float32)
    svc = JoinService(lanes=1)
    mesh = make_host_mesh(1, 1)
    with pytest.raises(RuntimeError, match=r"dropped at per-device capacity 2"):
        svc.submit_embeddings(ea, eb, 0.5, mesh, capacity=2,
                              impl="interpret")


# ---------------------------------------------------------------------------
# §15 mixed scheduling: cluster tasks + EM aggregation through the service
# ---------------------------------------------------------------------------
def _cluster_sessions():
    from repro.data.entities import make_session_pairsets

    return make_session_pairsets(3, seed=21, n_objects=(25, 35),
                                 n_pairs=(120, 200), n_entities=4,
                                 likelihood=(0.7, 0.4, 0.25))


def test_join_service_cluster_tasks_perfect_exact_and_cheaper():
    """With a perfect crowd, mixed scheduling must stay exact (agreed
    partitions decode to truth) while spending strictly less than pairs-only
    — the information-per-cent rule only posts tasks that beat the pair
    rate."""
    from repro.serve.join_service import JoinService

    pairsets = _cluster_sessions()
    spent = {}
    for tag, kw in [("pairs", {}),
                    ("mixed", {"cluster_tasks": True, "cluster_size": 8})]:
        svc = JoinService(lanes=2, **kw)
        rids = [svc.submit(ps, PerfectCrowd()) for ps in pairsets]
        res = svc.run()
        for rid, ps in zip(rids, pairsets):
            np.testing.assert_array_equal(res[rid].labels == POS, ps.truth)
            assert res[rid].n_crowdsourced + res[rid].n_deduced == len(ps)
        spent[tag] = sum(res[r].n_spent_cents for r in rids)
        n_tasks = sum(res[r].n_cluster_tasks for r in rids)
        n_cpairs = sum(res[r].n_cluster_pairs for r in rids)
        if tag == "mixed":
            assert n_tasks > 0 and n_cpairs > n_tasks  # multi-pair harvest
            assert sum(res[r].n_cluster_cents for r in rids) > 0
        else:
            assert n_tasks == n_cpairs == 0  # defaults untouched
    assert spent["mixed"] < spent["pairs"], spent


def test_join_service_em_cluster_noisy_pool_quality_and_cost():
    """EM + cluster tasks over a heterogeneous pool must finish fully
    labeled and transitively consistent, at no-worse quality and lower
    spend than the majority pairs-only baseline (measured: F 0.89 vs 0.86,
    670c vs 696c on these seeds)."""
    from repro.core import transitively_consistent
    from repro.serve.join_service import JoinService

    pairsets = _cluster_sessions()

    def crowd(k):
        return NoisyCrowd(error_rate=0.15, n_assignments=3, seed=30 + k,
                          n_workers=25, worker_concentration=3.0,
                          qualification=False)

    stats = {}
    for tag, kw in [("majority", {}),
                    ("mixed", {"aggregation": "em", "cluster_tasks": True})]:
        svc = JoinService(lanes=2, **kw)
        rids = [svc.submit(ps, crowd(k)) for k, ps in enumerate(pairsets)]
        res = svc.run()
        for rid, ps in zip(rids, pairsets):
            assert res[rid].n_crowdsourced + res[rid].n_deduced == len(ps)
            assert transitively_consistent(ps, res[rid].labels)
        stats[tag] = (
            float(np.mean([res[r].quality.f_measure for r in rids])),
            sum(res[r].n_spent_cents for r in rids))
    assert stats["mixed"][0] >= stats["majority"][0], stats
    assert stats["mixed"][1] < stats["majority"][1], stats


def test_cluster_tasks_disable_fused_path_cleanly(monkeypatch):
    """The §13 megabatch cannot consult live host-side coverage, so mixed
    scheduling must stand the fused driver down entirely — and still finish
    exact.  The default config on the same workload must keep using it."""
    from repro.serve.join_service import JoinService

    pairsets = _cluster_sessions()
    calls = []
    orig = JoinService._drive_fused
    monkeypatch.setattr(
        JoinService, "_drive_fused",
        lambda self, *a, **kw: calls.append(1) or orig(self, *a, **kw))

    svc = JoinService(lanes=2, cluster_tasks=True)
    rids = [svc.submit(ps, PerfectCrowd()) for ps in pairsets]
    res = svc.run()
    assert not calls, "fused driver ran with cluster tasks enabled"
    for rid, ps in zip(rids, pairsets):
        np.testing.assert_array_equal(res[rid].labels == POS, ps.truth)

    svc2 = JoinService(lanes=2)
    rids2 = [svc2.submit(ps, PerfectCrowd()) for ps in pairsets]
    svc2.run()
    assert calls, "default config no longer exercises the fused path"


def test_cluster_constructor_validation():
    from repro.serve.join_service import JoinService

    with pytest.raises(ValueError, match="cluster_size"):
        JoinService(cluster_size=2)
    with pytest.raises(ValueError, match="cluster_assignments"):
        JoinService(cluster_assignments=0)
    with pytest.raises(ValueError, match="aggregation"):
        JoinService(aggregation="bayes")


def test_join_service_embeddings_end_to_end():
    from repro.launch.mesh import make_host_mesh
    from repro.serve.join_service import JoinService

    rng = np.random.default_rng(3)
    n_ent = 12
    cents = rng.normal(size=(n_ent, 16))
    ea_ids = rng.integers(0, n_ent, 40)
    eb_ids = rng.integers(0, n_ent, 35)
    ea = jnp.asarray(cents[ea_ids] + 0.15 * rng.normal(size=(40, 16)),
                     jnp.float32)
    eb = jnp.asarray(cents[eb_ids] + 0.15 * rng.normal(size=(35, 16)),
                     jnp.float32)
    svc = JoinService(lanes=2)
    mesh = make_host_mesh(1, 1)
    rid = svc.submit_embeddings(
        ea, eb, 0.8, mesh, crowd=PerfectCrowd(),
        truth_fn=lambda r, c: ea_ids[r] == eb_ids[c], impl="interpret")
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0
    assert res.n_crowdsourced + res.n_deduced == len(res.labels)
    assert res.n_deduced > 0  # transitivity actually saved questions
