"""The service's spans and counters (``repro.obs``): off by default and
silent, nested with parents and request ids when on, counters that count
what they did before, and spans on the profiler's clock."""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import PerfectCrowd
from repro.data.entities import make_session_pairsets
from repro.serve.join_service import JoinService


@pytest.fixture(autouse=True)
def spans_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _serve(budgeted: bool):
    """Two lanes, two small sessions.  A budget on the second session takes
    the whole wave off the fused engine onto the per-round ``_step``."""
    pss = make_session_pairsets(2, seed=5, n_objects=(10, 16),
                                n_pairs=(20, 31), n_entities=4)
    svc = JoinService(lanes=2)
    rids = [svc.submit(pss[0], PerfectCrowd()),
            svc.submit(pss[1], PerfectCrowd(),
                       budget_cents=1000.0 if budgeted else None)]
    return rids, svc.run()


def test_off_records_nothing_and_makes_no_annotation(monkeypatch):
    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert obs.span("join.a") is obs.span("join.b", rid=3)
    _serve(budgeted=False)
    _serve(budgeted=True)
    assert obs.spans() == [] and made == []
    obs.enable()
    with obs.span("join.a"):
        pass
    assert made == ["join.clock", "join.a"]


def _by_id(spans):
    return {s.id: s for s in spans}


@pytest.mark.parametrize("budgeted", [False, True], ids=["fused", "step"])
def test_spans_nest_with_parents_and_rids(budgeted):
    obs.enable()
    rids, res = _serve(budgeted)
    spans = obs.spans()
    by_id = _by_id(spans)
    names = collections.Counter(s.name for s in spans)
    assert all(n.startswith("join.") for n in names)
    for s in spans:
        assert s.start_ns <= s.end_ns
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns

    def parent_name(s):
        return by_id[s.parent].name if s.parent is not None else None

    submits = [s for s in spans if s.name == "join.submit"]
    assert [s.rid for s in submits] == rids and \
        all(parent_name(s) is None for s in submits)
    admits = [s for s in spans if s.name == "join.admit"]
    assert [(s.rid, parent_name(s)) for s in admits] == \
        [(r, "join.submit") for r in rids]
    (run,) = [s for s in spans if s.name == "join.run"]
    assert run.parent is None and run.rid is None
    for name in ("join.open_lane", "join.finalize"):
        got = [s for s in spans if s.name == name]
        assert sorted(s.rid for s in got) == sorted(rids)
        assert {parent_name(s) for s in got} == {"join.run"}
    loop = "join.step" if budgeted else "join.drive_fused"
    assert names[loop] >= 1 and names[
        "join.drive_fused" if budgeted else "join.step"] == 0
    assert {parent_name(s) for s in spans if s.name == loop} == {"join.run"}
    posts = [s for s in spans if s.name == "join.gateway.post"]
    drains = [s for s in spans if s.name == "join.gateway.drain"]
    assert posts and drains
    assert {s.rid for s in posts} == set(rids)
    assert {parent_name(s) for s in posts + drains} == {loop}
    assert all(s.rid is None for s in drains)
    dispatches = [s for s in spans if s.name == "join.engine_dispatch"]
    if budgeted:
        assert not dispatches
    else:
        assert dispatches and {parent_name(s) for s in dispatches} == \
            {"join.drive_fused"}
    # what was served is what spans-off serving gives
    obs.disable()
    rids_off, res_off = _serve(budgeted)
    for a, b in zip(rids, rids_off):
        np.testing.assert_array_equal(res[a].labels, res_off[b].labels)
        assert res[a].round_sizes == res_off[b].round_sizes


@pytest.mark.parametrize("budgeted,expected", [(False, 9), (True, 21)],
                         ids=["fused", "step"])
def test_engine_dispatches_counts_as_it_did(budgeted, expected):
    from repro.core import engine_dispatches as from_core
    from repro.core.jax_graph import engine_dispatches as from_graph

    assert from_core is from_graph is obs.engine_dispatches
    for on in (False, True):
        if on:
            obs.enable()
        d0 = obs.engine_dispatches.count
        _serve(budgeted)
        assert obs.engine_dispatches.count - d0 == expected


def test_host_syncs_per_fused_dispatch():
    """A fused dispatch reads five arrays back (new crowd mask, round
    sizes, rounds done, exit codes, labels); finalize reads two per lane
    (fold rounds, conflicts).  Lane open reads nothing without seeds."""
    obs.enable()
    s0 = obs.host_syncs.count
    _serve(budgeted=False)
    n_dispatch = sum(s.name == "join.engine_dispatch" for s in obs.spans())
    assert n_dispatch >= 1
    assert obs.host_syncs.count - s0 == 5 * n_dispatch + 2 * 2


def test_to_host_counts_each_read_once():
    s0 = obs.host_syncs.count
    x = jnp.arange(4)
    host = obs.to_host(x)
    assert isinstance(host, np.ndarray) and obs.host_syncs.count == s0 + 1
    a, b = obs.to_host((x, x + 1))
    assert np.array_equal(b, host + 1) and obs.host_syncs.count == s0 + 2
    already = np.ones(3)
    assert obs.to_host(already) is already
    assert obs.to_host((already, 1)) == (already, 1)
    assert obs.host_syncs.count == s0 + 2


def test_set_rid_reaches_the_spans_opened_inside():
    obs.enable()
    with obs.span("join.outer"):
        with obs.span("join.inner"):
            pass
        obs.set_rid(7)
    inner, outer = sorted(obs.spans(), key=lambda s: s.name)
    assert (outer.rid, inner.rid, inner.parent) == (7, 7, outer.id)


def _host_events(xplane):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    out = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("join."):
                    out[e.name].append(e.start_ns)
    return out


def test_spans_sit_on_the_profiler_clock(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.enable()
        _serve(budgeted=False)
        obs.disable()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
    events = _host_events(xplane)
    (clock,) = events.pop("join.clock")
    offset = clock - obs.anchor_ns()
    spans = collections.defaultdict(list)
    for s in obs.spans():
        spans[s.name].append(s.start_ns + offset)
    assert set(spans) == set(events)
    for name, starts in spans.items():
        got = sorted(events[name])
        assert len(got) == len(starts), name
        assert max(abs(a - b) for a, b in zip(sorted(starts), got)) < 1e6


def test_engine_programs_carry_stable_names():
    """Every jitted engine program runs as ``jit_engine_<step>``: the
    batched folds used to share the name ``jit_call``."""
    import repro.core.jax_graph as graph
    import repro.core.ordering as ordering
    from repro.core import make_session_state

    jitted = {id(v): v for m in (graph, ordering) for v in vars(m).values()
              if callable(getattr(v, "lower", None))
              and callable(getattr(v, "trace", None))}
    names = [v.__name__ for v in jitted.values()]
    assert len(names) >= 30 and len(set(names)) == len(names)
    assert all(n.startswith("engine_") for n in names), names
    one = make_session_state(np.array([0, 1]), np.array([1, 2]), 3)
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), one)
    updates = jnp.zeros((2, 2), jnp.int32)
    text = graph._session_fold_fast_batch_jit.lower(
        stacked, updates, keep_conflicts_published=False).as_text()
    assert "@jit_engine_fold_fast_batch" in text.splitlines()[0]


def test_machine_readback_span_sits_under_the_score_span():
    """``join.machine.readback`` covers the four reads of the per-device
    candidate buffers, inside ``join.machine.score``."""
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(20, 8)), jnp.float32)
    obs.enable()
    svc = JoinService(lanes=1)
    s0 = obs.host_syncs.count
    svc.submit_embeddings(a, b, 0.3, make_host_mesh(1, 1),
                          crowd=PerfectCrowd(),
                          truth_fn=lambda r, c: r == c, impl="interpret")
    assert obs.host_syncs.count - s0 == 4
    spans = obs.spans()
    by_id = _by_id(spans)
    (read,) = [s for s in spans if s.name == "join.machine.readback"]
    score = by_id[read.parent]
    assert score.name == "join.machine.score"
    assert by_id[score.parent].name == "join.machine"
    assert score.start_ns <= read.start_ns <= read.end_ns <= score.end_ns


@pytest.mark.parametrize("n_objects,wide", [(46340, 0), (46341, 1),
                                            (100000, 1)])
def test_wide_key_lanes_counts_lanes_opened_with_two_word_keys(n_objects,
                                                               wide):
    from repro.core.pairs import PairSet

    ps = PairSet(np.array([0, 1, 0], np.int32),
                 np.array([1, n_objects - 1, n_objects - 1], np.int32),
                 np.array([0.9, 0.8, 0.7], np.float32),
                 np.array([True, True, True]), n_objects=n_objects)
    svc = JoinService(lanes=1)
    rid = svc.submit(ps, PerfectCrowd())
    w0 = obs.wide_key_lanes.count
    res = svc.run()[rid]
    assert obs.wide_key_lanes.count - w0 == wide
    assert res.labels.all() and res.n_deduced == 1


@pytest.mark.parametrize("case", ["perfect_fused", "noisy", "latency"])
def test_gateway_counters_say_which_path_posted(case):
    """A fused PerfectCrowd run replays every crowdsourced pair through the
    gateway's columnar post, with the parent's dispatches and host reads; a
    NoisyCrowd run and a latency-mode run ask pair by pair."""
    from repro.core import LatencyModel, NoisyCrowd

    pss = make_session_pairsets(2, seed=5, n_objects=(10, 16),
                                n_pairs=(20, 31), n_entities=4)
    if case == "perfect_fused":
        crowds, kw = [PerfectCrowd(), PerfectCrowd()], {}
    elif case == "noisy":
        crowds = [NoisyCrowd(error_rate=0.1, seed=s) for s in (1, 2)]
        kw = {}
    else:
        crowds = [PerfectCrowd(), PerfectCrowd()]
        kw = {"latency": LatencyModel(n_workers=4, seed=3)}
    svc = JoinService(lanes=2, **kw)
    rids = [svc.submit(ps, c) for ps, c in zip(pss, crowds)]
    obs.enable()
    b0, p0 = obs.gateway_bulk_pairs.count, obs.gateway_ballot_pairs.count
    d0, s0 = obs.engine_dispatches.count, obs.host_syncs.count
    res = svc.run()
    bulk = obs.gateway_bulk_pairs.count - b0
    ballot = obs.gateway_ballot_pairs.count - p0
    replayed = sum(int(res[r].crowdsourced.sum()) for r in rids)
    asked = sum(c.n_asked for c in crowds)
    assert replayed > 0 and asked == bulk + ballot
    if case == "perfect_fused":
        assert bulk == replayed == asked and ballot == 0
        n_dispatch = sum(s.name == "join.engine_dispatch"
                         for s in obs.spans())
        assert obs.host_syncs.count - s0 == 5 * n_dispatch + 2 * 2
        assert obs.engine_dispatches.count - d0 == 9
    else:
        assert bulk == 0 and ballot == asked >= replayed
