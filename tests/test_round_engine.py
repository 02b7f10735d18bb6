"""DESIGN.md §13 on-device round engine: ``session_run_rounds`` must be
bit-identical to driving the legacy per-round entry points (refresh ->
frontier -> fold) from the host with the same order-independent answers,
batched must equal unbatched, and donation must consume the input state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (NEG, POS, ROUNDS_CONFLICT, ROUNDS_DONE, ROUNDS_EMPTY,
                        ROUNDS_RUNNING, UNKNOWN, make_session_state,
                        make_session_state_batch, pack_sessions,
                        session_fold_answers, session_frontier,
                        session_from_labels, session_mark_published,
                        session_refresh_priorities, session_run_rounds,
                        session_run_rounds_batch)

STATE_FIELDS = ("u", "v", "labels", "published", "roots", "neg_keys",
                "rounds", "conflicts", "priority")


def _snap(state) -> dict:
    """Host copy of every array field (donation-proof comparison point)."""
    return {f: np.asarray(getattr(state, f)) for f in STATE_FIELDS}


def _assert_states_equal(a: dict, b: dict, msg: str = "") -> None:
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f"{msg} field={f}")


def _random_session(rng, n_objects: int, n_pairs: int):
    """Random pair list + transitively consistent truths (labels follow a
    random partition of the objects, as a perfect crowd would answer)."""
    u = rng.integers(0, n_objects, n_pairs).astype(np.int32)
    v = (u + 1 + rng.integers(0, n_objects - 1, n_pairs)).astype(np.int32) \
        % n_objects
    cluster = rng.integers(0, max(2, n_objects // 3), n_objects)
    truth = np.where(cluster[u] == cluster[v], POS, NEG).astype(np.int32)
    return u, v, truth


def _host_oracle(state, answers, prior, adaptive, rounds_allowed,
                 max_rounds):
    """The legacy host loop the fused engine folds on device — literally
    refresh -> frontier -> fold per round, with the same exit codes."""
    P = int(state.u.shape[0])
    crowd = np.zeros(P, bool)
    sizes = np.zeros(max_rounds, np.int32)
    r, code = 0, ROUNDS_RUNNING
    ra = min(int(rounds_allowed), max_rounds)
    while code == ROUNDS_RUNNING and r < ra:
        if not (np.asarray(state.labels) == UNKNOWN).any():
            code = ROUNDS_DONE
            break
        if adaptive:
            state = session_refresh_priorities(state, prior)
        frontier = np.asarray(session_frontier(state))
        updates = np.where(frontier, answers, UNKNOWN).astype(np.int32)
        pre = _snap(state)
        state, conflict = session_fold_answers(state, jnp.asarray(updates))
        if bool(np.asarray(conflict).any()):
            # the device loop exits with the pre-fold (refreshed) state so
            # the host can replay the round through the sequential path
            code = ROUNDS_CONFLICT
            return pre, crowd, sizes, r, code
        if not frontier.any():
            code = ROUNDS_EMPTY
            break
        crowd |= frontier
        sizes[r] = int(frontier.sum())
        r += 1
    return _snap(state), crowd, sizes, r, code


def _check_run_rounds_matches_host_loop(seed, max_rounds, adaptive):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 12))
    p = int(rng.integers(3, 20))
    u, v, truth = _random_session(rng, n, p)
    prior = rng.random(p).astype(np.float32)

    got_state, got_crowd, got_sizes, got_r, got_code = session_run_rounds(
        make_session_state(u, v, n), truth, max_rounds,
        prior=prior, adaptive=adaptive)
    exp_state, exp_crowd, exp_sizes, exp_r, exp_code = _host_oracle(
        make_session_state(u, v, n), truth, jnp.asarray(prior), adaptive,
        max_rounds, max_rounds)

    assert int(got_code) == exp_code
    assert int(got_r) == exp_r
    np.testing.assert_array_equal(np.asarray(got_crowd), exp_crowd)
    np.testing.assert_array_equal(np.asarray(got_sizes), exp_sizes)
    _assert_states_equal(_snap(got_state), exp_state,
                         f"seed={seed} k={max_rounds} adaptive={adaptive}")


@settings(deadline=None, max_examples=12)
@given(seed=st.integers(0, 2**31 - 1),
       max_rounds=st.sampled_from([1, 3, 8]),
       adaptive=st.booleans())
def test_run_rounds_matches_host_loop(seed, max_rounds, adaptive):
    _check_run_rounds_matches_host_loop(seed, max_rounds, adaptive)


def _check_run_rounds_batch_matches_unbatched(seed, max_rounds):
    rng = np.random.default_rng(seed)
    B = int(rng.integers(2, 5))
    sessions, truths, priors, adaptives = [], [], [], []
    for _ in range(B):
        n = int(rng.integers(4, 10))
        p = int(rng.integers(3, 14))
        u, v, t = _random_session(rng, n, p)
        sessions.append((u, v, n))
        truths.append(t)
        priors.append(rng.random(p).astype(np.float32))
        adaptives.append(bool(rng.integers(0, 2)))
    U, V, labels0, valid, n_cap = pack_sessions(sessions)
    answers = np.full(labels0.shape, UNKNOWN, np.int32)
    prior = np.zeros(labels0.shape, np.float32)
    for b in range(B):
        answers[b, :len(truths[b])] = truths[b]
        prior[b, :len(priors[b])] = priors[b]
    stacked = make_session_state_batch(U, V, labels0, n_cap)
    out, crowd, sizes, rdone, codes = session_run_rounds_batch(
        stacked, answers, max_rounds, prior=prior,
        adaptive=np.asarray(adaptives))
    out = _snap(out)

    for b, (u, v, n) in enumerate(sessions):
        p_cap = labels0.shape[1]
        state = make_session_state(u, v, n, pair_capacity=p_cap,
                                   object_capacity=n_cap)
        ref, ref_crowd, ref_sizes, ref_r, ref_code = session_run_rounds(
            state, answers[b], max_rounds, prior=prior[b],
            adaptive=adaptives[b])
        assert int(codes[b]) == int(ref_code), f"lane {b}"
        assert int(rdone[b]) == int(ref_r), f"lane {b}"
        np.testing.assert_array_equal(np.asarray(crowd)[b],
                                      np.asarray(ref_crowd))
        np.testing.assert_array_equal(np.asarray(sizes)[b],
                                      np.asarray(ref_sizes))
        ref = _snap(ref)
        for f in STATE_FIELDS:
            np.testing.assert_array_equal(out[f][b], ref[f],
                                          err_msg=f"lane {b} field={f}")


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**31 - 1), max_rounds=st.sampled_from([1, 4]))
def test_run_rounds_batch_matches_unbatched(seed, max_rounds):
    _check_run_rounds_batch_matches_unbatched(seed, max_rounds)


@pytest.mark.parametrize("seed,max_rounds,adaptive",
                         [(0, 1, False), (1, 3, True), (2, 8, False),
                          (3, 8, True), (4, 3, False)])
def test_run_rounds_matches_host_loop_fixed(seed, max_rounds, adaptive):
    """Fixed-seed spot checks of the property above (run even when
    hypothesis is unavailable)."""
    _check_run_rounds_matches_host_loop(seed, max_rounds, adaptive)


@pytest.mark.parametrize("seed,max_rounds", [(0, 1), (1, 4), (2, 4)])
def test_run_rounds_batch_matches_unbatched_fixed(seed, max_rounds):
    _check_run_rounds_batch_matches_unbatched(seed, max_rounds)


# ---------------------------------------------------------------------------
# Frontier edge cases (ISSUE satellite): early while_loop exits
# ---------------------------------------------------------------------------
def test_all_pairs_published_exits_empty():
    """Every pending pair already posted to the crowd: the frontier is empty
    on entry, the loop exits EMPTY after zero counted rounds and labels
    nothing."""
    u = np.array([0, 1, 2], np.int32)
    v = np.array([1, 2, 3], np.int32)
    state = make_session_state(u, v, 4)
    state = session_mark_published(state, jnp.ones(3, bool))
    truth = np.full(3, POS, np.int32)
    out, crowd, sizes, rdone, code = session_run_rounds(state, truth, 4)
    assert int(code) == ROUNDS_EMPTY
    assert int(rdone) == 0
    assert not np.asarray(crowd).any()
    assert not np.asarray(sizes).any()
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.full(3, UNKNOWN))


def test_all_pending_deduced_mid_loop_exits_done():
    """A path graph whose closing pair is deduced transitively after round
    one: the loop exits DONE before exhausting max_rounds and the trailing
    round_sizes slots stay zero."""
    u = np.array([0, 1, 0], np.int32)
    v = np.array([1, 2, 2], np.int32)
    truth = np.array([POS, POS, POS], np.int32)
    out, crowd, sizes, rdone, code = session_run_rounds(
        make_session_state(u, v, 3), truth, 8)
    assert int(code) == ROUNDS_DONE
    assert int(rdone) == 1
    np.testing.assert_array_equal(np.asarray(out.labels), truth)
    # only the two tree pairs were crowdsourced; (0, 2) came by transitivity
    np.testing.assert_array_equal(np.asarray(crowd), [True, True, False])
    np.testing.assert_array_equal(np.asarray(sizes),
                                  [2, 0, 0, 0, 0, 0, 0, 0])


def test_zero_rounds_allowed_exits_running():
    """Budget exhausted on entry (``rounds_allowed=0``): the loop body never
    runs, the state round-trips bit-for-bit and the code says RUNNING."""
    u = np.array([0, 1], np.int32)
    v = np.array([1, 2], np.int32)
    state = make_session_state(u, v, 3)
    before = _snap(state)
    truth = np.full(2, POS, np.int32)
    out, crowd, sizes, rdone, code = session_run_rounds(
        state, truth, 4, rounds_allowed=0)
    assert int(code) == ROUNDS_RUNNING
    assert int(rdone) == 0
    assert not np.asarray(crowd).any()
    assert not np.asarray(sizes).any()
    _assert_states_equal(_snap(out), before)


def test_conflict_exits_with_prefold_state():
    """§9 conflict screen inside the fused loop: two POS answers whose merge
    closes a chain across an existing NEG constraint.  The loop must exit
    CONFLICT with the pre-fold state (bit-equal to the input here: order is
    non-adaptive so the refresh is a no-op) so the host replays that round
    through the exact sequential path."""
    u = np.array([0, 1, 0], np.int32)
    v = np.array([1, 2, 2], np.int32)
    labels = np.array([UNKNOWN, UNKNOWN, NEG], np.int32)
    state = session_from_labels(u, v, labels, np.zeros(3, bool), 3)
    before = _snap(state)
    answers = np.array([POS, POS, UNKNOWN], np.int32)
    out, crowd, sizes, rdone, code = session_run_rounds(state, answers, 4)
    assert int(code) == ROUNDS_CONFLICT
    assert int(rdone) == 0
    assert not np.asarray(crowd).any()
    assert not np.asarray(sizes).any()
    _assert_states_equal(_snap(out), before, "conflict must return pre-fold")
    # the legacy replay of the same round from the returned state resolves
    # the conflict sequentially instead
    frontier = np.asarray(session_frontier(out))
    assert frontier[:2].all() and not frontier[2]
    replayed, conflict = session_fold_answers(
        out, jnp.where(jnp.asarray(frontier), jnp.asarray(answers), UNKNOWN))
    assert bool(np.asarray(conflict).any())
    assert not (np.asarray(replayed.labels) == UNKNOWN).any()


# ---------------------------------------------------------------------------
# Donation discipline (ISSUE satellite): state-in/state-out entry points
# hand their buffers to XLA; callers must not reuse the input state
# ---------------------------------------------------------------------------
def test_run_rounds_donates_input_state():
    u = np.array([0, 1], np.int32)
    v = np.array([1, 2], np.int32)
    state = make_session_state(u, v, 3)
    jax.block_until_ready(state.labels)
    donated = state.labels
    out, *_ = session_run_rounds(state, np.full(2, POS, np.int32), 4)
    jax.block_until_ready(out.labels)
    assert donated.is_deleted()


def test_fold_and_refresh_donate_and_alias():
    u = np.array([0, 1, 0], np.int32)
    v = np.array([1, 2, 2], np.int32)
    state = make_session_state(u, v, 3)
    jax.block_until_ready(state.labels)
    in_bufs = {f: getattr(state, f) for f in STATE_FIELDS}
    in_ptrs = {b.unsafe_buffer_pointer() for b in in_bufs.values()}
    out, _ = session_fold_answers(
        state, np.array([POS, UNKNOWN, UNKNOWN], np.int32))
    jax.block_until_ready(out.labels)
    assert all(b.is_deleted() for b in in_bufs.values())
    # donated buffers are reused in place: at least one output leaf lives at
    # an input address (XLA may rematerialize some leaves into new buffers)
    out_ptrs = {getattr(out, f).unsafe_buffer_pointer()
                for f in STATE_FIELDS}
    assert in_ptrs & out_ptrs

    prior = np.array([0.9, 0.5, 0.1], np.float32)
    donated = out.priority
    out2 = session_refresh_priorities(out, jnp.asarray(prior))
    jax.block_until_ready(out2.priority)
    assert donated.is_deleted()


def test_grow_does_not_donate():
    """Growth changes buffer shapes, so its outputs can never alias the
    inputs — the entry point must NOT donate or the old state would be
    destroyed without reuse (DESIGN.md §13)."""
    from repro.core import session_grow

    u = np.array([0, 1], np.int32)
    v = np.array([1, 2], np.int32)
    state = make_session_state(u, v, 3)
    jax.block_until_ready(state.labels)
    grown = session_grow(state, pair_capacity=8, object_capacity=6)
    jax.block_until_ready(grown.labels)
    assert not state.labels.is_deleted()
    np.testing.assert_array_equal(np.asarray(state.labels),
                                  np.asarray(grown.labels)[:2])


# ---------------------------------------------------------------------------
# Fused serving drive (tentpole): whole-wave megabatch vs per-round legacy
# ---------------------------------------------------------------------------
def _service_sessions(n_sessions: int, seed: int):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_sessions):
        n = int(rng.integers(6, 12))
        p = int(rng.integers(6, 18))
        u, v, truth = _random_session(rng, n, p)
        out.append((u, v, n, truth))
    return out


@pytest.mark.parametrize("async_mode", [False, True])
@pytest.mark.parametrize("order", ["expected", "adaptive"])
def test_service_fused_rounds_parity(async_mode, order):
    """The fused cross-lane drive must reproduce the legacy per-round serve
    loop observable-for-observable: labels, crowdsourced set, per-round
    sizes, conflicts and billing."""
    from repro.core import PairSet, PerfectCrowd
    from repro.serve.join_service import JoinService

    results = {}
    for fused in (True, False):
        svc = JoinService(lanes=2, order=order, async_mode=async_mode,
                          fused_rounds=fused)
        rids = []
        for (u, v, n, truth) in _service_sessions(3, seed=7):
            cand = PairSet(u=u, v=v, n_objects=n,
                           likelihood=np.linspace(0.9, 0.1, len(u)),
                           truth=(truth == POS))
            rids.append(svc.submit(cand, PerfectCrowd()))
        results[fused] = svc.run()
    for rid in results[True]:
        a, b = results[True][rid], results[False][rid]
        np.testing.assert_array_equal(a.labels, b.labels)
        assert a.n_crowdsourced == b.n_crowdsourced
        assert a.round_sizes == b.round_sizes
        assert a.n_conflicts == b.n_conflicts
        assert a.n_spent_cents == b.n_spent_cents
