"""TPU-native engine vs the Python oracle (DESIGN.md §4, §8 adaptation)."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (ClusterGraph, MATCH, NEG, NON_MATCH, POS, PairSet,
                        UNKNOWN, boruvka_frontier, connected_components,
                        deduce_batch, get_order, label_parallel_jax, neg_keys,
                        make_session_state, pair_key_bits, pair_keys_fit,
                        parallel_crowdsourced_pairs, session_apply_answers,
                        session_deduce, session_from_labels, session_frontier,
                        session_mark_published)
from repro.core.jax_graph import canonical_keys


@st.composite
def edge_world(draw):
    n = draw(st.integers(3, 12))
    entities = [draw(st.integers(0, 3)) for _ in range(n)]
    all_edges = list(itertools.combinations(range(n), 2))
    m = draw(st.integers(2, min(14, len(all_edges))))
    idx = draw(st.permutations(range(len(all_edges))))
    edges = [all_edges[i] for i in idx[:m]]
    labels = [entities[a] == entities[b] for a, b in edges]
    return n, edges, labels


@given(edge_world())
def test_connected_components_vs_union_find(world):
    n, edges, labels = world
    u = jnp.array([e[0] for e in edges], jnp.int32)
    v = jnp.array([e[1] for e in edges], jnp.int32)
    mask = jnp.array(labels)
    roots = np.asarray(connected_components(u, v, mask, n))
    g = ClusterGraph(n)
    for (a, b), m in zip(edges, labels):
        if m:
            g.add_label(a, b, MATCH)
    for a in range(n):
        for b in range(n):
            assert (roots[a] == roots[b]) == g.connected(a, b)


@given(edge_world())
def test_deduce_batch_vs_oracle(world):
    n, edges, labels = world
    u = jnp.array([e[0] for e in edges], jnp.int32)
    v = jnp.array([e[1] for e in edges], jnp.int32)
    pos_mask = jnp.array(labels)
    roots = connected_components(u, v, pos_mask, n)
    sneg = neg_keys(roots, u, v, ~pos_mask, n)
    g = ClusterGraph(n)
    for (a, b), m in zip(edges, labels):
        g.add_label(a, b, MATCH if m else NON_MATCH)
    qa, qb = np.meshgrid(np.arange(n), np.arange(n))
    got = np.asarray(deduce_batch(roots, sneg, jnp.asarray(qa.ravel()),
                                  jnp.asarray(qb.ravel()), n)).reshape(n, n)
    for a in range(n):
        for b in range(n):
            if a == b:
                continue
            want = g.deduce(a, b)
            want_code = {MATCH: POS, NON_MATCH: NEG, None: UNKNOWN}[want]
            assert got[a, b] == want_code, (a, b, edges, labels)


@given(edge_world())
def test_boruvka_round1_exact_parity(world):
    """With no labels (iteration 1) the Borůvka frontier equals the
    sequential scan's selection exactly (priority-Kruskal forest)."""
    n, edges, _ = world
    P = len(edges)
    u = np.array([e[0] for e in edges], np.int32)
    v = np.array([e[1] for e in edges], np.int32)
    ps = PairSet(u, v, np.linspace(1, 0.5, P).astype(np.float32),
                 np.zeros(P, bool), n_objects=n)
    oracle = set(parallel_crowdsourced_pairs(ps, np.arange(P), {}))
    fr = boruvka_frontier(jnp.asarray(u), jnp.asarray(v),
                          jnp.full((P,), UNKNOWN, jnp.int32),
                          jnp.zeros((P,), bool), n)
    assert set(np.nonzero(np.asarray(fr))[0].tolist()) == oracle


@given(edge_world())
def test_jax_engine_full_run_correct_and_no_worse(world):
    """Full engine run: labels == truth; crowdsourced count <= oracle's
    sequential count + small slack (the engine uses position-free labeled
    evidence, which can only help per DESIGN.md §4)."""
    n, edges, labels = world
    P = len(edges)
    u = np.array([e[0] for e in edges], np.int32)
    v = np.array([e[1] for e in edges], np.int32)
    truth_arr = np.where(np.array(labels), POS, NEG).astype(np.int32)
    out, crowdsourced, rounds, n_conflicts = label_parallel_jax(
        u, v, n, lambda idx: truth_arr[idx])
    assert (out == truth_arr).all()
    assert crowdsourced.sum() <= P
    assert n_conflicts == 0  # consistent truth never conflicts


# ---------------------------------------------------------------------------
# Persistent SessionState: incremental path bit-identical to from-scratch
# (DESIGN.md §8).  Worlds come from the shared conftest builder.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_session_state_incremental_bit_identical(make_random_world, seed):
    """Fold answers into a persistent SessionState in random chunks; after
    every fold the incrementally-maintained roots and sorted neg-key index
    must equal a from-scratch rebuild bit-for-bit, and the state frontier
    must equal the from-scratch wrapper's."""
    rng = np.random.default_rng(seed)
    n, u, v, truth = make_random_world(rng)
    m = len(u)
    state = make_session_state(u, v, n)
    labels = np.full(m, UNKNOWN, np.int32)
    order = rng.permutation(m)
    k = 0
    while k < m:
        step = int(rng.integers(1, 4))
        idx = order[k:k + step]
        k += step
        upd = np.full(m, UNKNOWN, np.int32)
        upd[idx] = truth[idx]
        labels[idx] = truth[idx]
        state, cmask = session_apply_answers(state, jnp.asarray(upd))
        assert not np.asarray(cmask).any()  # truth answers never conflict
        ref = session_from_labels(u, v, labels, np.zeros(m, bool), n)
        np.testing.assert_array_equal(np.asarray(state.labels), labels)
        np.testing.assert_array_equal(np.asarray(state.roots),
                                      np.asarray(ref.roots))
        np.testing.assert_array_equal(np.asarray(state.neg_keys),
                                      np.asarray(ref.neg_keys))
        np.testing.assert_array_equal(
            np.asarray(session_frontier(state)),
            np.asarray(boruvka_frontier(u, v, labels, np.zeros(m, bool), n)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_session_state_published_matches_from_scratch_frontier(
        make_random_world, seed):
    """In-flight (published) pairs are assumed matching but excluded from the
    frontier; the incremental state agrees with the from-scratch wrapper."""
    rng = np.random.default_rng(100 + seed)
    n, u, v, truth = make_random_world(rng)
    m = len(u)
    state = make_session_state(u, v, n)
    # reveal a third of the labels, publish a random subset of the rest
    reveal = rng.permutation(m)[:max(m // 3, 1)]
    upd = np.full(m, UNKNOWN, np.int32)
    upd[reveal] = truth[reveal]
    state, _ = session_apply_answers(state, jnp.asarray(upd))
    labels = np.asarray(state.labels)
    published = (rng.random(m) < 0.4) & (labels == UNKNOWN)
    state = session_mark_published(state, jnp.asarray(published))
    np.testing.assert_array_equal(
        np.asarray(session_frontier(state)),
        np.asarray(boruvka_frontier(u, v, labels, published, n)))
    # deduction skips published pairs (their answers are in flight)
    ded = np.asarray(session_deduce(state).labels)
    assert (ded[published] == labels[published]).all()


def test_session_deduce_matches_from_scratch_without_published(
        make_random_world):
    rng = np.random.default_rng(9)
    n, u, v, truth = make_random_world(rng)
    m = len(u)
    reveal = rng.permutation(m)[:m // 2]
    labels = np.full(m, UNKNOWN, np.int32)
    labels[reveal] = truth[reveal]
    state = session_from_labels(u, v, labels, np.zeros(m, bool), n)
    from repro.core import deduce_sessions
    want = np.asarray(deduce_sessions(u[None], v[None], labels[None], n))[0]
    got = np.asarray(session_deduce(state).labels)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Shared pair-key-overflow guard (DESIGN.md §8)
# ---------------------------------------------------------------------------
def test_pair_key_guard_x64_off_boundary():
    """With x64 disabled (the test default) keys are int32: n = 46340 is the
    last universe whose n*n fits below 2**31 and keeps one-word keys; from
    46341 on, both the predicate and canonical_keys switch to two int32
    words (lo root over hi root)."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled — int32 boundary not in effect")
    assert pair_key_bits() == 31
    n_ok, n_bad = 46340, 46341
    assert n_ok * n_ok < 2 ** 31 <= n_bad * n_bad
    assert pair_keys_fit(n_ok)
    assert not pair_keys_fit(n_bad)
    ru = jnp.array([5, 46339, 0], jnp.int32)
    rv = jnp.array([3, 46338, 0], jnp.int32)
    one = np.asarray(canonical_keys(ru, rv, n_ok))
    assert one.shape == (3,) and one.dtype == np.int32
    np.testing.assert_array_equal(one, [3 * n_ok + 5,
                                        46338 * n_ok + 46339, 0])
    two = np.asarray(canonical_keys(ru, rv, n_bad))
    assert two.shape == (2, 3) and two.dtype == np.int32
    np.testing.assert_array_equal(two, [[3, 46338, 0], [5, 46339, 0]])


# ---------------------------------------------------------------------------
# Cross-query warm start: session_seed_labels (DESIGN.md §14)
# ---------------------------------------------------------------------------
_STATE_FIELDS = ("u", "v", "labels", "published", "roots", "neg_keys",
                 "conflicts", "priority")


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("noisy", [False, True])
def test_session_seed_labels_bit_identical_to_fold(make_random_world, seed,
                                                   noisy):
    """Seeding cached verdicts must be EXACTLY replaying them through the
    answer fold — every state field bit-for-bit, including the conflict
    mask when the seeds contradict each other — except ``rounds``, which
    seeding leaves alone (seeds are prior queries' capital, not a crowd
    round of this session)."""
    from repro.core import session_fold_answers, session_seed_labels

    rng = np.random.default_rng(seed)
    n, u, v, truth = make_random_world(rng)
    m = len(u)
    reveal = rng.random(m) < 0.6
    seeds = np.where(reveal, truth, UNKNOWN).astype(np.int32)
    if noisy:  # contradictory seeds exercise the §9 screen path
        flip = rng.random(m) < 0.3
        seeds = np.where(reveal & flip,
                         np.where(seeds == POS, NEG, POS), seeds)
    sa, ca = session_seed_labels(make_session_state(u, v, n),
                                 jnp.asarray(seeds))
    sb, cb = session_fold_answers(make_session_state(u, v, n),
                                  jnp.asarray(seeds))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(sa, f)),
                                      np.asarray(getattr(sb, f)),
                                      err_msg=f)
    assert int(np.asarray(sa.rounds)) == 0
    assert int(np.asarray(sb.rounds)) == 1


@pytest.mark.parametrize("seed", [0, 1])
def test_session_seed_labels_pad_preserving(make_random_world, seed):
    """Seeding a capacity-padded state must leave the padded tail exactly as
    the fold would: pads stay UNKNOWN/unpublished, real slots identical to
    the unpadded run."""
    from repro.core import next_pow2, session_fold_answers, session_seed_labels

    rng = np.random.default_rng(seed)
    n, u, v, truth = make_random_world(rng)
    m = len(u)
    p_cap, n_cap = next_pow2(2 * m), next_pow2(2 * n)
    seeds = np.full(p_cap, UNKNOWN, np.int32)
    reveal = rng.random(m) < 0.7
    seeds[:m] = np.where(reveal, truth, UNKNOWN)
    sa, ca = session_seed_labels(
        make_session_state(u, v, n, pair_capacity=p_cap,
                           object_capacity=n_cap), jnp.asarray(seeds))
    sb, cb = session_fold_answers(
        make_session_state(u, v, n, pair_capacity=p_cap,
                           object_capacity=n_cap), jnp.asarray(seeds))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(sa, f)),
                                      np.asarray(getattr(sb, f)),
                                      err_msg=f)
    # padding is inert: real-slot results identical to the unpadded run,
    # and padded slots never enter flight
    su, _ = session_seed_labels(make_session_state(u, v, n),
                                jnp.asarray(seeds[:m]))
    np.testing.assert_array_equal(np.asarray(sa.labels)[:m],
                                  np.asarray(su.labels))
    assert not np.asarray(sa.published)[m:].any()


@pytest.mark.parametrize("seed", [0, 1])
def test_session_seed_labels_batch_matches_unbatched(make_random_world, seed):
    """The vmapped seed fold (speculative fast path + exact fallback) must
    reproduce the per-session transform bit-for-bit."""
    import jax

    from repro.core import session_seed_labels, session_seed_labels_batch

    rng = np.random.default_rng(seed)
    worlds = [make_random_world(rng) for _ in range(3)]
    p_cap = max(len(w[1]) for w in worlds)
    n_cap = max(w[0] for w in worlds)
    states, seed_rows = [], []
    for n, u, v, truth in worlds:
        states.append(make_session_state(u, v, n, pair_capacity=p_cap,
                                         object_capacity=n_cap))
        s = np.full(p_cap, UNKNOWN, np.int32)
        reveal = rng.random(len(u)) < 0.6
        s[:len(u)] = np.where(reveal, truth, UNKNOWN)
        seed_rows.append(s)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    bs, bc = session_seed_labels_batch(stacked, jnp.asarray(seed_rows))
    for b, (n, u, v, truth) in enumerate(worlds):
        ss, cc = session_seed_labels(
            make_session_state(u, v, n, pair_capacity=p_cap,
                               object_capacity=n_cap),
            jnp.asarray(seed_rows[b]))
        np.testing.assert_array_equal(np.asarray(bc)[b], np.asarray(cc))
        for f in _STATE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(bs, f))[b],
                np.asarray(getattr(ss, f)), err_msg=f"{f} (lane {b})")
