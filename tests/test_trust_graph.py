"""The in-flight bookkeeping programs the service runs on every lane
(DESIGN.md §8, §9): ``session_mark_published{,_batch}`` records posted
pairs, ``session_trust_graph{,_batch}`` pulls requery-exhausted pairs out
of flight and labels them by deduction.  Each is checked against a numpy
or ``ClusterGraph`` oracle and against its per-lane twin, with one-word
pair keys and with the two-word keys of sessions past 46,340 objects."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (NEG, POS, UNKNOWN, ClusterGraph, MATCH, NON_MATCH,
                        pair_keys_fit, session_from_labels,
                        session_mark_published, session_mark_published_batch,
                        session_trust_graph, session_trust_graph_batch)
from repro.data.entities import make_session_pairsets

FIELDS = ("u", "v", "labels", "published", "roots", "neg_keys", "rounds",
          "conflicts", "priority")
WIDE_OBJECTS = 50_000          # past 46,340: two-word pair keys
KEY_WIDTHS = {"one_word": None, "two_word": WIDE_OBJECTS}


def _snap(state) -> dict:
    return {f: np.asarray(getattr(state, f)) for f in FIELDS}


def _lanes(n_lanes: int, wide, seed: int):
    """``n_lanes`` entity-clustered sessions, each with the truth of about
    half its pairs labelled and a third of the rest in flight.  ``wide``
    spreads the object ids over that many objects."""
    rng = np.random.default_rng(seed)
    sessions = make_session_pairsets(n_lanes, seed=seed, n_objects=(14, 24),
                                     n_pairs=(30, 60), n_entities=4)
    lanes = []
    for ps in sessions:
        u, v = np.asarray(ps.u), np.asarray(ps.v)
        if wide:
            step = (wide - 1) // ps.n_objects
            u, v = u * step + 3, v * step + 3
        truth = np.where(ps.truth, POS, NEG).astype(np.int32)
        known = rng.random(len(u)) < 0.5
        labels = np.where(known, truth, UNKNOWN).astype(np.int32)
        published = ~known & (rng.random(len(u)) < 0.4)
        lanes.append((u.astype(np.int32), v.astype(np.int32), labels,
                      published))
    n = wide or max(ps.n_objects for ps in sessions)
    p_cap = max(len(u) for u, _, _, _ in lanes)
    return lanes, n, p_cap


def _pad(lane, p_cap):
    """A lane at the batch's pair capacity: padded slots are the inert
    pre-labelled POS self-loop (0, 0)."""
    u, v, labels, published = lane
    pad = p_cap - len(u)
    return (np.pad(u, (0, pad)), np.pad(v, (0, pad)),
            np.pad(labels, (0, pad), constant_values=POS),
            np.pad(published, (0, pad)))


def _states(lanes, n, p_cap):
    return [session_from_labels(*_pad(lane, p_cap), n) for lane in lanes]


def _stack(states):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _masks(lanes, p_cap, seed):
    """Half of each lane's in-flight pairs."""
    rng = np.random.default_rng(seed + 1)
    out = np.zeros((len(lanes), p_cap), bool)
    for b, (_, _, _, published) in enumerate(lanes):
        out[b, :len(published)] = published & (rng.random(len(published))
                                                < 0.5)
    return out


def _trusted(lane, mask):
    """ClusterGraph oracle: un-publish ``mask``, then every unlabelled pair
    out of flight takes Algorithm 1's deduction."""
    u, v, labels, published = lane
    n = int(max(u.max(), v.max())) + 1
    graph = ClusterGraph(n)
    for a, b, lab in zip(u, v, labels):
        if lab != UNKNOWN:
            assert graph.add_label(int(a), int(b),
                                   MATCH if lab == POS else NON_MATCH)
    published = published & ~mask[:len(u)]
    want = labels.copy()
    for i in np.nonzero((labels == UNKNOWN) & ~published)[0]:
        d = graph.deduce(int(u[i]), int(v[i]))
        if d is not None:
            want[i] = POS if d == MATCH else NEG
    return want, published


def _assert_trusted(got: dict, before: dict, lane, mask, n, p_cap):
    want_labels, want_pub = _trusted(lane, mask)
    p = len(lane[0])
    np.testing.assert_array_equal(got["labels"][:p], want_labels)
    np.testing.assert_array_equal(got["published"][:p], want_pub)
    np.testing.assert_array_equal(got["labels"][p:], POS)
    # the maintained forest and neg-key index equal a from-scratch rebuild
    rebuilt = _snap(session_from_labels(
        *_pad((lane[0], lane[1], want_labels, want_pub), p_cap), n))
    for f in ("roots", "neg_keys"):
        np.testing.assert_array_equal(got[f], rebuilt[f], err_msg=f)
    for f in ("u", "v", "rounds", "conflicts", "priority"):
        np.testing.assert_array_equal(got[f], before[f], err_msg=f)
    return (want_labels != lane[2]).sum()


@pytest.mark.parametrize("n_lanes", [1, 3])
@pytest.mark.parametrize("width", list(KEY_WIDTHS))
def test_mark_published_batch_matches_oracle_and_per_lane(width, n_lanes):
    wide = KEY_WIDTHS[width]
    lanes, n, p_cap = _lanes(n_lanes, wide, seed=3 + n_lanes)
    assert pair_keys_fit(n) == (wide is None)
    states = _states(lanes, n, p_cap)
    masks = _masks(lanes, p_cap, seed=n_lanes)
    before = [_snap(s) for s in states]
    batch = _snap(session_mark_published_batch(_stack(states),
                                               jnp.asarray(masks)))
    for b, state in enumerate(states):
        twin = _snap(session_mark_published(state, jnp.asarray(masks[b])))
        np.testing.assert_array_equal(twin["published"],
                                      before[b]["published"] | masks[b])
        for f in FIELDS:
            np.testing.assert_array_equal(batch[f][b], twin[f], err_msg=f)
            if f != "published":
                np.testing.assert_array_equal(twin[f], before[b][f],
                                              err_msg=f)
    assert masks.any()


@pytest.mark.parametrize("n_lanes", [1, 3])
@pytest.mark.parametrize("width", list(KEY_WIDTHS))
def test_trust_graph_matches_cluster_graph(width, n_lanes):
    wide = KEY_WIDTHS[width]
    lanes, n, p_cap = _lanes(n_lanes, wide, seed=11 + n_lanes)
    masks = _masks(lanes, p_cap, seed=7)
    deduced = 0
    for lane, state, mask in zip(lanes, _states(lanes, n, p_cap), masks):
        before = _snap(state)
        got = _snap(session_trust_graph(state, jnp.asarray(mask)))
        deduced += _assert_trusted(got, before, lane, mask, n, p_cap)
    assert deduced > 0


@pytest.mark.parametrize("n_lanes", [1, 3])
@pytest.mark.parametrize("width", list(KEY_WIDTHS))
def test_trust_graph_batch_matches_per_lane_and_oracle(width, n_lanes):
    wide = KEY_WIDTHS[width]
    lanes, n, p_cap = _lanes(n_lanes, wide, seed=23 + n_lanes)
    masks = _masks(lanes, p_cap, seed=9)
    before = [_snap(s) for s in _states(lanes, n, p_cap)]
    batch = _snap(session_trust_graph_batch(
        _stack(_states(lanes, n, p_cap)), jnp.asarray(masks)))
    deduced = 0
    for b, (lane, state) in enumerate(zip(lanes, _states(lanes, n, p_cap))):
        twin = _snap(session_trust_graph(state, jnp.asarray(masks[b])))
        for f in FIELDS:
            np.testing.assert_array_equal(batch[f][b], twin[f], err_msg=f)
        deduced += _assert_trusted(twin, before[b], lane, masks[b], n, p_cap)
    assert deduced > 0
