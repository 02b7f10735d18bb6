"""Blocked-vs-dense parity (DESIGN.md §12): the LSH blocking stage + fused
compaction kernel against the ``ref.py`` dense oracle.

The contract under test, on corpora small enough to score densely:
  - blocked candidates are a *subset* of dense candidates (blocking can
    only miss, never invent);
  - recall >= the configured floor;
  - every surviving pair scores within ``BAND`` of the dense oracle (same
    f32 dot over the same normalized rows; only the tile shape's summation
    order differs), and a blocked pair the oracle lacks scores within
    ``BAND`` of the threshold;
  - the same three properties hold through StreamingCandidateIndex epochs,
    whose union must equal one batch blocked call exactly.

Seeded deterministic tests always run; the @given variants re-check the
same properties over drawn corpora where hypothesis is installed (CI).
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core.jax_graph import next_pow2
from repro.kernels.pair_scores import blocking
from repro.kernels.pair_scores.blocking import (BlockingConfig,
                                                blocked_candidates,
                                                blocker_recall,
                                                dense_block_pairs,
                                                expected_recall,
                                                score_block_pairs, signatures)
from repro.kernels.pair_scores.ops import l2_normalize
from repro.kernels.pair_scores.ref import candidate_diff, candidates_ref
from repro.kernels.pair_scores.sharded import StreamingCandidateIndex
from repro.launch.mesh import make_host_mesh

TAU = 0.85
# score agreement with the oracle: a few f32 ulps near 1.0
BAND = 1e-6
# small tiles so tiny corpora still exercise multi-tile buckets, and one
# jit entry serves the whole module
CFG_KW = dict(n_bits=5, bn=16, bm=16, tiles_per_call=32)


def _corpus(seed, n_a=40, n_b=36, n_entities=12, dim=16, noise=0.15):
    """Entity-clustered embeddings (same shape as the conftest factory) —
    real candidate structure at cosine thresholds, normalized up front so
    both paths score the same rows."""
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_entities, dim))
    mk = lambda n: (cents[rng.integers(0, n_entities, n)]
                    + noise * rng.normal(size=(n, dim))).astype(np.float32)
    a = np.asarray(l2_normalize(jnp.asarray(mk(n_a))))
    b = np.asarray(l2_normalize(jnp.asarray(mk(n_b))))
    return a, b


def _pair_set(rows, cols):
    return set(zip(np.asarray(rows).tolist(), np.asarray(cols).tolist()))


def _assert_parity(cand, a, b, tau, floor):
    """The three-way contract vs the dense oracle."""
    rr, rc, rs = candidates_ref(jnp.asarray(a), jnp.asarray(b), tau)
    dense = _pair_set(rr, rc)
    blocked = _pair_set(cand.rows, cand.cols)
    dmax, extra, _ = candidate_diff((cand.rows, cand.cols, cand.scores),
                                    (rr, rc, rs))
    assert (extra < tau + BAND).all(), "blocking invented candidates"
    recall, n_dense = blocker_recall(cand, a, b, tau)
    assert n_dense == len(dense)
    assert recall >= floor, (recall, floor)
    assert dmax <= BAND
    return dense, blocked


# ---------------------------------------------------------------------------
# batch parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7, 23])
def test_blocked_subset_recall_and_bitwise_parity(seed):
    a, b = _corpus(seed)
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    cand = blocked_candidates(a, b, TAU, cfg, normalize=False)
    dense, blocked = _assert_parity(cand, a, b, TAU, floor=0.95)
    assert cand.dense_cells == len(a) * len(b)


def test_blocking_scores_fewer_cells_than_dense_at_floor_recall():
    """The point of the stage: on a bucket-sparse corpus (many entities
    relative to rows) the blocked path scores strictly fewer cells than the
    dense grid while holding the recall floor.  (On tiny dense-cluster
    corpora cross-table re-scoring can exceed the grid — that trade-off is
    size-dependent, which is why this runs on a larger corpus than the
    parity sweep.)"""
    rng = np.random.default_rng(0)
    cents = rng.normal(size=(100, 16))
    mk = lambda n: (cents[rng.integers(0, 100, n)]
                    + 0.1 * rng.normal(size=(n, 16))).astype(np.float32)
    a = np.asarray(l2_normalize(jnp.asarray(mk(200))))
    b = np.asarray(l2_normalize(jnp.asarray(mk(200))))
    cfg = BlockingConfig.for_recall(0.95, 0.9, n_bits=6, bn=16, bm=16,
                                    tiles_per_call=64)
    cand = blocked_candidates(a, b, 0.9, cfg, normalize=False)
    assert cand.cells_scored < cand.dense_cells == 200 * 200
    recall, _ = blocker_recall(cand, a, b, 0.9)
    assert recall >= 0.95


def test_dense_tiling_equals_oracle_exactly():
    """The degenerate blocking (full-grid tiles) IS the dense path: same
    set, zero misses — isolates kernel-vs-oracle parity
    from bucket-recall effects."""
    a, b = _corpus(3, n_a=37, n_b=51)
    cfg = BlockingConfig(**CFG_KW)
    ta, tb = dense_block_pairs(len(a), len(b), cfg.bn, cfg.bm)
    cand = score_block_pairs(a, b, ta, tb, TAU, cfg)
    rr, rc, _ = candidates_ref(jnp.asarray(a), jnp.asarray(b), TAU)
    assert _pair_set(cand.rows, cand.cols) == _pair_set(rr, rc)
    assert cand.n_dropped == 0
    recall, _ = blocker_recall(cand, a, b, TAU)
    assert recall == 1.0


def test_blocker_recall_row_subsample():
    """Recall measured on a row subsample uses only those rows' dense
    candidates — the mechanism the 10M-cell bench relies on to validate
    recall without ever scoring its full grid."""
    a, b = _corpus(11)
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    cand = blocked_candidates(a, b, TAU, cfg, normalize=False)
    sample = np.arange(0, len(a), 2)
    recall, n_dense = blocker_recall(cand, a, b, TAU, row_sample=sample)
    rr, _, _ = candidates_ref(jnp.asarray(a), jnp.asarray(b), TAU)
    assert n_dense == int(np.isin(np.asarray(rr), sample).sum())
    assert 0.95 <= recall <= 1.0


# ---------------------------------------------------------------------------
# streaming epochs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [1, 19])
def test_streaming_epochs_union_equals_batch_blocked(seed):
    """Epoch-by-epoch blocked appends must reproduce the batch blocked set
    exactly (same buckets — signatures are deterministic in the seed), with
    no cross-epoch duplicates, bitwise scores, and strictly less scoring
    work than dense."""
    rng = np.random.default_rng(seed)
    a, b = _corpus(seed, n_a=70, n_b=60)
    cuts_a = sorted(rng.integers(1, len(a), 2))
    cuts_b = sorted(rng.integers(1, len(b), 2))
    a_parts = np.split(a, cuts_a)
    b_parts = np.split(b, cuts_b)
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    idx = StreamingCandidateIndex(TAU, make_host_mesh(1, 1), blocking=cfg,
                                  normalize=False, impl="interpret")
    union = set()
    scores = {}
    for na, nb in zip(a_parts, b_parts):
        cand = idx.append(new_a=na if len(na) else None,
                          new_b=nb if len(nb) else None)
        fresh = _pair_set(cand.rows, cand.cols)
        assert not (fresh & union), "cross-epoch duplicate candidate"
        union |= fresh
        scores.update({(r, c): s for r, c, s in
                       zip(cand.rows.tolist(), cand.cols.tolist(),
                           cand.scores.tolist())})
    batch = blocked_candidates(a, b, TAU, cfg, normalize=False)
    assert union == _pair_set(batch.rows, batch.cols)
    batch_scores = {(r, c): s for r, c, s in
                    zip(batch.rows.tolist(), batch.cols.tolist(),
                        batch.scores.tolist())}
    assert all(np.float32(scores[k]) == np.float32(batch_scores[k])
               for k in union)
    # incremental blocked work beats per-epoch full re-runs
    assert idx.pairs_scored < idx.full_rescore_pairs
    # the union also satisfies the dense-parity contract
    _assert_parity(batch, a, b, TAU, floor=0.95)


# ---------------------------------------------------------------------------
# config + capacity contracts
# ---------------------------------------------------------------------------
def test_blocking_config_validation():
    with pytest.raises(ValueError, match="n_bits"):
        BlockingConfig(n_bits=0)
    with pytest.raises(ValueError, match="n_bits"):
        BlockingConfig(n_bits=40)
    with pytest.raises(ValueError, match="n_tables"):
        BlockingConfig(n_tables=0)
    with pytest.raises(ValueError, match="tiles_per_call"):
        BlockingConfig(tiles_per_call=0)
    with pytest.raises(ValueError, match="floor"):
        BlockingConfig.for_recall(1.5, 0.8)
    with pytest.raises(ValueError, match="max_tables"):
        # recall 0.999 at a low threshold with fine buckets needs more
        # tables than allowed — must raise, not silently under-deliver
        BlockingConfig.for_recall(0.999, 0.3, n_bits=12, max_tables=4)


def test_expected_recall_monotone_and_for_recall_clears_floor():
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    assert cfg.recall_floor == 0.95
    # analytic capture at the threshold boundary clears the floor, and
    # rises with similarity (the boundary is the worst case)
    assert expected_recall(cfg, TAU) >= 0.95
    sims = [TAU, 0.9, 0.95, 0.99, 1.0]
    vals = [expected_recall(cfg, s) for s in sims]
    assert all(x <= y + 1e-12 for x, y in zip(vals, vals[1:]))
    # more tables never hurt recall
    more = BlockingConfig(n_bits=cfg.n_bits, n_tables=cfg.n_tables + 4)
    assert expected_recall(more, TAU) >= expected_recall(cfg, TAU) - 1e-12


def test_signatures_deterministic_and_seed_sensitive():
    a, _ = _corpus(5)
    cfg = BlockingConfig(**CFG_KW)
    s1 = signatures(a, cfg)
    s2 = signatures(a, cfg)
    np.testing.assert_array_equal(s1, s2)
    assert s1.shape == (cfg.n_tables, len(a))
    s3 = signatures(a, BlockingConfig(seed=1, **CFG_KW))
    assert not np.array_equal(s1, s3)
    # streaming invariant: hashing rows in two halves == hashing them at once
    half = np.concatenate([signatures(a[:17], cfg),
                           signatures(a[17:], cfg)], axis=1)
    np.testing.assert_array_equal(half, s1)


def _reference_projection(x, cfg):
    """(n_tables, N, n_bits) float64 projections on the same seeded planes."""
    rng = np.random.default_rng(cfg.seed)
    planes = rng.normal(size=(cfg.n_tables, x.shape[1], cfg.n_bits)
                        ).astype(np.float32)
    return np.einsum("nd,ldb->lnb", x.astype(np.float64),
                     planes.astype(np.float64))


def test_signature_codes_match_float64_reference():
    """The device codes are the sign bits of the seeded projections: equal
    to a float64 reference wherever no projection lies within rounding of
    zero (a sign there may go either way)."""
    a, _ = _corpus(8, n_a=300, dim=32)
    cfg = BlockingConfig(n_bits=7, n_tables=9)
    proj = _reference_projection(a, cfg)
    ref = (proj >= 0.0) @ (np.int64(1) << np.arange(cfg.n_bits))
    clear = (np.abs(proj) > 1e-5).all(axis=2)
    assert clear.mean() > 0.99
    np.testing.assert_array_equal(signatures(a, cfg)[clear], ref[clear])


@pytest.mark.parametrize("n_bits", [1, 5, 30])
def test_signature_codes_are_int64_tables_of_n_bits(n_bits):
    a, _ = _corpus(9, n_a=50)
    codes = signatures(a, BlockingConfig(n_bits=n_bits, n_tables=3))
    assert codes.dtype == np.int64 and codes.shape == (3, len(a))
    assert codes.min() >= 0 and codes.max() < 2 ** n_bits


def test_signatures_numpy_and_device_inputs_agree():
    a, _ = _corpus(10)
    cfg = BlockingConfig(**CFG_KW)
    np.testing.assert_array_equal(signatures(a, cfg),
                                  signatures(jnp.asarray(a), cfg))


ROWS = 8   # signature row chunk in the test below


@pytest.mark.parametrize("cut", [1, ROWS - 1, ROWS + 1])
def test_signatures_invariant_across_chunk_boundary(monkeypatch, cut):
    """Rows hashed in two calls split anywhere, across the fixed row chunk
    too, get the codes they get in one call: the streaming invariant."""
    monkeypatch.setattr(blocking, "_SIGNATURE_ROWS", ROWS)
    a, _ = _corpus(12, n_a=ROWS + 3)
    cfg = BlockingConfig(**CFG_KW)
    whole = signatures(a, cfg)
    split = np.concatenate([signatures(a[:cut], cfg),
                            signatures(jnp.asarray(a[cut:]), cfg)], axis=1)
    np.testing.assert_array_equal(split, whole)


def test_blocked_candidates_reads_back_codes_and_chunks_only():
    """The blocked machine phase reads the host nothing but one array of
    codes per side and one result per kernel chunk: no embedding."""
    a, b = _corpus(14, n_a=60, n_b=56)
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    s0 = obs.host_syncs.count
    cand = blocked_candidates(jnp.asarray(a), jnp.asarray(b), TAU, cfg)
    syncs = obs.host_syncs.count - s0
    n_chunks = -(-cand.n_tiles // min(cfg.tiles_per_call,
                                       next_pow2(cand.n_tiles, floor=1)))
    assert n_chunks >= 2
    assert syncs == 2 + n_chunks


def test_blocked_capacity_overflow_and_suggested_retry():
    a, b = _corpus(2)
    cfg = BlockingConfig.for_recall(0.95, TAU, **CFG_KW)
    small = blocked_candidates(a, b, TAU, cfg, capacity=6, normalize=False)
    assert small.n_dropped > 0
    assert len(small) <= 6
    retry = blocked_candidates(a, b, TAU, cfg,
                               capacity=small.suggested_capacity,
                               normalize=False)
    assert retry.n_dropped == 0
    # kept-under-pressure candidates are a subset of the lossless set
    assert _pair_set(small.rows, small.cols) <= \
        _pair_set(retry.rows, retry.cols)


# ---------------------------------------------------------------------------
# service integration (submit_embeddings / append_embeddings with blocking)
# ---------------------------------------------------------------------------
def _entity_corpus(seed, n_a=60, n_b=52, n_entities=12, noise=0.1):
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_entities, 16))
    ids_a = rng.integers(0, n_entities, n_a)
    ids_b = rng.integers(0, n_entities, n_b)
    a = (cents[ids_a] + noise * rng.normal(size=(n_a, 16))).astype(np.float32)
    b = (cents[ids_b] + noise * rng.normal(size=(n_b, 16))).astype(np.float32)
    return ids_a, a, ids_b, b, cents


def test_join_service_blocked_end_to_end():
    """submit_embeddings with a blocking config: blocked machine phase feeds
    the normal crowd/deduce loop and finishes with perfect precision."""
    from repro.serve.join_service import JoinService

    ids_a, a, ids_b, b, _ = _entity_corpus(21)
    truth_fn = lambda r, c: np.asarray(ids_a[np.asarray(r)]
                                       == ids_b[np.asarray(c)])
    svc = JoinService(lanes=1)
    cfg = BlockingConfig.for_recall(0.95, 0.8, **CFG_KW)
    rid = svc.submit_embeddings(jnp.asarray(a), jnp.asarray(b), 0.8,
                                make_host_mesh(1, 1), truth_fn=truth_fn,
                                impl="interpret", blocking=cfg)
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0
    assert res.labels.sum() > 0


def test_submit_embeddings_blocked_overflow_raises_then_suggested_fits():
    """Satellite regression: blocked overflow at submit must raise the
    standard re-submit message, leave no stream registered, and the
    suggested capacity must actually fit on retry."""
    from repro.serve.join_service import JoinService

    ids_a, a, ids_b, b, _ = _entity_corpus(4)
    truth_fn = lambda r, c: np.asarray(ids_a[np.asarray(r)]
                                       == ids_b[np.asarray(c)])
    svc = JoinService(lanes=1)
    cfg = BlockingConfig.for_recall(0.95, 0.8, **CFG_KW)
    mesh = make_host_mesh(1, 1)
    with pytest.raises(RuntimeError, match=r"re-submit with capacity=\d+") \
            as exc:
        svc.submit_embeddings(jnp.asarray(a), jnp.asarray(b), 0.8, mesh,
                              truth_fn=truth_fn, capacity=4,
                              impl="interpret", streaming=True, blocking=cfg)
    # the failed submit must not leave a half-registered stream behind
    assert not svc._streams
    cap = int(re.search(r"capacity=(\d+)", str(exc.value)).group(1))
    rid = svc.submit_embeddings(jnp.asarray(a), jnp.asarray(b), 0.8, mesh,
                                truth_fn=truth_fn, capacity=cap,
                                impl="interpret", streaming=True,
                                blocking=cfg)
    lossless = blocked_candidates(jnp.asarray(a), jnp.asarray(b), 0.8,
                                  cfg, impl="interpret")
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0
    # the retried capacity kept every blocked candidate
    assert len(res.labels) == len(lossless)


def test_append_embeddings_blocked_overflow_rolls_back_the_epoch():
    """Mirror of the PR 5 atomic-rollback regression, under blocking: a
    rejected arrival must also forget the *bucket/code caches* for the
    failed rows — a stale signature column would desync every later epoch's
    bucket matching, not just the row -> id maps."""
    from repro.serve.join_service import JoinService

    ids_a, a, ids_b, b, cents = _entity_corpus(13, n_a=12, n_b=10)
    all_a, all_b = list(ids_a), list(ids_b)
    truth_fn = lambda r, c: (np.asarray(all_a)[np.asarray(r)]
                             == np.asarray(all_b)[np.asarray(c)])
    svc = JoinService(lanes=1)
    # coarse buckets (this test is about rollback, not recall) and a
    # capacity that fits the 12 x 10 submit but not the 90-row arrival
    cfg = BlockingConfig(n_bits=3, n_tables=6, bn=16, bm=16,
                         tiles_per_call=32)
    rid = svc.submit_embeddings(jnp.asarray(a), jnp.asarray(b), 0.5,
                                make_host_mesh(1, 1), truth_fn=truth_fn,
                                capacity=128, impl="interpret",
                                streaming=True, blocking=cfg)
    stream = svc._streams[rid]
    rng = np.random.default_rng(99)
    big_ids = rng.integers(0, len(cents), 90)
    big = (cents[big_ids] + 0.1 * rng.normal(size=(90, 16))
           ).astype(np.float32)
    with pytest.raises(RuntimeError, match="rolled back"):
        svc.append_embeddings(rid, jnp.asarray(big), None)
    # corpus, id maps AND signature caches all reverted
    assert stream.index.n_a == len(stream.ids_a) == 12
    assert stream.index._codes_a.shape[1] == 12
    small_ids = rng.integers(0, len(cents), 3)
    small = (cents[small_ids] + 0.1 * rng.normal(size=(3, 16))
             ).astype(np.float32)
    all_a += list(small_ids)
    svc.append_embeddings(rid, jnp.asarray(small), None)
    assert stream.index.n_a == len(stream.ids_a) == 15
    assert stream.index._codes_a.shape[1] == 15
    res = svc.run()[rid]
    assert res.quality is not None and res.quality.precision == 1.0


# ---------------------------------------------------------------------------
# property-based variants (hypothesis; skipped where not installed)
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 10**6))
@settings(max_examples=15, deadline=None, derandomize=True)
def test_property_blocked_parity(seed):
    """For any drawn corpus: blocked subset of dense, recall >= floor,
    score parity within ``BAND``.  The floor holds by for_recall's analytic
    headroom at the boundary (capture at s=tau >= 1 - (1-floor)/20)."""
    a, b = _corpus(seed)
    cfg = BlockingConfig.for_recall(0.9, TAU, **CFG_KW)
    cand = blocked_candidates(a, b, TAU, cfg, normalize=False)
    _assert_parity(cand, a, b, TAU, floor=0.9)


@given(seed=st.integers(0, 10**6), cut=st.integers(1, 39))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_property_streaming_union_matches_batch(seed, cut):
    """For any drawn corpus and epoch split: the union of streaming blocked
    epochs equals the batch blocked set exactly, and satisfies the same
    dense-parity contract."""
    a, b = _corpus(seed)
    cfg = BlockingConfig.for_recall(0.9, TAU, **CFG_KW)
    idx = StreamingCandidateIndex(TAU, make_host_mesh(1, 1), blocking=cfg,
                                  normalize=False, impl="interpret")
    cut_b = min(cut, len(b) - 1)
    union = set()
    for na, nb in ((a[:cut], b[:cut_b]), (a[cut:], b[cut_b:])):
        cand = idx.append(new_a=na if len(na) else None,
                          new_b=nb if len(nb) else None)
        fresh = _pair_set(cand.rows, cand.cols)
        assert not (fresh & union)
        union |= fresh
    batch = blocked_candidates(a, b, TAU, cfg, normalize=False)
    assert union == _pair_set(batch.rows, batch.cols)
    _assert_parity(batch, a, b, TAU, floor=0.9)
