"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode
(the kernels target TPU; interpret executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.pair_scores.kernel import pair_scores_compact
from repro.kernels.pair_scores.ops import l2_normalize, pair_scores
from repro.kernels.pair_scores.ref import candidate_diff, candidates_ref

RNG = np.random.default_rng(0)
# score agreement with the oracle: a few f32 ulps near 1.0
BAND = 1e-6


def _pallas_interpret_available() -> bool:
    """Probe once whether Pallas interpret-mode lowering works on this
    install (it can be missing/broken on exotic jax builds); the compact
    kernel tier skips — not fails — without it."""
    if not hasattr(_pallas_interpret_available, "ok"):
        try:
            x = jnp.ones((1, 4), jnp.float32)
            ids = jnp.zeros((1, 1), jnp.int32)
            pair_scores_compact(x, x, ids, ids, 0.5, 4, 1, 1, interpret=True)
            _pallas_interpret_available.ok = True
        except Exception:
            _pallas_interpret_available.ok = False
    return _pallas_interpret_available.ok


needs_pallas_interpret = pytest.mark.skipif(
    not _pallas_interpret_available(),
    reason="Pallas interpret-mode lowering unavailable on this jax install")


def _compact_dense(a, b, threshold, capacity, bn, bm, interpret=True):
    """Run pair_scores_compact over a full-grid tiling of (a, b) and return
    (rows, cols, scores, n_total) with padding/tail stripped."""
    from repro.kernels.pair_scores.blocking import dense_block_pairs

    N, D = a.shape
    M = b.shape[0]
    ta, tb = dense_block_pairs(N, M, bn, bm)
    a_ext = jnp.concatenate([a, jnp.zeros((1, D), a.dtype)])
    b_ext = jnp.concatenate([b, jnp.zeros((1, D), b.dtype)])
    ga = np.where(ta < 0, N, ta).reshape(-1)
    gb = np.where(tb < 0, M, tb).reshape(-1)
    rows, cols, scores, n_tot = pair_scores_compact(
        a_ext[jnp.asarray(ga)], b_ext[jnp.asarray(gb)],
        jnp.asarray(ta.reshape(-1, 1).astype(np.int32)),
        jnp.asarray(tb.reshape(-1, 1).astype(np.int32)),
        float(threshold), int(capacity), bn, bm, interpret=interpret)
    rows = np.asarray(rows)
    keep = rows >= 0
    return (rows[keep], np.asarray(cols)[keep], np.asarray(scores)[keep],
            int(n_tot))


# ---------------------------------------------------------------------------
# pair_scores
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,M,D", [(256, 256, 128), (512, 384, 64),
                                   (300, 200, 96), (128, 128, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pair_scores_sweep(N, M, D, dtype):
    a = jnp.asarray(RNG.normal(size=(N, D)), dtype)
    b = jnp.asarray(RNG.normal(size=(M, D)), dtype)
    s, c = pair_scores(a, b, 0.2, impl="interpret")
    sr, cr = pair_scores(a, b, 0.2, impl="ref")
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=tol)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))


def test_pair_scores_counts_match_threshold_semantics():
    a = jnp.asarray(RNG.normal(size=(128, 64)), jnp.float32)
    s, c = pair_scores(a, a, 0.5, impl="interpret")
    # self-similarity of normalized rows is 1.0 -> every row has >= 1 cand
    assert (np.asarray(c)[:, 0] >= 1).all()


# ---------------------------------------------------------------------------
# pair_scores_compact: fused similarity + threshold + on-chip compaction
# (DESIGN.md §12) vs the dense ref.py oracle
# ---------------------------------------------------------------------------
@needs_pallas_interpret
@pytest.mark.parametrize("N,M,bn,bm", [(100, 90, 32, 32), (64, 64, 64, 64),
                                       (33, 57, 16, 16), (7, 130, 8, 32)])
def test_pair_scores_compact_matches_dense_oracle(N, M, bn, bm):
    """Full-grid tiling through the compact kernel must reproduce the dense
    oracle's candidate set — scores within ``BAND`` (the tile shape sets the
    f32 summation order), a pair in only one set only if it scores within
    ``BAND`` of the threshold — with the true total count, in tile-list
    order and row-major within a tile, including ragged tile edges."""
    a = l2_normalize(jnp.asarray(RNG.normal(size=(N, 16)), jnp.float32))
    b = l2_normalize(jnp.asarray(RNG.normal(size=(M, 16)), jnp.float32))
    tau = 0.3
    rows, cols, scores, n_tot = _compact_dense(a, b, tau, N * M, bn, bm)
    rr, rc, rs = candidates_ref(a, b, tau)
    assert n_tot == len(rows)
    dmax, extra, missing = candidate_diff((rows, cols, scores),
                                          (rr, rc, rs))
    assert dmax <= BAND
    assert (extra < tau + BAND).all() and (missing < tau + BAND).all()
    # dense_block_pairs lists tiles row-block-major: the output order is
    # (row block, col block, row, col)
    order = np.lexsort((cols, rows, cols // bm, rows // bn))
    np.testing.assert_array_equal(order, np.arange(len(rows)))


@needs_pallas_interpret
def test_pair_scores_compact_threshold_boundary():
    """>= semantics at the boundary: a pair scoring *exactly* tau is a
    candidate; one ulp below is not.  Crafted unit vectors make the f32 dot
    land exactly on tau (0.5 is exactly representable; 1*0.5 + 0*... has no
    rounding)."""
    tau = np.float32(0.5)
    just_below = np.nextafter(tau, np.float32(0.0), dtype=np.float32)
    a = np.zeros((1, 4), np.float32)
    a[0, 0] = 1.0
    b = np.zeros((2, 4), np.float32)
    b[0, 0] = tau
    b[0, 1] = np.sqrt(1.0 - float(tau) ** 2)
    b[1, 0] = just_below
    b[1, 1] = np.sqrt(1.0 - float(just_below) ** 2)
    rows, cols, scores, n_tot = _compact_dense(
        jnp.asarray(a), jnp.asarray(b), float(tau), 8, 8, 8)
    assert n_tot == 1
    assert rows.tolist() == [0] and cols.tolist() == [0]
    assert np.float32(scores[0]) == tau


@needs_pallas_interpret
def test_pair_scores_compact_overflow_counts_true_total():
    """Capacity overflow is a counted contract: the buffer holds exactly
    ``capacity`` candidates, ``n_total`` reports the true count, and the
    driver-level suggested capacity (capacity + dropped, next pow2)
    provably fits on retry."""
    from repro.core.jax_graph import next_pow2

    a = l2_normalize(jnp.asarray(RNG.normal(size=(48, 16)), jnp.float32))
    b = l2_normalize(jnp.asarray(RNG.normal(size=(40, 16)), jnp.float32))
    tau = 0.2
    rr, _, _ = candidates_ref(a, b, tau)
    assert len(rr) > 8  # the workload genuinely overflows capacity=8
    rows, _, _, n_tot = _compact_dense(a, b, tau, 8, 16, 16)
    assert n_tot == len(rr)
    assert len(rows) == 8
    suggested = next_pow2(8 + (n_tot - 8))
    rows2, _, _, n2 = _compact_dense(a, b, tau, suggested, 16, 16)
    assert n2 == len(rr) and len(rows2) == len(rr)


@needs_pallas_interpret
def test_pair_scores_compact_all_padding_tiles():
    """A tile list that is pure padding (sentinel -1 ids, zero gather rows)
    must produce zero candidates — the chunked driver pads with such tiles
    to keep jit cache keys fixed."""
    bn = bm = 8
    a_g = jnp.zeros((bn, 4), jnp.float32)
    b_g = jnp.zeros((bm, 4), jnp.float32)
    ids_a = jnp.full((bn, 1), -1, jnp.int32)
    ids_b = jnp.full((bm, 1), -1, jnp.int32)
    rows, cols, scores, n_tot = pair_scores_compact(
        a_g, b_g, ids_a, ids_b, 0.5, 16, bn, bm, interpret=True)
    assert int(n_tot) == 0
    assert (np.asarray(rows) == -1).all()


# ---------------------------------------------------------------------------
# the embedding cells' width: D = 300 (DeepMatcher's fastText vectors, the
# walmart_amazon and febrl_link deployments), on shapes no tile divides
# ---------------------------------------------------------------------------
D_CELLS = 300


def _clustered(rng, n, cents, noise=0.8):
    """Rows around shared entity centroids: cosine near 0.6 within an
    entity, near 0 across, so the threshold cuts through real structure."""
    ids = rng.integers(0, len(cents), n)
    return cents[ids] + noise * rng.normal(size=(n, cents.shape[1]))


@pytest.mark.parametrize("N,M", [(300, 500), (257, 1000), (1024, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pair_scores_at_cell_width_matches_oracle(N, M, dtype):
    """The dense kernel at D = 300 against the oracle: padded to 256-row
    tiles on both sides, scores within a few ulps (bf16 inputs are scored
    in f32, so they keep the f32 band) and per-row counts exact."""
    rng = np.random.default_rng(N * 7 + M)
    cents = rng.normal(size=(64, D_CELLS))
    a = jnp.asarray(_clustered(rng, N, cents), dtype)
    b = jnp.asarray(_clustered(rng, M, cents), dtype)
    s, c = pair_scores(a, b, 0.5, impl="interpret")
    sr, cr = pair_scores(a, b, 0.5, impl="ref")
    assert s.shape == (N, M) and c.shape == (N, 1)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=BAND)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    assert int(np.asarray(c).sum()) > N // 8   # the threshold bites


@needs_pallas_interpret
@pytest.mark.parametrize("N,M", [
    (16, 128),    # exactly one tile
    (9, 70),      # one tile, ragged on both sides
    (100, 300),   # ragged row and column blocks
    (48, 384),    # tiles divide both sides
])
def test_pair_scores_compact_at_blocked_cell_tile(N, M):
    """``pair_scores_compact`` with ``wa.blocked``'s 16 x 128 tile at
    D = 300: the dense oracle's candidates, in tile-list order, within
    ``BAND`` — one-tile and ragged lists included."""
    rng = np.random.default_rng(N + M)
    cents = rng.normal(size=(24, D_CELLS))
    a = l2_normalize(jnp.asarray(_clustered(rng, N, cents), jnp.float32))
    b = l2_normalize(jnp.asarray(_clustered(rng, M, cents), jnp.float32))
    tau = 0.5
    rows, cols, scores, n_tot = _compact_dense(a, b, tau, N * M, 16, 128)
    rr, rc, rs = candidates_ref(a, b, tau)
    assert n_tot == len(rows) and len(rr) > 0
    dmax, extra, missing = candidate_diff((rows, cols, scores),
                                          (rr, rc, rs))
    assert dmax <= BAND
    assert (extra < tau + BAND).all() and (missing < tau + BAND).all()
    order = np.lexsort((cols, rows, cols // 128, rows // 16))
    np.testing.assert_array_equal(order, np.arange(len(rows)))


@pytest.mark.parametrize("variant", ["one_piece", "row_chunked", "overflow"])
def test_sharded_candidates_at_cell_width(monkeypatch, variant):
    """``wa.dense``'s program on a 1x1 mesh at D = 300: the oracle's
    candidates in row-major order, whether the block is compacted in one
    piece or seven rows at a time; under a capacity of half of them, the
    first half in the same order and the rest counted as dropped."""
    from repro.kernels.pair_scores import sharded
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(300)
    cents = rng.normal(size=(40, D_CELLS))
    n = 301 if variant == "row_chunked" else 300   # a shape of its own
    a = jnp.asarray(_clustered(rng, n, cents), jnp.float32)
    b = jnp.asarray(_clustered(rng, 500, cents), jnp.float32)
    mesh = make_host_mesh(1, 1)
    if variant == "row_chunked":
        monkeypatch.setattr(sharded, "CHUNK_CELLS", 7 * 500)
        assert sharded._chunk_rows(n, 500) == 7
    full = sharded.sharded_candidates(a, b, 0.5, mesh)
    ref = candidates_ref(l2_normalize(a), l2_normalize(b), 0.5)
    assert full.n_dropped == 0 and len(full) > 500
    dmax, extra, missing = candidate_diff(
        (full.rows, full.cols, full.scores), ref)
    assert dmax <= BAND
    assert (extra < 0.5 + BAND).all() and (missing < 0.5 + BAND).all()
    np.testing.assert_array_equal(np.lexsort((full.cols, full.rows)),
                                  np.arange(len(full)))
    if variant == "overflow":
        cap = len(full) // 2
        half = sharded.sharded_candidates(a, b, 0.5, mesh, capacity=cap)
        np.testing.assert_array_equal(half.rows, full.rows[:cap])
        np.testing.assert_array_equal(half.cols, full.cols[:cap])
        np.testing.assert_array_equal(half.scores, full.scores[:cap])
        assert half.n_dropped == len(full) - cap
        assert half.suggested_capacity >= len(full)
