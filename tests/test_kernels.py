"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode
(the kernels target TPU; interpret executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
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
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,K,d", [
    (2, 256, 4, 4, 64),     # MHA
    (1, 512, 8, 2, 128),    # GQA 4:1, d=128
    (2, 384, 6, 3, 64),     # GQA 2:1, non-pow2 S
    (1, 128, 2, 1, 128),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, K, d, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, K, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, K, d)), dtype)
    o = flash_attention(q, k, v, impl="interpret")
    r = flash_attention(q, k, v, impl="ref")
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


def test_flash_attention_block_shape_invariance():
    q = jnp.asarray(RNG.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 256, 2, 64)), jnp.float32)
    o1 = flash_attention(q, k, v, impl="interpret", bq=128, bk=128)
    o2 = flash_attention(q, k, v, impl="interpret", bq=64, bk=256)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,K,d,length", [
    (2, 1024, 8, 2, 64, 700),
    (1, 2048, 4, 4, 128, 2048),
    (3, 512, 6, 2, 64, 1),
    (2, 512, 8, 8, 64, 311),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, H, K, d, length, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, d)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, S, K, d)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, S, K, d)), dtype)
    o = decode_attention(q, kc, vc, jnp.int32(length), impl="interpret")
    r = decode_attention(q, kc, vc, jnp.int32(length), impl="ref")
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


def test_decode_attention_ignores_tail_garbage():
    """Entries past `length` must not affect the result."""
    B, S, H, K, d = 1, 512, 4, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, H, d)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(B, S, K, d)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(B, S, K, d)), jnp.float32)
    o1 = decode_attention(q, kc, vc, jnp.int32(100), impl="interpret")
    kc2 = kc.at[:, 100:].set(1e9)
    vc2 = vc.at[:, 100:].set(-1e9)
    o2 = decode_attention(q, kc2, vc2, jnp.int32(100), impl="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
