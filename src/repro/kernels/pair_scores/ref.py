"""Pure-jnp oracles for the pair-similarity kernels: the dense score matrix
(``pair_scores_ref``) and the dense candidate list (``candidates_ref``) the
blocked+fused path is property-tested against (DESIGN.md §12).

Every similarity product — here and inside the kernels — goes through
:func:`similarity`, which pins f32 precision: a TPU runs an f32 product at
default precision as one bf16 pass, which would put kernel and oracle apart
near the threshold for reasons unrelated to correctness.  What precision
cannot pin is summation order, which follows tile shape and backend;
:func:`candidate_diff` is the comparison that allows for it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def similarity(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(n, D) x (m, D) -> (n, m) f32 dot products at full f32 precision."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def pair_scores_ref(a: jnp.ndarray, b: jnp.ndarray, threshold: float):
    """Cosine-style similarity of every (row of a, row of b) pair.

    a: (N, D), b: (M, D) — L2-normalized embeddings.
    Returns (scores (N, M) f32 zeroed below threshold, counts (N,) i32 of
    above-threshold candidates per left record)."""
    s = similarity(a, b)
    mask = s >= threshold
    return jnp.where(mask, s, 0.0), mask.sum(axis=1).astype(jnp.int32)


def candidates_ref(a: jnp.ndarray, b: jnp.ndarray, threshold: float):
    """Dense candidate oracle: every (i, j) with similarity >= threshold,
    in row-major order.  a/b must already be L2-normalized — the parity
    tests feed both paths the same normalized arrays.

    Returns (rows (C,) i32, cols (C,) i32, scores (C,) f32)."""
    s = np.asarray(similarity(a, b))
    rows, cols = np.nonzero(s >= threshold)
    return (rows.astype(np.int32), cols.astype(np.int32),
            s[rows, cols].astype(np.float32))


def candidate_diff(got, ref):
    """Compare two (rows, cols, scores) candidate lists pair by pair.

    Summation order (tile shape, backend) can move a score by an ulp or
    so, and so move a pair scored at the threshold across it; callers
    bound both with a band: the max score difference stays within it, and
    every pair in one list only scores within it above the threshold.

    Returns ``(max |score difference| over shared pairs, scores of pairs
    only in ``got``, scores of pairs only in ``ref``)``."""
    (gr, gc, gs), (rr, rc, rs) = [
        tuple(np.asarray(x) for x in side) for side in (got, ref)]
    width = np.int64(max(gc.max(initial=0), rc.max(initial=0)) + 1)
    kg = gr.astype(np.int64) * width + gc
    kr = rr.astype(np.int64) * width + rc
    _, ig, ir = np.intersect1d(kg, kr, return_indices=True)
    dmax = float(np.abs(gs[ig].astype(np.float64) - rs[ir]).max(initial=0.0))
    return dmax, gs[~np.isin(kg, kr)], rs[~np.isin(kr, kg)]
