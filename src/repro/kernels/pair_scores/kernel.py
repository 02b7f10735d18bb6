"""Pallas TPU kernels: blocked all-pairs similarity + fused thresholding,
over the dense grid and over a gathered tile list (DESIGN.md §12).

The machine phase of the paper's pipeline scores N x M candidate pairs
(496K for Cora; O(N^2) in general).  On TPU this is a classic MXU tiling
problem: stream (bn x D) / (bm x D) embedding tiles through VMEM, one
(bn x bm) MXU matmul per grid cell, fuse the threshold test so the sparse
candidate structure (scores zeroed below tau + per-row counts) comes out of
the kernel without a second pass over HBM.

``pair_scores`` keeps the dense layout (grid (N/bn, M/bm); the per-row
count accumulator revisits its (bn, 1) block across the sequential minor
grid axis).  ``pair_scores_compact`` is the scale-unlock variant: it walks
a *list* of gathered bucket tiles (grid (T,)), writes each tile's
thresholded (bn, bm) scores to HBM, and one XLA compaction in the same jit
packs the above-threshold triples (row, col, score) into a fixed-capacity
buffer — a cumsum of the candidate mask gives every candidate its slot and
a scatter writes it, so the dense score matrix exists one chunk of tiles at
a time.  Overflow is a counted contract, not a crash: candidates past
``capacity`` are dropped and the true total comes back for the caller's
``suggested_capacity`` arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import similarity

DEFAULT_BN = 256
DEFAULT_BM = 256


def _make_kernel(threshold: float):
    def kernel(a_ref, b_ref, out_ref, cnt_ref):
        j = pl.program_id(1)
        s = similarity(a_ref[...], b_ref[...])          # (bn, bm)
        mask = s >= threshold
        out_ref[...] = jnp.where(mask, s, 0.0)

        @pl.when(j == 0)
        def _init():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        cnt_ref[...] += mask.sum(axis=1, keepdims=True).astype(jnp.int32)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("threshold", "bn", "bm", "interpret"))
def pair_scores(a: jax.Array, b: jax.Array, threshold: float,
                bn: int = DEFAULT_BN, bm: int = DEFAULT_BM,
                interpret: bool = False):
    """a: (N, D), b: (M, D) L2-normalized; returns (scores (N, M) f32 with
    sub-threshold entries zeroed, per-row candidate counts (N, 1) i32)."""
    N, D = a.shape
    M, _ = b.shape
    bn = min(bn, N)
    bm = min(bm, M)
    assert N % bn == 0 and M % bm == 0, (N, M, bn, bm)
    grid = (N // bn, M // bm)
    return pl.pallas_call(
        _make_kernel(float(threshold)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, M), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
        ],
        interpret=interpret,
        name="pair_scores",
    )(a, b)


def _make_tile_kernel(threshold: float):
    def kernel(a_ref, b_ref, out_ref):
        s = similarity(a_ref[...], b_ref[...])          # (bn, bm)
        out_ref[0] = jnp.where(s >= threshold, s, 0.0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("threshold", "capacity", "bn", "bm",
                                    "interpret"))
def pair_scores_compact(a_g: jax.Array, b_g: jax.Array,
                        ida: jax.Array, idb: jax.Array,
                        threshold: float, capacity: int,
                        bn: int, bm: int, interpret: bool = False):
    """Fused similarity + threshold over gathered bucket tiles, then
    candidate compaction (DESIGN.md §12).

    a_g: (T*bn, D) / b_g: (T*bm, D) — tile-gathered L2-normalized
    embeddings (tile t's rows live at [t*bn, (t+1)*bn)); padding rows are
    zero vectors.  ida: (T*bn, 1) / idb: (T*bm, 1) int32 global row/col
    ids, -1 on padding.  Requires ``threshold > 0`` so zero padding can
    never score as a candidate.

    Returns (rows (capacity,) i32, cols ditto, scores ditto f32, n_total
    () i32).  Entries [0, min(n_total, capacity)) are the candidates in
    tile-list order, row-major within a tile; the tail is marked row/col
    -1, score 0.  n_total is the true candidate count, so
    ``n_total - capacity`` (when positive) is the overflow the caller must
    surface.
    """
    T = a_g.shape[0] // bn
    D = a_g.shape[1]
    C = int(capacity)
    tiles = pl.pallas_call(
        _make_tile_kernel(float(threshold)),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda t: (t, 0)),
            pl.BlockSpec((bm, D), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, bm), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, bn, bm), jnp.float32),
        interpret=interpret,
        name="pair_scores_compact",
    )(a_g, b_g)
    ida = ida.reshape(-1)
    idb = idb.reshape(-1)
    # id -1 marks tile padding; padded gather rows are also zero vectors,
    # so with threshold > 0 the id mask is belt-and-braces
    mask = ((tiles >= threshold)
            & (ida.reshape(T, bn, 1) >= 0)
            & (idb.reshape(T, 1, bm) >= 0)).reshape(-1)
    # stable compaction: each candidate's slot is its rank in flat
    # (tile, row, col) order; ranks past the capacity scatter out of range
    # and are dropped, so the buffer keeps the first C candidates
    slot = jnp.cumsum(mask, dtype=jnp.int32) - 1
    n_total = slot[-1] + 1
    src = jnp.zeros((C,), jnp.int32).at[
        jnp.where(mask, slot, C)].set(
            jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")
    got = jnp.arange(C) < n_total
    t = src // (bn * bm)
    rows = ida[src // bm]                      # = ida[t*bn + row in tile]
    cols = idb[t * bm + src % bm]
    return (jnp.where(got, rows, -1), jnp.where(got, cols, -1),
            jnp.where(got, tiles.reshape(-1)[src], 0.0), n_total)
