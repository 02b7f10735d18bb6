"""Sharded candidate generation: the pair-scores kernel across a mesh.

The machine phase scores an N x M similarity grid — O(N^2) work that a
single device cannot hold once N reaches web scale.  This driver tiles the
grid over the 2-D (data, model) mesh of ``repro.launch.mesh``
(DESIGN.md §7): ``a`` rows shard over ``data``, ``b`` rows shard over
``model``, every device scores its (N/dd) x (M/dm) block with the Pallas
kernel, and — the important part — *compacts its above-threshold candidates
into a fixed-capacity buffer on device*.  Only candidate triples
(row, col, score) ever cross the mesh; the dense score matrix is never
materialized on one host.

Capacity is a hard contract: a device that finds more than ``capacity``
local candidates reports the overflow in ``n_dropped`` (callers either
raise, re-run with a higher threshold, or grow the buffer) — never a silent
truncation.

A device compacts its block with a stable argsort of the candidate mask,
one row chunk of at most ``CHUNK_CELLS`` cells at a time, into one buffer
in row-major order.  A block up to that size (a product join on one chip)
is one chunk; a person-registry linkage puts 25,000 x 25,000 on each
device of a 2x2 mesh, where one sort over the whole block would hold
several block-sized sort buffers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs

from .kernel import pair_scores as _kernel_call
from .ops import l2_normalize


# Most cells of a device block compacted by one argsort.
CHUNK_CELLS = 1 << 26


def _chunk_rows(n_loc: int, m_loc: int) -> int:
    """Rows per compaction chunk of an (n_loc, m_loc) device block:
    ``n_loc`` (one piece) up to ``CHUNK_CELLS`` cells."""
    if n_loc * m_loc <= CHUNK_CELLS:
        return n_loc
    return max(1, CHUNK_CELLS // m_loc)


def _compact_by_rows(s, mask, capacity: int, chunk_rows: int, i0=0, j0=0):
    """The first ``capacity`` candidate cells (``mask``) of the block ``s``
    in row-major order — row and column (offset by the block's origin
    ``i0``, ``j0``), -1 past the candidates, and score — with the block's
    candidate count: what one stable argsort of the whole mask gives, one
    ``chunk_rows`` slice of rows at a time, with indices that stay within
    a chunk.  Chunk k appends its first
    ``capacity`` candidates at the running count (clamped to ``capacity``),
    so later chunks overwrite the unfilled tail and anything past
    ``capacity`` falls off the end."""
    n_loc, m_loc = s.shape
    R = chunk_rows
    take_k = min(capacity, R * m_loc)
    ragged = n_loc % R != 0

    def put(buf, x, off):
        return jax.lax.dynamic_update_slice(buf, x, (off,))

    def chunk(k, carry):
        rows, cols, sc, total = carry
        r0 = k * R
        a0 = jnp.minimum(r0, n_loc - R)   # the last chunk slides back ...
        blk_s = jax.lax.dynamic_slice_in_dim(s, a0, R, axis=0)
        blk_m = jax.lax.dynamic_slice_in_dim(mask, a0, R, axis=0)
        if ragged:  # ... and leaves the rows of the chunk before it to it
            blk_m = blk_m & (a0 + jnp.arange(R)[:, None] >= r0)
        flat_m = blk_m.reshape(-1)
        take = jnp.argsort(~flat_m, stable=True)[:take_k]
        got = flat_m[take]
        off = jnp.minimum(total, capacity)
        rows = put(rows, jnp.where(got, i0 + a0 + take // m_loc, -1), off)
        cols = put(cols, jnp.where(got, j0 + take % m_loc, -1), off)
        sc = put(sc, jnp.where(got, blk_s.reshape(-1)[take], 0.0), off)
        return rows, cols, sc, total + flat_m.sum(dtype=jnp.int32)

    size = capacity + take_k
    init = (jnp.full((size,), -1, jnp.int32), jnp.full((size,), -1, jnp.int32),
            jnp.zeros((size,), jnp.float32), jnp.int32(0))
    rows, cols, sc, total = jax.lax.fori_loop(0, -(-n_loc // R), chunk, init)
    return rows[:capacity], cols[:capacity], sc[:capacity], total


def _mesh_extents(mesh: Mesh):
    ext = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ext.get("data", 1), ext.get("model", 1)


def _pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


@dataclasses.dataclass
class ShardedCandidates:
    """Thresholded candidates gathered from per-device compaction buffers."""

    rows: np.ndarray     # (C,) int32 global row (index into a)
    cols: np.ndarray     # (C,) int32 global col (index into b)
    scores: np.ndarray   # (C,) float32 similarity
    n_dropped: int       # candidates lost to per-device capacity overflow
    capacity: int = 0    # per-device capacity actually used

    def __len__(self) -> int:
        return len(self.rows)

    @property
    def suggested_capacity(self) -> int:
        """Per-device capacity that provably fits this workload — the
        post-growth number a streaming caller should re-submit (or keep
        appending) with.  ``capacity + n_dropped`` covers the worst case of
        every dropped candidate landing on one device; rounded up to the
        next power of two so it lands on a stable jit-cache bucket."""
        from repro.core.jax_graph import next_pow2

        return next_pow2(self.capacity + self.n_dropped)


def _local_block_scores(a_loc, b_loc, threshold: float, interpret: bool):
    """Score one device's (n_loc, m_loc) block with the Pallas kernel,
    handling tile-multiple padding locally (same scheme as ops.pair_scores)."""
    from .kernel import DEFAULT_BM, DEFAULT_BN

    N, M = a_loc.shape[0], b_loc.shape[0]
    bn = min(DEFAULT_BN, N)
    bm = min(DEFAULT_BM, M)
    pn = (-N) % bn
    pm = (-M) % bm
    if pn or pm:
        a_loc = jnp.pad(a_loc, ((0, pn), (0, 0)))
        b_loc = jnp.pad(b_loc, ((0, pm), (0, 0)))
    s, _ = _kernel_call(a_loc, b_loc, float(threshold), bn=bn, bm=bm,
                        interpret=interpret)
    return s[:N, :M]


@functools.partial(jax.jit,
                   static_argnames=("threshold", "capacity", "mesh",
                                    "interpret"))
def _sharded_candidates_jit(a, b, *, threshold: float, capacity: int,
                            mesh: Mesh, interpret: bool):
    dd, dm = _mesh_extents(mesh)
    n_loc = a.shape[0] // dd
    m_loc = b.shape[0] // dm
    chunk_rows = _chunk_rows(n_loc, m_loc)

    def body(a_loc, b_loc):
        # a_loc: (n_loc, D) on this data-rank; b_loc: (m_loc, D) on this
        # model-rank.  Everything below is per-device local work.
        i0 = jax.lax.axis_index("data") * n_loc
        j0 = jax.lax.axis_index("model") * m_loc
        s = _local_block_scores(a_loc, b_loc, threshold, interpret)
        mask = s >= threshold
        rows, cols, scores, n_cand = _compact_by_rows(
            s, mask, capacity, chunk_rows, i0, j0)
        return (rows[None, None], cols[None, None], scores[None, None],
                jnp.maximum(n_cand - capacity, 0)[None, None])

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P("model", None)),
        out_specs=(P("data", "model", None), P("data", "model", None),
                   P("data", "model", None), P("data", "model")),
        check_vma=False,
    )
    # leading (1, 1) block axes inside the body become the global (dd, dm)
    # device grid outside — candidate buffers only, never the dense matrix
    return fn(a, b)


def sharded_candidates(
    a: jax.Array,
    b: jax.Array,
    threshold: float,
    mesh: Mesh,
    capacity: Optional[int] = None,
    normalize: bool = True,
    impl: str = "auto",
) -> ShardedCandidates:
    """Mesh-parallel machine phase: embeddings -> thresholded candidate pairs.

    a: (N, D), b: (M, D); rows of ``a`` shard over the ``data`` axis, rows of
    ``b`` over ``model``.  ``capacity`` bounds per-device candidates (default:
    the whole local block, i.e. lossless).  Requires ``threshold > 0`` so
    zero-padded rows can never alias a real candidate.
    """
    if threshold <= 0.0:
        raise ValueError("sharded_candidates requires threshold > 0 "
                         "(padding rows score exactly 0)")
    dd, dm = _mesh_extents(mesh)
    N, M = a.shape[0], b.shape[0]
    if normalize:
        a = l2_normalize(a)
        b = l2_normalize(b)
    a = _pad_rows(a, dd)
    b = _pad_rows(b, dm)
    n_loc = a.shape[0] // dd
    m_loc = b.shape[0] // dm
    cap = int(capacity) if capacity is not None else n_loc * m_loc
    cap = min(cap, n_loc * m_loc)
    interpret = (impl == "interpret") or (
        impl == "auto" and jax.default_backend() != "tpu")
    with obs.span("join.machine.score"):
        rows, cols, scores, dropped = _sharded_candidates_jit(
            a, b, threshold=threshold, capacity=cap, mesh=mesh,
            interpret=interpret)
        with obs.span("join.machine.readback"):
            rows = obs.to_host(rows).reshape(-1)
            cols = obs.to_host(cols).reshape(-1)
            scores = obs.to_host(scores).reshape(-1)
            dropped = obs.to_host(dropped)
    keep = rows >= 0
    # padded rows/cols score 0 < threshold, so they can't appear as candidates
    return ShardedCandidates(
        rows=rows[keep].astype(np.int32),
        cols=cols[keep].astype(np.int32),
        scores=scores[keep].astype(np.float32),
        n_dropped=int(dropped.sum()),
        capacity=cap,
    )


def sharded_pair_scores(
    a: jax.Array,
    b: jax.Array,
    threshold: float,
    mesh: Mesh,
    normalize: bool = True,
    impl: str = "auto",
):
    """Dense sharded variant for parity testing and small grids: the (N, M)
    score matrix stays device-sharded (NamedSharding over (data, model));
    per-row counts shard over ``data``.  Semantics match
    ``ops.pair_scores`` exactly."""
    dd, dm = _mesh_extents(mesh)
    N, M = a.shape[0], b.shape[0]
    if normalize:
        a = l2_normalize(a)
        b = l2_normalize(b)
    a = _pad_rows(a, dd)
    b = _pad_rows(b, dm)
    interpret = (impl == "interpret") or (
        impl == "auto" and jax.default_backend() != "tpu")

    def body(a_loc, b_loc):
        s = _local_block_scores(a_loc, b_loc, threshold, interpret)
        cnt = (s >= threshold).sum(axis=1, keepdims=True).astype(jnp.int32)
        cnt = jax.lax.psum(cnt, "model")
        return s, cnt

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P("model", None)),
        out_specs=(P("data", "model"), P("data", None)),
        check_vma=False,
    )
    s, cnt = jax.jit(fn)(a, b)
    return s[:N, :M], cnt[:N]


# ---------------------------------------------------------------------------
# Streaming ingest: incremental candidate generation (DESIGN.md §11)
# ---------------------------------------------------------------------------
class StreamingCandidateIndex:
    """Incremental machine phase for streaming arrivals (DESIGN.md §11).

    The one-shot :func:`sharded_candidates` scores the full N x M cross
    product; under streaming ingest that cost is paid again on every
    arrival.  This index caches the (normalized) corpus embeddings and, per
    :meth:`append` of new ``a`` and/or ``b`` rows, scores only the blocks a
    full re-run would add — ``new_a x (b_old + b_new)`` and
    ``a_old x new_b`` — so the work per epoch is O(dN*M + N*dM) instead of
    O(N*M).  Appended rows keep global indices (offset past the cached
    corpus), so the union of every epoch's candidates equals one batch
    ``sharded_candidates`` call over the final corpora, set-for-set.

    ``pairs_scored`` counts grid cells actually scored; the bench compares
    it against ``full_rescore_pairs`` (what resubmitting from scratch every
    epoch would have scored) to show the incremental driver doing strictly
    less pair-score work.

    With a ``blocking`` config (DESIGN.md §12) the index additionally rides
    the LSH bucket structure: arrivals hash into the *existing* buckets
    (signatures are deterministic in the seed, so an arrival's codes match
    the codes the corpus was bucketed with), and only tiles from buckets
    the arrival touched reach the fused tile kernel — the per-epoch
    work drops from the dense dN x M block to the colliding cells.
    """

    def __init__(self, threshold: float, mesh: Mesh,
                 capacity: Optional[int] = None, normalize: bool = True,
                 impl: str = "auto", blocking=None):
        if threshold <= 0.0:
            raise ValueError("StreamingCandidateIndex requires threshold > 0 "
                             "(padding rows score exactly 0)")
        self.threshold = float(threshold)
        self.mesh = mesh
        self.capacity = capacity
        self.normalize = normalize
        self.impl = impl
        self.blocking = blocking
        self._a = np.zeros((0, 0), np.float32)  # cached normalized corpus
        self._b = np.zeros((0, 0), np.float32)
        # cached (n_tables, N) signature codes of the corpus (blocking only)
        n_tables = blocking.n_tables if blocking is not None else 0
        self._codes_a = np.zeros((n_tables, 0), np.int64)
        self._codes_b = np.zeros((n_tables, 0), np.int64)
        self.pairs_scored = 0        # grid cells the incremental path scored
        self.full_rescore_pairs = 0  # cells full per-epoch re-runs would score
        self._undo = None            # pre-append snapshot (rollback_append)

    @property
    def n_a(self) -> int:
        return self._a.shape[0]

    @property
    def n_b(self) -> int:
        return self._b.shape[0]

    def _norm(self, x: jax.Array) -> np.ndarray:
        x = jnp.asarray(x, jnp.float32)
        if self.normalize:
            x = l2_normalize(x)
        return obs.to_host(x)

    def _block(self, a: np.ndarray, b: np.ndarray, row0: int, col0: int):
        """Score one (already-normalized) block; offset indices to global."""
        self.pairs_scored += a.shape[0] * b.shape[0]
        cand = sharded_candidates(
            jnp.asarray(a), jnp.asarray(b), self.threshold, self.mesh,
            capacity=self.capacity, normalize=False, impl=self.impl)
        return ShardedCandidates(
            rows=cand.rows + np.int32(row0), cols=cand.cols + np.int32(col0),
            scores=cand.scores, n_dropped=cand.n_dropped,
            capacity=cand.capacity)

    def rollback_append(self) -> None:
        """Undo the most recent :meth:`append` — the corpus caches and work
        counters revert to their pre-append values.  For callers that
        reject an epoch after scoring it (e.g. on capacity overflow): the
        index must not remember rows whose candidates were never ingested,
        or every later epoch would score against (and skip) them."""
        if self._undo is None:
            raise RuntimeError("no append to roll back")
        (self._a, self._b, self._codes_a, self._codes_b,
         self.pairs_scored, self.full_rescore_pairs) = self._undo
        self._undo = None

    def _append_blocked(self, na: Optional[np.ndarray],
                        nb: Optional[np.ndarray]):
        """Blocked epoch: hash arrivals into the existing buckets and score
        only the colliding tiles.  Same cell coverage as the dense path —
        ``new_a x b_full`` then ``a_old x new_b`` — restricted per group to
        bucket collisions, so the union over epochs equals one batch
        :func:`blocking.blocked_candidates` call over the final corpora."""
        from .blocking import (BlockedCandidates, block_pairs,
                               score_block_pairs, signatures)

        cfg = self.blocking
        n0, m0 = self.n_a, self.n_b
        dn = len(na) if na is not None else 0
        dm = len(nb) if nb is not None else 0
        ca_new = (signatures(na, cfg) if dn
                  else np.zeros((cfg.n_tables, 0), np.int64))
        cb_new = (signatures(nb, cfg) if dm
                  else np.zeros((cfg.n_tables, 0), np.int64))
        a_full = (self._a if not dn
                  else (na if n0 == 0 else np.concatenate([self._a, na])))
        b_full = (self._b if not dm
                  else (nb if m0 == 0 else np.concatenate([self._b, nb])))
        codes_a = np.concatenate([self._codes_a, ca_new], axis=1)
        codes_b = np.concatenate([self._codes_b, cb_new], axis=1)
        parts = []
        if dn and (m0 + dm):
            ta, tb = block_pairs(codes_a, np.arange(n0, n0 + dn),
                                 codes_b, np.arange(m0 + dm),
                                 cfg.bn, cfg.bm)
            parts.append(score_block_pairs(
                a_full, b_full, ta, tb, self.threshold, cfg,
                capacity=self.capacity, impl=self.impl))
        if dm and n0:
            ta, tb = block_pairs(codes_a, np.arange(n0),
                                 codes_b, np.arange(m0, m0 + dm),
                                 cfg.bn, cfg.bm)
            parts.append(score_block_pairs(
                a_full, b_full, ta, tb, self.threshold, cfg,
                capacity=self.capacity, impl=self.impl))
        self._a, self._b = a_full, b_full
        self._codes_a, self._codes_b = codes_a, codes_b
        self.pairs_scored += sum(p.cells_scored for p in parts)
        self.full_rescore_pairs += self.n_a * self.n_b
        # the two groups are row-disjoint (group 1 rows >= n0, group 2
        # rows < n0) and each call dedups cross-table re-finds, so a plain
        # concat is already duplicate-free
        return BlockedCandidates(
            rows=np.concatenate([p.rows for p in parts])
            if parts else np.zeros(0, np.int32),
            cols=np.concatenate([p.cols for p in parts])
            if parts else np.zeros(0, np.int32),
            scores=np.concatenate([p.scores for p in parts])
            if parts else np.zeros(0, np.float32),
            n_dropped=sum(p.n_dropped for p in parts),
            capacity=(max(p.capacity for p in parts) if parts
                      else (self.capacity or 0)),
            cells_scored=sum(p.cells_scored for p in parts),
            padded_cells=sum(p.padded_cells for p in parts),
            dense_cells=dn * (m0 + dm) + n0 * dm,
            n_tiles=sum(p.n_tiles for p in parts),
            n_duplicates=sum(p.n_duplicates for p in parts),
        )

    def append(self, new_a: Optional[jax.Array] = None,
               new_b: Optional[jax.Array] = None) -> ShardedCandidates:
        """Ingest new rows and return ONLY the new candidate pairs — every
        (row, col) with at least one appended endpoint that scores at or
        above the threshold, with global indices into the grown corpora."""
        self._undo = (self._a, self._b, self._codes_a, self._codes_b,
                      self.pairs_scored, self.full_rescore_pairs)
        na = self._norm(new_a) if new_a is not None else None
        nb = self._norm(new_b) if new_b is not None else None
        if self.blocking is not None:
            return self._append_blocked(na, nb)
        n0, m0 = self.n_a, self.n_b
        blocks = []
        # new_a against the full post-append b corpus (old + new cols), then
        # the old a corpus against new_b: covers each new cell exactly once
        b_full = self._b if nb is None else (
            nb if m0 == 0 else np.concatenate([self._b, nb]))
        if na is not None and len(na) and len(b_full):
            blocks.append(self._block(na, b_full, n0, 0))
        if nb is not None and len(nb) and n0:
            blocks.append(self._block(self._a, nb, 0, m0))
        if na is not None and len(na):
            self._a = na if n0 == 0 else np.concatenate([self._a, na])
        if nb is not None and len(nb):
            self._b = b_full
        self.full_rescore_pairs += self.n_a * self.n_b
        if not blocks:
            return ShardedCandidates(
                rows=np.zeros(0, np.int32), cols=np.zeros(0, np.int32),
                scores=np.zeros(0, np.float32), n_dropped=0,
                capacity=self.capacity or 0)
        return ShardedCandidates(
            rows=np.concatenate([c.rows for c in blocks]),
            cols=np.concatenate([c.cols for c in blocks]),
            scores=np.concatenate([c.scores for c in blocks]),
            n_dropped=sum(c.n_dropped for c in blocks),
            capacity=max(c.capacity for c in blocks),
        )
