"""LSH blocking + fused candidate generation (DESIGN.md §12).

The dense machine phase scores every cell of the N x M similarity grid —
O(N*M) work that caps corpus size at what one sweep of the mesh affords.
This module puts a *blocking* stage in front of the scorer, in the spirit
of CrowdER's similarity-based candidate pruning: sign-random-projection
LSH hashes every row into ``n_bits``-bit bucket codes across ``n_tables``
independent tables, and only (a-row, b-row) pairs that collide in at least
one table's bucket ever reach the kernel.  Colliding buckets are chunked
into (bn x bm) tiles and streamed through ``pair_scores_compact``, which
fuses similarity and threshold in the kernel and compacts the candidates in
the same jit — the dense score matrix exists one chunk of tiles at a time.

Recall is a tunable contract, not luck: for unit vectors with cosine
similarity ``s``, one hyperplane splits the pair with probability
``acos(s) / pi``, so a pair survives one table with ``p(s)^n_bits`` and is
captured overall with ``1 - (1 - p(s)^n_bits)^n_tables``
(:func:`expected_recall`).  Capture probability rises with similarity, so
the threshold boundary is the worst case — :meth:`BlockingConfig.for_recall`
sizes the table count from the floor you need at ``s = threshold``.  More
tables buy recall linearly in scoring work; fewer bits coarsen buckets
(higher recall, more cells scored).  The knobs trade machine cells for
crowd-visible misses, which is exactly where the paper's machine/crowd
cost ratio lives.

Candidates keep the :class:`ShardedCandidates` contract (capacity is hard,
overflow is counted and reported with a ``suggested_capacity`` that
provably fits), extended with the blocking accounting the benchmarks and
CI smoke assert on (cells scored vs dense cells, tiles, duplicates).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .kernel import pair_scores_compact
from .ops import l2_normalize
from .ref import similarity
from .sharded import ShardedCandidates


@dataclasses.dataclass(frozen=True)
class BlockingConfig:
    """Blocking-stage knobs: LSH shape, kernel tiling, and bookkeeping.

    ``n_bits`` hyperplanes per table (finer buckets = fewer cells scored,
    lower per-table recall); ``n_tables`` independent tables (each adds a
    capture chance); ``seed`` fixes the hyperplanes so streaming arrivals
    hash into the same buckets as the corpus they join.  ``bn``/``bm`` are
    the kernel tile shape; ``tiles_per_call`` bounds device buffers by
    splitting long tile lists into fixed-shape kernel launches.
    ``recall_floor`` records what :meth:`for_recall` was asked for — the
    parity tests assert measured recall against it."""

    n_bits: int = 8
    n_tables: int = 8
    seed: int = 0
    bn: int = 128
    bm: int = 128
    tiles_per_call: int = 256
    recall_floor: Optional[float] = None

    def __post_init__(self):
        if not 1 <= self.n_bits <= 30:
            raise ValueError(
                f"n_bits must be in [1, 30] (codes pack into int32 and "
                f"2**30 buckets is already past any useful grain), got "
                f"{self.n_bits}")
        if self.n_tables < 1:
            raise ValueError(f"n_tables must be >= 1, got {self.n_tables}")
        if self.bn < 1 or self.bm < 1 or self.tiles_per_call < 1:
            raise ValueError(
                f"tile shape and tiles_per_call must be positive, got "
                f"bn={self.bn} bm={self.bm} "
                f"tiles_per_call={self.tiles_per_call}")

    @classmethod
    def for_recall(cls, floor: float, threshold: float, n_bits: int = 8,
                   max_tables: int = 256, **kwargs) -> "BlockingConfig":
        """Smallest table count whose *analytic* capture probability at the
        threshold boundary clears ``floor`` with headroom (the analytic
        number is an expectation; the headroom keeps measured recall above
        the floor rather than oscillating around it).  Raises when the
        floor is unreachable within ``max_tables`` — lower ``n_bits``."""
        if not 0.0 < floor < 1.0:
            raise ValueError(f"recall floor must be in (0, 1), got {floor}")
        p = _collision_prob(threshold) ** n_bits
        if p <= 0.0:
            raise ValueError(
                f"threshold {threshold} gives zero per-table collision "
                "probability — no table count can reach the floor")
        target = 1.0 - (1.0 - floor) / 20.0
        n_tables = max(1, math.ceil(math.log(1.0 - target)
                                    / math.log(1.0 - p)))
        if n_tables > max_tables:
            raise ValueError(
                f"recall floor {floor} at threshold {threshold} needs "
                f"{n_tables} tables (> max_tables={max_tables}) with "
                f"n_bits={n_bits} — use fewer bits per table")
        return cls(n_bits=n_bits, n_tables=n_tables, recall_floor=floor,
                   **kwargs)


def _collision_prob(s: float) -> float:
    """P[one random hyperplane keeps a pair with cosine similarity s]."""
    return 1.0 - math.acos(min(max(s, -1.0), 1.0)) / math.pi


def expected_recall(config: BlockingConfig, similarity: float) -> float:
    """Analytic capture probability of a pair at the given similarity —
    the blocker's expected recall at the threshold boundary (its worst
    case over the candidate set)."""
    p = _collision_prob(similarity) ** config.n_bits
    return 1.0 - (1.0 - p) ** config.n_tables


# Rows per call of the signature program.  One fixed shape gives a row the
# same compiled projection whichever call or slice it arrives in, so
# streaming arrivals hash into the corpus's buckets bit for bit.
_SIGNATURE_ROWS = 4096


@functools.lru_cache(maxsize=64)
def _planes(seed: int, dim: int, n_tables: int, n_bits: int) -> jax.Array:
    """The seeded hyperplanes on the device as one (D, n_tables * n_bits)
    matrix: column ``l * n_bits + j`` is table l's j-th plane."""
    rng = np.random.default_rng(seed)
    planes = rng.normal(size=(n_tables, dim, n_bits)).astype(np.float32)
    return jnp.asarray(planes.transpose(1, 0, 2).reshape(dim, -1))


@functools.partial(jax.jit, static_argnames=("n_bits",))
def lsh_signatures(x: jax.Array, planes: jax.Array, n_bits: int) -> jax.Array:
    """(n_tables, R) int32 codes of R rows: the sign bits of each table's
    ``n_bits`` projections, bit j from plane j.  HIGHEST keeps the float32
    projection, so a sign flips only within rounding of zero."""
    proj = jnp.dot(x, planes, precision=jax.lax.Precision.HIGHEST)
    bits = (proj >= 0.0).astype(jnp.int32).reshape(x.shape[0], -1, n_bits)
    return jnp.sum(bits << jnp.arange(n_bits, dtype=jnp.int32), axis=-1).T


@functools.partial(jax.jit, static_argnames=("rows",))
def _row_chunks(x: jax.Array, rows: int) -> Tuple[jax.Array, ...]:
    """``x`` zero-padded to whole chunks of ``rows`` rows, cut into them."""
    k = max(1, -(-x.shape[0] // rows))
    x = jnp.pad(x, ((0, k * rows - x.shape[0]), (0, 0)))
    return tuple(x[i * rows:(i + 1) * rows] for i in range(k))


def signatures(x, config: BlockingConfig) -> np.ndarray:
    """(n_tables, N) int64 bucket codes: sign bits of ``n_bits`` seeded
    random hyperplane projections, packed per table.  Deterministic in
    (seed, D, n_bits, n_tables) alone, so rows hashed in different calls
    (streaming arrivals vs the original corpus) land in the same buckets.
    Feed the *normalized* embeddings, a numpy array or a ``jax.Array``:
    the codes are computed on the device in fixed row chunks
    (:func:`lsh_signatures`) and read back once."""
    with obs.span("join.machine.signatures"):
        x = jnp.asarray(x, jnp.float32)
        planes = _planes(config.seed, x.shape[1], config.n_tables,
                         config.n_bits)
        codes = [lsh_signatures(c, planes, config.n_bits)
                 for c in _row_chunks(x, _SIGNATURE_ROWS)]
        codes = np.concatenate(obs.to_host(codes), axis=1)
        return codes[:, :x.shape[0]].astype(np.int64)


def _pad_chunks(rows: np.ndarray, tile: int) -> np.ndarray:
    """Chunk a bucket's member rows into (t, tile) with -1 padding."""
    n = len(rows)
    t = -(-n // tile)
    out = np.full((t, tile), -1, np.int64)
    out.reshape(-1)[:n] = rows
    return out


def block_pairs(codes_a: np.ndarray, idx_a: np.ndarray,
                codes_b: np.ndarray, idx_b: np.ndarray,
                bn: int, bm: int) -> Tuple[np.ndarray, np.ndarray]:
    """Tile pairs for every bucket collision between the given row subsets.

    ``codes_a``/``codes_b`` are full-corpus signature tables (n_tables, N)
    / (n_tables, M); ``idx_a``/``idx_b`` select which global rows
    participate on each side (the streaming index passes new-rows-only
    subsets so only touched buckets rescore).  Returns
    (tiles_a (T, bn), tiles_b (T, bm)) int64 global row indices, -1 padded
    — tile pair t means "score every (row of tiles_a[t]) x (row of
    tiles_b[t]) cell"."""
    with obs.span("join.machine.block"):
        idx_a = np.asarray(idx_a, np.int64)
        idx_b = np.asarray(idx_b, np.int64)
        tiles_a: List[np.ndarray] = []
        tiles_b: List[np.ndarray] = []
        if len(idx_a) == 0 or len(idx_b) == 0:
            return (np.zeros((0, bn), np.int64), np.zeros((0, bm), np.int64))
        for table in range(codes_a.shape[0]):
            ca = codes_a[table, idx_a]
            cb = codes_b[table, idx_b]
            oa = np.argsort(ca, kind="stable")
            ob = np.argsort(cb, kind="stable")
            ua, sa, na = np.unique(ca[oa], return_index=True,
                                   return_counts=True)
            ub, sb, nb = np.unique(cb[ob], return_index=True,
                                   return_counts=True)
            shared, ia, ib = np.intersect1d(ua, ub, assume_unique=True,
                                            return_indices=True)
            for k in range(len(shared)):
                rows = idx_a[oa[sa[ia[k]]:sa[ia[k]] + na[ia[k]]]]
                cols = idx_b[ob[sb[ib[k]]:sb[ib[k]] + nb[ib[k]]]]
                ra = _pad_chunks(rows, bn)
                rb = _pad_chunks(cols, bm)
                tiles_a.append(ra[np.repeat(np.arange(len(ra)), len(rb))])
                tiles_b.append(rb[np.tile(np.arange(len(rb)), len(ra))])
        if not tiles_a:
            return (np.zeros((0, bn), np.int64), np.zeros((0, bm), np.int64))
        return np.concatenate(tiles_a), np.concatenate(tiles_b)


@dataclasses.dataclass
class BlockedCandidates(ShardedCandidates):
    """ShardedCandidates plus the blocking accounting CI asserts on."""

    cells_scored: int = 0    # genuine (row, col) cells the tiles covered
    padded_cells: int = 0    # kernel work actually issued (incl. padding)
    dense_cells: int = 0     # what the dense path would have scored
    n_tiles: int = 0
    n_duplicates: int = 0    # cross-table re-finds removed by dedup

    @property
    def cells_saved_frac(self) -> float:
        if self.dense_cells == 0:
            return 0.0
        return 1.0 - self.cells_scored / self.dense_cells


def _resolve_interpret(impl: str) -> bool:
    if impl not in ("auto", "pallas", "interpret"):
        raise ValueError(
            f"impl must be 'auto', 'pallas', or 'interpret', got {impl!r}")
    return (impl == "interpret") or (
        impl == "auto" and jax.default_backend() != "tpu")


def score_block_pairs(a, b, tiles_a: np.ndarray, tiles_b: np.ndarray,
                      threshold: float, config: BlockingConfig,
                      capacity: Optional[int] = None,
                      impl: str = "auto") -> BlockedCandidates:
    """Stream the tile list through the fused kernel and gather the
    compacted candidates.  ``a``/``b`` must already be L2-normalized; the
    caller owns bucket construction (:func:`block_pairs`) and dedup.

    ``capacity`` bounds *total* kept candidates across the whole tile list
    (default: lossless).  Tile lists longer than ``config.tiles_per_call``
    are split into fixed-shape kernel launches (one jit entry), each
    keeping at most ``min(capacity, chunk_cells)`` candidates — the
    suggested-capacity arithmetic accounts for both limits."""
    if threshold <= 0.0:
        raise ValueError("score_block_pairs requires threshold > 0 "
                         "(padding rows score exactly 0)")
    bn, bm = config.bn, config.bm
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    N, D = a.shape
    M = b.shape[0]
    T = tiles_a.shape[0]
    interpret = _resolve_interpret(impl)
    cells_scored = int(((tiles_a >= 0).sum(axis=1)
                        * (tiles_b >= 0).sum(axis=1)).sum()) if T else 0
    if capacity is None:
        cap = T * bn * bm
    else:
        cap = int(capacity)
    if T == 0 or cap <= 0:
        return BlockedCandidates(
            rows=np.zeros(0, np.int32), cols=np.zeros(0, np.int32),
            scores=np.zeros(0, np.float32), n_dropped=0, capacity=cap,
            cells_scored=cells_scored, padded_cells=0,
            dense_cells=N * M, n_tiles=T)
    # fixed-shape chunks: pad the tile list with all-padding tiles so every
    # kernel launch shares one (T_chunk, capacity) jit entry
    from repro.core.jax_graph import next_pow2

    chunk = min(config.tiles_per_call, next_pow2(T, floor=1))
    t_pad = (-T) % chunk
    if t_pad:
        tiles_a = np.concatenate(
            [tiles_a, np.full((t_pad, bn), -1, np.int64)])
        tiles_b = np.concatenate(
            [tiles_b, np.full((t_pad, bm), -1, np.int64)])
    c_call = min(cap, chunk * bn * bm)
    # padding rows gather the appended zero vector (index N / M)
    with obs.span("join.machine.score"):
        a_ext = jnp.concatenate([a, jnp.zeros((1, D), a.dtype)])
        b_ext = jnp.concatenate([b, jnp.zeros((1, D), b.dtype)])
        rows_acc: List[np.ndarray] = []
        cols_acc: List[np.ndarray] = []
        scores_acc: List[np.ndarray] = []
        kept_total = 0
        found_total = 0
        for t0 in range(0, tiles_a.shape[0], chunk):
            ta = tiles_a[t0:t0 + chunk]
            tb = tiles_b[t0:t0 + chunk]
            ga = np.where(ta < 0, N, ta).reshape(-1)
            gb = np.where(tb < 0, M, tb).reshape(-1)
            a_g = a_ext[jnp.asarray(ga)]
            b_g = b_ext[jnp.asarray(gb)]
            ida = jnp.asarray(ta.reshape(-1, 1).astype(np.int32))
            idb = jnp.asarray(tb.reshape(-1, 1).astype(np.int32))
            rows, cols, scores, n_tot = obs.to_host(pair_scores_compact(
                a_g, b_g, ida, idb, float(threshold), c_call, bn, bm,
                interpret=interpret))
            n_found = int(n_tot)
            found_total += n_found
            keep = min(n_found, c_call, cap - kept_total)
            if keep > 0:
                rows_acc.append(rows[:keep])
                cols_acc.append(cols[:keep])
                scores_acc.append(scores[:keep])
                kept_total += keep
    n_dropped = found_total - kept_total
    rows = (np.concatenate(rows_acc) if rows_acc
            else np.zeros(0, np.int64)).astype(np.int64)
    cols = (np.concatenate(cols_acc) if cols_acc
            else np.zeros(0, np.int64)).astype(np.int64)
    scores = (np.concatenate(scores_acc) if scores_acc
              else np.zeros(0, np.float32))
    # cross-table dedup: a pair colliding in several tables is scored in
    # each (same gathered rows -> bitwise-identical score), kept once
    keys = rows * np.int64(M) + cols
    _, first = np.unique(keys, return_index=True)
    n_dup = len(rows) - len(first)
    return BlockedCandidates(
        rows=rows[first].astype(np.int32),
        cols=cols[first].astype(np.int32),
        scores=scores[first].astype(np.float32),
        n_dropped=n_dropped,
        capacity=cap,
        cells_scored=cells_scored,
        padded_cells=int(tiles_a.shape[0]) * bn * bm,
        dense_cells=N * M,
        n_tiles=T,
        n_duplicates=n_dup,
    )


def blocked_candidates(a, b, threshold: float,
                       config: Optional[BlockingConfig] = None,
                       capacity: Optional[int] = None,
                       normalize: bool = True,
                       impl: str = "auto") -> BlockedCandidates:
    """Blocked machine phase: embeddings -> thresholded candidate pairs
    without ever scoring the dense N x M grid.

    Hash both sides into LSH buckets, tile every bucket collision, and
    stream the tiles through the fused similarity/threshold kernel and its
    candidate compaction.  Pairs the blocker never buckets together are
    the recall cost — size ``config`` with
    :meth:`BlockingConfig.for_recall` for a floor
    at the threshold boundary, and measure with :func:`blocker_recall`."""
    config = config or BlockingConfig()
    if normalize:
        a = l2_normalize(jnp.asarray(a, jnp.float32))
        b = l2_normalize(jnp.asarray(b, jnp.float32))
    codes_a = signatures(a, config)
    codes_b = signatures(b, config)
    tiles_a, tiles_b = block_pairs(codes_a, np.arange(a.shape[0]),
                                   codes_b, np.arange(b.shape[0]),
                                   config.bn, config.bm)
    return score_block_pairs(a, b, tiles_a, tiles_b, threshold, config,
                             capacity=capacity, impl=impl)


def blocker_recall(cand, a, b, threshold: float,
                   row_sample: Optional[np.ndarray] = None,
                   col_chunk: int = 8192) -> Tuple[float, int]:
    """Measured recall of a candidate set against the dense oracle,
    restricted to a densely-checkable a-row subsample (the full dense grid
    is exactly what the blocked path exists to avoid).  Scores the sampled
    rows in column chunks with plain jnp (never more than
    ``len(row_sample) * col_chunk`` cells live).  Returns
    (recall, n_dense_candidates_in_sample); an empty dense set counts as
    recall 1.0."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M = b.shape[0]
    rows = (np.arange(a.shape[0]) if row_sample is None
            else np.asarray(row_sample, np.int64))
    cand_keys = np.sort(np.asarray(cand.rows, np.int64) * np.int64(M)
                        + np.asarray(cand.cols, np.int64))
    a_s = a[jnp.asarray(rows)]
    n_dense = 0
    n_hit = 0
    for c0 in range(0, M, col_chunk):
        s = obs.to_host(similarity(a_s, b[c0:c0 + col_chunk]))
        ri, ci = np.nonzero(s >= threshold)
        keys = rows[ri] * np.int64(M) + (ci + c0)
        n_dense += len(keys)
        n_hit += int(np.isin(keys, cand_keys, assume_unique=False).sum())
    return (1.0 if n_dense == 0 else n_hit / n_dense), n_dense


def dense_block_pairs(n: int, m: int, bn: int, bm: int) -> Tuple[np.ndarray,
                                                                 np.ndarray]:
    """Tile pairs covering the full N x M grid — the degenerate blocking
    (everything in one bucket) the kernel-vs-oracle exactness tests use."""
    ra = _pad_chunks(np.arange(n, dtype=np.int64), bn)
    rb = _pad_chunks(np.arange(m, dtype=np.int64), bm)
    return (ra[np.repeat(np.arange(len(ra)), len(rb))],
            rb[np.tile(np.arange(len(rb)), len(ra))])
