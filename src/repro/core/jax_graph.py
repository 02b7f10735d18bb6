"""TPU-native transitive-relations engine (DESIGN.md §4, §7, §8).

Vectorized, ``jit``-able re-formulation of the paper's ClusterGraph machinery
so the deduction/selection inner loops run as dense array programs on an
accelerator mesh instead of pointer-chasing union-find on a host.

The engine is organized around a persistent, device-resident
:class:`SessionState` pytree (DESIGN.md §8): per-session
``(u, v, labels, published, roots, neg_keys, rounds, priority)``.  State is
updated **incrementally** as crowd answers land:

* new POS labels hook into the existing union-find forest via *bounded*
  pointer jumping from the current ``roots`` (``_union_impl`` starting from
  the live forest, not from ``arange(n)``);
* new NEG labels are keyed under the current roots and merged into the
  sorted ``neg_keys`` array with a ``searchsorted`` parallel merge instead
  of a full rebuild + sort; existing keys are re-canonicalized (decompose →
  remap through the new roots → re-sort) only when a union actually moved a
  root.

State transformations (all jitted, state-in/state-out):

* ``session_frontier``  — priority-Borůvka selection (parallel Algorithm 3)
  over the live forest; published (in-flight) pairs are assumed matching but
  excluded from the output (the §5.2 instant-decision contract).  Selection
  keys on the state's live ``priority`` field (DESIGN.md §10) — positional
  when fresh, refreshed between rounds by ``core/ordering.py``.
* ``session_apply_answers`` — fold crowd answers into labels/roots/neg_keys,
  **conflict-aware** (DESIGN.md §9): every incoming answer is screened
  against the live state; an answer contradicting the deduced label is
  rejected (the label stays UNKNOWN until deduction fills it, or until the
  serving layer requeries), counted in the per-pair ``conflicts`` field, and
  returned in a conflict mask — bit-identical to feeding the same stream
  through ``ClusterGraph.add_label`` one answer at a time.
* ``session_deduce``    — one deduction sweep (Algorithm 1 batched) over the
  maintained roots + neg-key index; published pairs are skipped (their
  answers are in flight).
* ``session_fold_answers`` — apply + deduce fused into one dispatch.
* ``session_seed_labels`` — warm-start fold of cached cross-query cluster
  verdicts (DESIGN.md §14): identical to ``session_fold_answers`` except the
  ``rounds`` counter does not advance — seeds are prior queries' capital,
  not a crowd round of this session.
* ``session_trust_graph`` — the requery ladder's endpoint: un-publish a set
  of exhausted pairs and let deduction label them from the graph.

Conflict screening is two-speed: an optimistic all-answers union is checked
for *self-keys* (a negative edge whose endpoints landed in one cluster —
the corruption signature).  A fold with no self-key provably has no
conflict under sequential semantics and takes the same fully-parallel path
as before; a fold with one falls back (``lax.cond``) to an exact
sequential replay that reproduces the oracle's answer-at-a-time semantics
in pair-index order.

``*_batch`` variants are ``vmap``s over stacked states that advance B
independent join sessions per device dispatch (DESIGN.md §7).

Thin **from-scratch wrappers** keep the historical signatures for oracle
parity tests: ``boruvka_frontier{,_batch}`` and ``deduce_sessions`` rebuild a
state from plain label arrays (connected components from ``arange(n)``, full
neg-key sort) and then run the same state transformations — the incremental
path is property-tested bit-identical against them.

The priority-Borůvka selection itself is unchanged math (DESIGN.md §4): with
every unlabeled pair optimistically assumed matching, the sequential scan
selects exactly the priority-Kruskal forest of the candidate graph; by the
MSF cut property each component's minimum-priority incident valid edge
belongs to that forest, so Borůvka rounds reproduce it in O(log n)
data-parallel steps.  Negative-edge exclusion is evaluated against *current*
components, which can only shrink a round's frontier relative to the
sequential scan — it never publishes a pair the oracle wouldn't.

All functions take fixed-shape arrays + validity masks so they stay jittable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# label encoding for the array engine (canonical home: cluster_graph.py,
# which stays importable without jax)
from .cluster_graph import NEG, POS, UNKNOWN
from ..obs import engine_dispatches


# ---------------------------------------------------------------------------
# Program names in the device trace (DESIGN.md §8)
# ---------------------------------------------------------------------------
def engine_jit(step: str, fn=None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the program name
    ``engine_<step>``, so the device trace shows it as
    ``jit_engine_<step>`` whatever wraps ``fn``.  Without ``fn``, a
    decorator."""
    if fn is None:
        return functools.partial(engine_jit, step, **jit_kwargs)

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = f"engine_{step}"
    return jax.jit(program, **jit_kwargs)


# ---------------------------------------------------------------------------
# Canonical pair keys + representable-range guard (shared helper)
# ---------------------------------------------------------------------------
def next_pow2(n: int, floor: int = 1) -> int:
    """Next power of two >= max(n, floor) — the one bucket-rounding policy
    shared by the serving layer's capacity buckets, the candidate buffers'
    suggested capacity, and the benchmarks (stable jit cache keys)."""
    b = floor
    while b < n:
        b *= 2
    return b


def pair_key_bits() -> int:
    """Usable bits of a one-word ``lo * n + hi`` pair key.

    Under the default jax config int64 silently narrows to int32, so only 31
    bits are available; with ``jax_enable_x64`` the full 63-bit positive
    range is usable.  Larger universes take two-word keys
    (``pair_keys_fit``)."""
    return 63 if jax.config.jax_enable_x64 else 31


def pair_keys_fit(n_objects: int) -> bool:
    """True iff an ``n_objects`` universe's pair keys fit one word of the
    key dtype (``lo * n + hi``; up to 46,340 objects in int32).  Otherwise
    the engine carries each key as two int32 words, a (2, P) array of lo
    roots over hi roots sorted lexicographically (DESIGN.md §8).  The
    choice is static, from a session's object capacity: shared by
    ``canonical_keys``, the fresh-state builders and the serving layer's
    capacity bucketing."""
    return n_objects * n_objects < 2 ** pair_key_bits()


def _key_dtype():
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _key_sentinel() -> int:
    """Max value of the key dtype — the padding sentinel for neg-key arrays
    (strictly above any real key thanks to the ``pair_keys_fit`` guard)."""
    return int(np.iinfo(np.dtype(_key_dtype().dtype)).max)


def _empty_keys(shape: Tuple[int, ...], n_objects: int) -> jax.Array:
    """An all-sentinel neg-key index of ``shape`` (leading batch axes, then
    the P slots) for an ``n_objects`` universe: one word per key where the
    keys fit, else two int32 words (a pad key has both words at the int32
    maximum, above every real key since object ids are int32)."""
    if pair_keys_fit(n_objects):
        return jnp.full(shape, _key_sentinel(), _key_dtype())
    return jnp.full(shape[:-1] + (2,) + shape[-1:],
                    np.iinfo(np.int32).max, jnp.int32)


def canonical_keys(roots_u: jax.Array, roots_v: jax.Array, n_objects: int) -> jax.Array:
    """Canonical cluster-pair keys of ``(roots_u, roots_v)``: one word
    ``lo * n + hi`` where ``pair_keys_fit(n_objects)``, else two int32
    words stacked on a new leading axis (``[lo, hi]``), which compare
    lexicographically as the one-word keys compare numerically."""
    if not pair_keys_fit(n_objects):
        return jnp.stack([jnp.minimum(roots_u, roots_v),
                          jnp.maximum(roots_u, roots_v)]).astype(jnp.int32)
    kdt = _key_dtype()
    lo = jnp.minimum(roots_u, roots_v).astype(kdt)
    hi = jnp.maximum(roots_u, roots_v).astype(kdt)
    return lo * jnp.asarray(n_objects, kdt) + hi


# Helpers over either key form.  A neg-key index is (P,) one-word keys or
# (2, P) two-word keys; under ``vmap`` each function sees one session, so
# ``ndim`` tells the forms apart.  The one-word branches are the
# expressions the engine has always used.
def _sentinel_of(keys: jax.Array) -> jax.Array:
    return jnp.asarray(jnp.iinfo(keys.dtype).max, keys.dtype)


def _per_key(mask: jax.Array, keys: jax.Array) -> jax.Array:
    """``mask`` over key slots, broadcastable against ``keys``."""
    return mask if keys.ndim == mask.ndim else mask[None]


def _sort_keys(keys: jax.Array) -> jax.Array:
    """Ascending sort; two-word keys sort lexicographically (lo, then hi)."""
    if keys.ndim == 1:
        return jnp.sort(keys)
    return jnp.stack(jax.lax.sort((keys[0], keys[1]), num_keys=2))


def _keys_equal(keys: jax.Array, other: jax.Array) -> jax.Array:
    """Slot-wise key equality (both words of a two-word key)."""
    if keys.ndim == 1:
        return keys == other
    return (keys[0] == other[0]) & (keys[1] == other[1])


def _key_starts(sorted_keys: jax.Array) -> jax.Array:
    """(P,) bool: True at each slot of a sorted index that holds a key
    other than the slot before it (slot 0 always)."""
    first = jnp.ones((1,), bool)
    if sorted_keys.ndim == 1:
        return jnp.concatenate([first, sorted_keys[1:] != sorted_keys[:-1]])
    return jnp.concatenate(
        [first, ~_keys_equal(sorted_keys[:, 1:], sorted_keys[:, :-1])])


def _has_keys(sorted_keys: jax.Array) -> jax.Array:
    """True iff a sorted index holds a real key (it would sit at slot 0)."""
    if sorted_keys.ndim == 1:
        return sorted_keys[0] != _sentinel_of(sorted_keys)
    return sorted_keys[0, 0] != _sentinel_of(sorted_keys)


def _search_wide(sorted_keys: jax.Array, queries: jax.Array,
                 right: bool) -> jax.Array:
    """``searchsorted`` over lexicographically sorted two-word keys: per
    query, the first slot whose key is >= it (> it with ``right``), by a
    fixed-length binary search over both words."""
    n = sorted_keys.shape[-1]
    s_lo, s_hi = sorted_keys[0], sorted_keys[1]
    q_lo, q_hi = queries[0], queries[1]

    def body(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        m = jnp.minimum(mid, n - 1)
        a, b = s_lo[m], s_hi[m]
        below = (a < q_lo) | ((a == q_lo) & ((b <= q_hi) if right
                                              else (b < q_hi)))
        open_ = lo < hi
        return (jnp.where(open_ & below, mid + 1, lo),
                jnp.where(open_ & ~below, mid, hi))

    lo = jnp.zeros(q_lo.shape, jnp.int32)
    hi = jnp.full(q_lo.shape, n, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, int(n).bit_length(), body, (lo, hi))
    return lo


# ---------------------------------------------------------------------------
# Union-find over matching edges: hook-and-compress pointer jumping.
# ``_union_impl`` starts from an arbitrary existing forest, which is what
# makes the incremental path bounded: merging k new edges into a compressed
# forest takes O(log k) rounds instead of O(log n) from scratch.
# ---------------------------------------------------------------------------
def _union_impl(parent0: jax.Array, u: jax.Array, v: jax.Array,
                mask: jax.Array, n_objects: int) -> jax.Array:
    big = jnp.int32(n_objects)  # sentinel larger than any id
    uu = jnp.where(mask, u, 0).astype(jnp.int32)
    vv = jnp.where(mask, v, 0).astype(jnp.int32)

    def body(state):
        parent, _ = state
        ru = parent[uu]
        rv = parent[vv]
        lo = jnp.minimum(ru, rv)
        # hook: parent[max(ru,rv)] <- min(ru,rv) (scatter-min, masked)
        hi = jnp.where(mask, jnp.maximum(ru, rv), big)
        tgt = jnp.where(mask, lo, big)
        parent = parent.at[hi.clip(0, n_objects - 1)].min(
            jnp.where(hi < big, tgt, big)
        )
        parent = jnp.minimum(parent, parent0)  # sentinel guard
        # compress: jump twice per round
        parent = parent[parent]
        parent = parent[parent]
        changed = jnp.any(parent[uu] != parent[vv])
        return parent, changed

    def cond(state):
        return state[1]

    parent, _ = jax.lax.while_loop(cond, body, (parent0, jnp.bool_(True)))
    # final full compression
    def comp_body(p):
        return p[p]
    def comp_cond(p):
        return jnp.any(p[p] != p)
    parent = jax.lax.while_loop(comp_cond, comp_body, parent)
    return parent


def _cc_impl(u, v, mask, n_objects: int) -> jax.Array:
    return _union_impl(jnp.arange(n_objects, dtype=jnp.int32), u, v, mask,
                       n_objects)


@engine_jit("connected_components", static_argnames=("n_objects",))
def _connected_components_jit(u, v, mask, n_objects):
    return _cc_impl(u, v, mask, n_objects)


def connected_components(u: jax.Array, v: jax.Array, mask: jax.Array,
                         n_objects: int) -> jax.Array:
    """Roots (min vertex id per component) over edges where ``mask`` is True."""
    engine_dispatches.add()
    return _connected_components_jit(u, v, mask, n_objects)


@engine_jit("connected_components_batch", static_argnames=("n_objects",))
def _connected_components_batch_jit(u, v, mask, n_objects):
    return jax.vmap(lambda uu, vv, mm: _cc_impl(uu, vv, mm, n_objects))(
        u, v, mask)


def connected_components_batch(u: jax.Array, v: jax.Array, mask: jax.Array,
                               n_objects: int) -> jax.Array:
    """(B, P) edge lists -> (B, n_objects) roots, one dispatch for B sessions."""
    engine_dispatches.add()
    return _connected_components_batch_jit(u, v, mask, n_objects)


# ---------------------------------------------------------------------------
# Sorted negative-key index: build, query, incremental maintenance
# ---------------------------------------------------------------------------
def _neg_keys_impl(roots, u, v, neg_mask, n_objects: int) -> jax.Array:
    keys = canonical_keys(roots[u], roots[v], n_objects)
    sentinel = _sentinel_of(keys)
    keys = jnp.where(_per_key(neg_mask, keys), keys, sentinel)
    return _sort_keys(keys)


@engine_jit("neg_keys", static_argnames=("n_objects",))
def _neg_keys_jit(roots, u, v, neg_mask, n_objects):
    return _neg_keys_impl(roots, u, v, neg_mask, n_objects)


def neg_keys(roots: jax.Array, u: jax.Array, v: jax.Array, neg_mask: jax.Array,
             n_objects: int) -> jax.Array:
    """Sorted canonical keys of cluster pairs joined by a labeled neg edge.
    Invalid slots are pushed to the end as max-sentinels."""
    engine_dispatches.add()
    return _neg_keys_jit(roots, u, v, neg_mask, n_objects)


def _in_sorted(sorted_keys: jax.Array, queries: jax.Array) -> jax.Array:
    if sorted_keys.ndim == 2:
        idx = _search_wide(sorted_keys, queries, right=False)
    else:
        idx = jnp.searchsorted(sorted_keys, queries)
    idx = idx.clip(0, sorted_keys.shape[-1] - 1)
    return _keys_equal(sorted_keys[..., idx], queries)


def _decompose_keys(keys: jax.Array, n_objects: int):
    """Split canonical keys back into endpoint ids: ``lo * n + hi`` by
    division, two-word keys by taking their words.  Returns (lo, hi,
    is_pad); pad slots decompose to (0, 0)."""
    sentinel = _sentinel_of(keys)
    if keys.ndim == 2:
        is_pad = keys[0] == sentinel
        lo = jnp.where(is_pad, 0, keys[0])
        hi = jnp.where(is_pad, 0, keys[1])
        return lo.clip(0, n_objects - 1), hi.clip(0, n_objects - 1), is_pad
    is_pad = keys == sentinel
    nn = jnp.asarray(n_objects, keys.dtype)
    lo = jnp.where(is_pad, 0, keys // nn).astype(jnp.int32)
    hi = jnp.where(is_pad, 0, keys % nn).astype(jnp.int32)
    return lo.clip(0, n_objects - 1), hi.clip(0, n_objects - 1), is_pad


def _rekey_impl(sorted_keys: jax.Array, roots: jax.Array,
                n_objects: int) -> jax.Array:
    """Re-canonicalize a sorted neg-key array after unions moved roots:
    decompose each key, remap both endpoints through the new forest, re-sort.
    A key whose endpoints were untouched maps to itself; sentinels stay
    sentinels.  The resulting multiset equals a from-scratch rebuild under the
    new roots (DESIGN.md §8 invariant)."""
    sentinel = _sentinel_of(sorted_keys)
    lo, hi, is_pad = _decompose_keys(sorted_keys, n_objects)
    new = canonical_keys(roots[lo], roots[hi], n_objects)
    new = jnp.where(_per_key(is_pad, new), sentinel, new)
    return _sort_keys(new)


def _merge_sorted_impl(a: jax.Array, b: jax.Array) -> jax.Array:
    """Parallel merge of two sentinel-padded sorted (P,) key arrays via
    ``searchsorted`` rank computation — the incremental alternative to a full
    rebuild + sort when new NEG keys arrive.  Returns the first P slots of
    the merged order, which hold every real key (each pair contributes at
    most one key, so real keys across both inputs never exceed P).  Two-word
    (2, P) keys merge the same way by the two-word search."""
    P = a.shape[-1]
    sentinel = _sentinel_of(a)
    if a.ndim == 2:
        ia = jnp.arange(P, dtype=jnp.int32) + _search_wide(b, a, right=False)
        ib = jnp.arange(P, dtype=jnp.int32) + _search_wide(a, b, right=True)
        out = jnp.full((2, 2 * P), sentinel, a.dtype)
        out = out.at[:, ia].set(a)
        out = out.at[:, ib].set(b)
        return out[:, :P]
    ia = jnp.arange(P, dtype=jnp.int32) + jnp.searchsorted(b, a, side="left")
    ib = jnp.arange(P, dtype=jnp.int32) + jnp.searchsorted(a, b, side="right")
    out = jnp.full((2 * P,), sentinel, a.dtype)
    out = out.at[ia].set(a)
    out = out.at[ib].set(b)
    return out[:P]


# ---------------------------------------------------------------------------
# Algorithm 1, batched: POS / NEG / UNKNOWN lookup against roots + neg index
# ---------------------------------------------------------------------------
def _deduce_lookup_impl(roots, sorted_neg, qu, qv, n_objects: int) -> jax.Array:
    ru, rv = roots[qu], roots[qv]
    same = ru == rv
    keys = canonical_keys(ru, rv, n_objects)
    neg = _in_sorted(sorted_neg, keys) & ~same
    return jnp.where(same, POS, jnp.where(neg, NEG, UNKNOWN)).astype(jnp.int32)


@engine_jit("deduce_lookup", static_argnames=("n_objects",))
def _deduce_batch_jit(roots, sorted_neg, qu, qv, n_objects):
    return _deduce_lookup_impl(roots, sorted_neg, qu, qv, n_objects)


def deduce_batch(roots: jax.Array, sorted_neg: jax.Array, qu: jax.Array,
                 qv: jax.Array, n_objects: int) -> jax.Array:
    """Algorithm 1 vectorized: per query pair returns POS / NEG / UNKNOWN."""
    engine_dispatches.add()
    return _deduce_batch_jit(roots, sorted_neg, qu, qv, n_objects)


# ---------------------------------------------------------------------------
# SessionState: persistent on-device join-session state (DESIGN.md §8)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("u", "v", "labels", "published", "roots", "neg_keys",
                 "rounds", "conflicts", "priority"),
    meta_fields=("n_objects",),
)
@dataclasses.dataclass
class SessionState:
    """One join session's engine state, resident on device across rounds.

    Invariants (DESIGN.md §8): ``roots`` are the canonical (min-vertex-id)
    connected components of the POS-labeled edges, and ``neg_keys`` is the
    sorted multiset of canonical root-pair keys of the NEG-labeled edges
    under those roots (sentinel-padded to P slots).  Both are therefore
    bit-identical to a from-scratch rebuild from ``labels`` at any point —
    which holds even under noisy answer streams, because contradictory
    answers are rejected at the fold (DESIGN.md §9) rather than folded in.
    ``published`` marks in-flight pairs (posted to the crowd, no answer yet);
    ``rounds`` counts answer folds; ``conflicts`` counts rejected answers
    per pair.  ``priority`` is the live labeling priority (DESIGN.md §10) —
    the frontier selects each cluster's minimum-**priority** incident edge;
    fresh states carry ``arange(P)``, which reproduces the historical
    position-is-priority order bit-for-bit, and ``core/ordering.py``
    refreshes it between rounds from the live posterior.  ``n_objects`` is
    static metadata so the state jits with stable cache keys.
    """

    u: jax.Array          # (P,) int32 pair endpoints, labeling order
    v: jax.Array          # (P,) int32
    labels: jax.Array     # (P,) int32 {UNKNOWN, NEG, POS}
    published: jax.Array  # (P,) bool — in-flight pairs
    roots: jax.Array      # (n_objects,) int32 union-find forest over POS edges
    neg_keys: jax.Array   # (P,) sorted canonical keys of NEG edges, or
    #                       (2, P) two-word keys where they do not fit one
    rounds: jax.Array     # () int32 answer-fold counter
    conflicts: jax.Array  # (P,) int32 rejected contradictory answers per pair
    priority: jax.Array   # (P,) f32 live labeling priority (lower = sooner)
    n_objects: int        # static


def make_session_state(u, v, n_objects: int, pair_capacity: int = 0,
                       object_capacity: int = 0) -> SessionState:
    """Fresh (all-UNKNOWN) session state, padded to the given capacities.

    Padded pair slots hold the inert pre-labeled POS self-loop (0, 0)
    (DESIGN.md §7); padded object ids are isolated singletons.  This is the
    once-per-lane pack the serving layer runs at lane open."""
    u = np.asarray(u, np.int32)
    v = np.asarray(v, np.int32)
    P = len(u)
    p_cap = max(pair_capacity, P)
    n_cap = max(object_capacity, int(n_objects))
    U = np.zeros(p_cap, np.int32)
    V = np.zeros(p_cap, np.int32)
    U[:P] = u
    V[:P] = v
    labels = np.full(p_cap, POS, np.int32)
    labels[:P] = UNKNOWN
    engine_dispatches.add()
    return SessionState(
        u=jnp.asarray(U),
        v=jnp.asarray(V),
        labels=jnp.asarray(labels),
        published=jnp.zeros(p_cap, bool),
        roots=jnp.arange(n_cap, dtype=jnp.int32),
        neg_keys=_empty_keys((p_cap,), n_cap),
        rounds=jnp.int32(0),
        conflicts=jnp.zeros(p_cap, jnp.int32),
        priority=jnp.arange(p_cap, dtype=jnp.float32),
        n_objects=n_cap,
    )


def make_session_state_batch(U, V, labels0, n_objects: int) -> SessionState:
    """Stacked fresh state over (B, P) packed sessions (``pack_sessions``)."""
    B, P = np.asarray(U).shape
    engine_dispatches.add()
    return SessionState(
        u=jnp.asarray(U, jnp.int32),
        v=jnp.asarray(V, jnp.int32),
        labels=jnp.asarray(labels0, jnp.int32),
        published=jnp.zeros((B, P), bool),
        roots=jnp.broadcast_to(jnp.arange(n_objects, dtype=jnp.int32),
                               (B, n_objects)),
        neg_keys=_empty_keys((B, P), int(n_objects)),
        rounds=jnp.zeros((B,), jnp.int32),
        conflicts=jnp.zeros((B, P), jnp.int32),
        priority=jnp.broadcast_to(jnp.arange(P, dtype=jnp.float32), (B, P)),
        n_objects=int(n_objects),
    )


def _state_from_labels_impl(u, v, labels, published, n_objects: int
                            ) -> SessionState:
    """From-scratch state build: CC from ``arange(n)`` + full neg-key sort.
    The reference the incremental path is tested bit-identical against."""
    u = u.astype(jnp.int32)
    v = v.astype(jnp.int32)
    labels = labels.astype(jnp.int32)
    roots = _cc_impl(u, v, labels == POS, n_objects)
    negk = _neg_keys_impl(roots, u, v, labels == NEG, n_objects)
    return SessionState(u=u, v=v, labels=labels, published=published,
                        roots=roots, neg_keys=negk, rounds=jnp.int32(0),
                        conflicts=jnp.zeros(u.shape, jnp.int32),
                        priority=jnp.arange(u.shape[0], dtype=jnp.float32),
                        n_objects=n_objects)


@engine_jit("from_labels", static_argnames=("n_objects",))
def _session_from_labels_jit(u, v, labels, published, n_objects):
    return _state_from_labels_impl(u, v, labels, published, n_objects)


def session_from_labels(u, v, labels, published, n_objects: int) -> SessionState:
    """Rebuild a :class:`SessionState` from plain label arrays (one dispatch).
    Used by the thin oracle-parity wrappers and for state audits."""
    engine_dispatches.add()
    return _session_from_labels_jit(jnp.asarray(u), jnp.asarray(v),
                                    jnp.asarray(labels), jnp.asarray(published),
                                    n_objects)


# ---------------------------------------------------------------------------
# Streaming growth (DESIGN.md §11): extend a live session's capacities and
# fold newly-arrived pairs into the padded tail, preserving every invariant
# ---------------------------------------------------------------------------
def _grow_impl(state: SessionState, pair_capacity: int, object_capacity: int
               ) -> SessionState:
    """Pad-preserving capacity extension.  Every live field keeps its prefix
    bit-for-bit; new pair slots take the inert pre-labeled POS self-loop
    (0, 0) exactly as ``make_session_state`` pads them, new object ids join
    as isolated singletons, and the sorted neg-key index is re-encoded under
    the enlarged object universe (``lo * n' + hi``, or two words once the
    keys no longer fit one).  The re-encoding is a strictly monotone map on
    real keys (keys compare as (lo, hi) tuples for any modulus > hi) and
    fixes the sentinel, so the array stays sorted with no merge pass."""
    P_old = state.u.shape[0]
    n_old = state.n_objects
    pad_p = pair_capacity - P_old
    lo, hi, is_pad = _decompose_keys(state.neg_keys, n_old)
    rekeyed = canonical_keys(lo, hi, object_capacity)
    sentinel = _sentinel_of(rekeyed)
    rekeyed = jnp.where(_per_key(is_pad, rekeyed), sentinel, rekeyed)
    negk = jnp.concatenate(
        [rekeyed, jnp.full(rekeyed.shape[:-1] + (pad_p,), sentinel,
                           rekeyed.dtype)], axis=-1)
    return SessionState(
        u=jnp.concatenate([state.u, jnp.zeros(pad_p, jnp.int32)]),
        v=jnp.concatenate([state.v, jnp.zeros(pad_p, jnp.int32)]),
        labels=jnp.concatenate(
            [state.labels, jnp.full(pad_p, POS, jnp.int32)]),
        published=jnp.concatenate(
            [state.published, jnp.zeros(pad_p, bool)]),
        roots=jnp.concatenate(
            [state.roots,
             jnp.arange(n_old, object_capacity, dtype=jnp.int32)]),
        neg_keys=negk,
        rounds=state.rounds,
        conflicts=jnp.concatenate(
            [state.conflicts, jnp.zeros(pad_p, jnp.int32)]),
        priority=jnp.concatenate(
            [state.priority,
             jnp.arange(P_old, pair_capacity, dtype=jnp.float32)]),
        n_objects=object_capacity,
    )


@engine_jit("grow",
            static_argnames=("pair_capacity", "object_capacity"))
def _session_grow_jit(state, pair_capacity, object_capacity):
    return _grow_impl(state, pair_capacity, object_capacity)


@engine_jit("grow_batch",
            static_argnames=("pair_capacity", "object_capacity"))
def _session_grow_batch_jit(state, pair_capacity, object_capacity):
    return jax.vmap(functools.partial(
        _grow_impl, pair_capacity=pair_capacity,
        object_capacity=object_capacity))(state)


def _check_grow(state: SessionState, pair_capacity: int,
                object_capacity: int) -> None:
    if pair_capacity < state.u.shape[-1]:
        raise ValueError(
            f"session_grow cannot shrink pair capacity "
            f"{state.u.shape[-1]} -> {pair_capacity}")
    if object_capacity < state.n_objects:
        raise ValueError(
            f"session_grow cannot shrink object capacity "
            f"{state.n_objects} -> {object_capacity}")


def session_grow(state: SessionState, pair_capacity: int,
                 object_capacity: int) -> SessionState:
    """Extend a live session to larger pair/object capacities (one
    dispatch, DESIGN.md §11).  Existing pair slots — labels, published
    bits, conflicts, priorities, in-flight positions — are untouched, so
    gateway tickets indexed into the old layout stay valid; a fresh state
    grown this way is bit-identical to ``make_session_state`` built at the
    larger capacities."""
    _check_grow(state, pair_capacity, object_capacity)
    engine_dispatches.add()
    return _session_grow_jit(state, pair_capacity, object_capacity)


def session_grow_batch(state: SessionState, pair_capacity: int,
                       object_capacity: int) -> SessionState:
    """Grow B stacked sessions to shared larger capacities (one dispatch)."""
    _check_grow(state, pair_capacity, object_capacity)
    engine_dispatches.add()
    return _session_grow_batch_jit(state, pair_capacity, object_capacity)


def _append_pairs_impl(state: SessionState, new_u: jax.Array,
                       new_v: jax.Array, mask: jax.Array) -> SessionState:
    """Claim padded pair slots for newly-arrived candidate pairs: ``mask``
    marks the slots to fill with ``new_u``/``new_v`` endpoints.  Arrivals
    enter UNKNOWN and unpublished; no union has happened and no neg key
    exists for them, so roots and the sorted neg-key index carry over
    bit-for-bit — exactly what ``make_session_state`` on the concatenated
    pair list would build (the appended slots keep their positional
    priority)."""
    return dataclasses.replace(
        state,
        u=jnp.where(mask, new_u.astype(jnp.int32), state.u),
        v=jnp.where(mask, new_v.astype(jnp.int32), state.v),
        labels=jnp.where(mask, UNKNOWN, state.labels),
    )


_session_append_pairs_jit = engine_jit("append_pairs", _append_pairs_impl)
_session_append_pairs_batch_jit = engine_jit("append_pairs_batch",
                                             jax.vmap(_append_pairs_impl))


def session_append_pairs(state: SessionState, new_u, new_v, mask
                         ) -> SessionState:
    """Fold newly-arrived pairs into padded slots (one dispatch).  The mask
    must claim only padded slots (past the live pair count — the serving
    layer tracks it); claimed slots become UNKNOWN candidates that the next
    frontier/deduce sweep treats like any other pending pair."""
    engine_dispatches.add()
    return _session_append_pairs_jit(state, jnp.asarray(new_u),
                                     jnp.asarray(new_v), jnp.asarray(mask))


def session_append_pairs_batch(state: SessionState, new_u, new_v, mask
                               ) -> SessionState:
    """(B, P) stacked variant of :func:`session_append_pairs`."""
    engine_dispatches.add()
    return _session_append_pairs_batch_jit(
        state, jnp.asarray(new_u), jnp.asarray(new_v), jnp.asarray(mask))


# ---------------------------------------------------------------------------
# State transformations (DESIGN.md §8, §9): apply / deduce / fold / frontier
# ---------------------------------------------------------------------------
def _apply_fast(state: SessionState, updates: jax.Array, new: jax.Array,
                pos_new: jax.Array, neg_new: jax.Array, roots: jax.Array):
    """The conflict-free fold (the pre-§9 incremental path): all answers
    accepted, fully parallel.  ``roots`` is the already-computed union over
    every incoming POS edge."""
    n = state.n_objects
    labels = jnp.where(new, updates, state.labels)
    sentinel = _sentinel_of(state.neg_keys)
    # re-key only when a union moved a root AND there are real keys to move
    # (an all-sentinel index — the common early-session case — needs no sort)
    moved = jnp.any(roots != state.roots) & _has_keys(state.neg_keys)
    negk = jax.lax.cond(
        moved, lambda nk: _rekey_impl(nk, roots, n), lambda nk: nk,
        state.neg_keys)
    fresh = canonical_keys(roots[state.u], roots[state.v], n)
    fresh = jnp.where(_per_key(neg_new, fresh), fresh, sentinel)
    negk = jax.lax.cond(
        jnp.any(neg_new),
        lambda nk: _merge_sorted_impl(nk, _sort_keys(fresh)),
        lambda nk: nk, negk)
    return labels, roots, negk, jnp.zeros(new.shape, bool)


def _apply_sequential(state: SessionState, updates: jax.Array,
                      new: jax.Array):
    """Exact sequential replay of a conflicting fold (DESIGN.md §9).

    Answers are applied one pair slot at a time in index order — pair order
    IS the labeling order, so this reproduces ``ClusterGraph.add_label``
    stream semantics bit-for-bit: an answer contradicting the evidence
    accepted so far (same cluster for a NEG, negatively-adjacent clusters
    for a POS) is rejected and flagged in the conflict mask; its label slot
    stays UNKNOWN for deduction (or a requery) to settle.

    The scan keeps ``roots`` fully compressed (one vectorized remap per
    accepted union) and carries the neg-key multiset unsorted in a (2P,)
    work array re-canonicalized after every union, so membership is a
    linear compare; the final state is re-sorted once on exit and equals a
    from-scratch rebuild from the surviving labels."""
    n = state.n_objects
    P = state.u.shape[0]
    kdt = state.neg_keys.dtype
    sentinel = jnp.asarray(jnp.iinfo(kdt).max, kdt)
    negw0 = jnp.concatenate(
        [state.neg_keys,
         jnp.full(state.neg_keys.shape[:-1] + (P,), sentinel, kdt)], axis=-1)

    def body(i, carry):
        labels, roots, negw, cmask = carry
        upd = updates[i]
        active = new[i]
        ru, rv = roots[state.u[i]], roots[state.v[i]]
        same = ru == rv
        key = canonical_keys(ru, rv, n)
        neg_hit = jnp.any(_keys_equal(negw, key)) & ~same
        conflict = active & ((same & (upd == NEG)) | (neg_hit & (upd == POS)))
        accept = active & ~conflict
        acc_pos = accept & (upd == POS) & ~same  # same-root POS: no-op union
        acc_neg = accept & (upd == NEG)
        labels = labels.at[i].set(jnp.where(accept, upd, labels[i]))
        # union: remap every vertex rooted at max(ru, rv) to min(ru, rv)
        roots = jnp.where(acc_pos & (roots == jnp.maximum(ru, rv)),
                          jnp.minimum(ru, rv), roots)
        # re-canonicalize the work keys under the post-union forest
        klo, khi, is_pad = _decompose_keys(negw, n)
        rlo, rhi = roots[klo], roots[khi]
        rekeyed = canonical_keys(rlo, rhi, n)
        negw = jnp.where(_per_key(acc_pos & ~is_pad, negw), rekeyed, negw)
        # an accepted NEG appends its key at the scratch slot for pair i
        negw = negw.at[..., P + i].set(jnp.where(acc_neg, key, sentinel))
        cmask = cmask.at[i].set(conflict)
        return labels, roots, negw, cmask

    labels, roots, negw, cmask = jax.lax.fori_loop(
        0, P, body,
        (state.labels, state.roots, negw0, jnp.zeros((P,), bool)))
    # keys are already canonical under the final roots; real keys never
    # exceed P (one per NEG-labeled pair), so the first P sorted slots hold
    # them all — bit-identical to a from-scratch rebuild
    return labels, roots, _sort_keys(negw)[..., :P], cmask


def _screen_impl(state: SessionState, updates: jax.Array):
    """The §9 conflict detector: run the optimistic union over every
    incoming POS edge and look for *self-keys* — a negative edge (existing
    or incoming) whose two endpoints land in one cluster.  Any contradiction
    in the stream, against the prior state or between answers inside the
    batch, produces a self-key under that union, so a clean check proves
    the batch conflict-free.  Returns the masks, the optimistic roots (the
    fast path's union — computed once), and the conflict flag."""
    n = state.n_objects
    new = (updates != UNKNOWN) & (state.labels == UNKNOWN)
    pos_new = new & (updates == POS)
    neg_new = new & (updates == NEG)
    roots_opt = _union_impl(state.roots, state.u, state.v, pos_new, n)
    olo, ohi, opad = _decompose_keys(state.neg_keys, n)
    old_self = ~opad & (roots_opt[olo] == roots_opt[ohi])
    fresh_self = neg_new & (roots_opt[state.u] == roots_opt[state.v])
    has_conflict = jnp.any(old_self) | jnp.any(fresh_self)
    return new, pos_new, neg_new, roots_opt, has_conflict


def _finish_apply(state: SessionState, labels, roots, negk, cmask,
                  new, count_round: bool, keep_conflicts_published: bool
                  ) -> SessionState:
    """Shared bookkeeping tail of every apply variant: published bits,
    round counter, per-pair conflict counts.  Rejected pairs keep their
    UNKNOWN label and increment ``conflicts``; their ``published`` bit is
    cleared like any answered pair unless ``keep_conflicts_published`` (the
    serving layer's requery policy) holds them in flight so the fused
    deduce cannot settle them before the escalated answer returns."""
    answered = new & ~cmask if keep_conflicts_published else new
    published = state.published & ~answered
    rounds = state.rounds
    if count_round:
        rounds = rounds + jnp.any(new).astype(jnp.int32)
    conflicts = state.conflicts + cmask.astype(jnp.int32)
    return dataclasses.replace(
        state, labels=labels, published=published, roots=roots,
        neg_keys=negk, rounds=rounds, conflicts=conflicts)


def _apply_impl(state: SessionState, updates: jax.Array, count_round: bool,
                keep_conflicts_published: bool
                ) -> Tuple[SessionState, jax.Array]:
    """Fold new labels into the state incrementally, screening conflicts.

    ``updates`` is (P,) int32, UNKNOWN where nothing landed.  A clean
    ``_screen_impl`` check proves the batch conflict-free and the
    fully-parallel fold applies (POS hooks by bounded pointer jumping, NEG
    keys merged by ``searchsorted``, re-key ``lax.cond``-gated as before).
    Otherwise an exact sequential replay reproduces the oracle's
    answer-at-a-time drop semantics.  Returns ``(state, conflict_mask)``.

    The ``lax.cond`` is a true branch only unbatched; under ``vmap`` it
    lowers to a select that pays for both sides, so the batched wrappers
    run the speculative `_apply_fast_flagged_impl` first and re-dispatch
    here only when some session's screen actually fired."""
    new, pos_new, neg_new, roots_opt, has_conflict = _screen_impl(state,
                                                                  updates)
    labels, roots, negk, cmask = jax.lax.cond(
        has_conflict,
        lambda: _apply_sequential(state, updates, new),
        lambda: _apply_fast(state, updates, new, pos_new, neg_new,
                            roots_opt))
    return _finish_apply(state, labels, roots, negk, cmask, new,
                         count_round, keep_conflicts_published), cmask


def _apply_fast_flagged_impl(state: SessionState, updates: jax.Array,
                             count_round: bool,
                             keep_conflicts_published: bool):
    """Speculative conflict-free apply: always takes the parallel path and
    returns the screen flag alongside ``(state, conflict_mask)``.  The
    caller must discard the result and fall back to the exact fold when the
    flag fired (the state would contain the §9 corruption signature)."""
    new, pos_new, neg_new, roots_opt, has_conflict = _screen_impl(state,
                                                                  updates)
    labels, roots, negk, cmask = _apply_fast(state, updates, new, pos_new,
                                             neg_new, roots_opt)
    return _finish_apply(state, labels, roots, negk, cmask, new,
                         count_round, keep_conflicts_published), \
        cmask, has_conflict


def _deduce_impl(state: SessionState) -> SessionState:
    """One deduction sweep over the maintained roots + neg-key index.  Pairs
    still in flight (``published``) are skipped — their crowd answers are the
    ones that will label them (§5.2 stream semantics).

    Deduction needs no structural maintenance beyond duplicate neg keys: a
    deduced-POS pair has equal roots by construction (no union can occur, so
    no re-key either), and a deduced-NEG pair joins already-negatively-
    adjacent clusters — its key is merged in as a duplicate, which is what a
    from-scratch rebuild would also contain, keeping the state bit-identical."""
    n = state.n_objects
    ded = _deduce_lookup_impl(state.roots, state.neg_keys, state.u, state.v,
                              n)
    new = (ded != UNKNOWN) & (state.labels == UNKNOWN) & ~state.published
    labels = jnp.where(new, ded, state.labels)
    neg_new = new & (ded == NEG)
    sentinel = _sentinel_of(state.neg_keys)
    fresh = canonical_keys(state.roots[state.u], state.roots[state.v], n)
    fresh = jnp.where(_per_key(neg_new, fresh), fresh, sentinel)
    negk = jax.lax.cond(
        jnp.any(neg_new),
        lambda nk: _merge_sorted_impl(nk, _sort_keys(fresh)),
        lambda nk: nk, state.neg_keys)
    return dataclasses.replace(state, labels=labels, neg_keys=negk)


def _fold_impl(state: SessionState, updates: jax.Array,
               keep_conflicts_published: bool
               ) -> Tuple[SessionState, jax.Array]:
    state, cmask = _apply_impl(state, updates, count_round=True,
                               keep_conflicts_published=keep_conflicts_published)
    return _deduce_impl(state), cmask


def _fold_fast_flagged_impl(state: SessionState, updates: jax.Array,
                            keep_conflicts_published: bool):
    state, cmask, flag = _apply_fast_flagged_impl(
        state, updates, count_round=True,
        keep_conflicts_published=keep_conflicts_published)
    return _deduce_impl(state), cmask, flag


def _seed_labels_impl(state: SessionState, seeds: jax.Array
                      ) -> Tuple[SessionState, jax.Array]:
    """Warm-start a session from cached cluster verdicts (DESIGN.md §14).

    ``seeds`` is (P,) int32 {UNKNOWN, NEG, POS} — per-slot labels recovered
    from a cross-query ``ClusterCache`` rather than paid for again.  The fold
    is exactly an answer fold (same conflict screen, same union/neg-key/deduce
    tail — property-tested bit-identical to ``session_fold_answers`` on the
    same updates) EXCEPT that ``rounds`` does not advance: seeding is capital
    carried in from earlier queries, not a crowd round of this one."""
    state, cmask = _apply_impl(state, seeds, count_round=False,
                               keep_conflicts_published=False)
    return _deduce_impl(state), cmask


def _seed_labels_fast_flagged_impl(state: SessionState, seeds: jax.Array):
    state, cmask, flag = _apply_fast_flagged_impl(
        state, seeds, count_round=False, keep_conflicts_published=False)
    return _deduce_impl(state), cmask, flag


def _trust_graph_impl(state: SessionState, mask: jax.Array) -> SessionState:
    """Requery-ladder endpoint (DESIGN.md §9): pairs whose escalated answers
    kept conflicting are pulled out of flight and labeled by deduction —
    the graph's evidence outvotes the crowd."""
    state = dataclasses.replace(state, published=state.published & ~mask)
    return _deduce_impl(state)


def _frontier_impl(state: SessionState) -> jax.Array:
    """Priority-Borůvka frontier over the live forest (parallel Algorithm 3).

    Starts from the state's roots instead of re-deriving components from the
    edge list: published pairs are hooked in as assumed-matching with one
    bounded union, and each Borůvka round's winners are likewise merged
    incrementally, with the neg-key index re-canonicalized per round.

    Selection runs on ``state.priority`` (DESIGN.md §10): the f32 priorities
    collapse to dense int32 *ranks* via a stable argsort, so equal priorities
    tie-break by pair index and the scatter-min machinery below stays exact.
    With ``priority == arange(P)`` (every fresh state) the ranks are the pair
    positions and the frontier is bit-identical to the historical
    position-is-priority selection (property-tested)."""
    u, v, n = state.u, state.v, state.n_objects
    P = u.shape[0]
    order = jnp.argsort(state.priority, stable=True)
    prio = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32))
    inf = jnp.int32(P)
    unknown = state.labels == UNKNOWN
    # the optimistic assumption only covers pairs the graph does not already
    # contradict: a published pair whose deduced label is NEG (a rejected
    # noisy answer awaiting requery, DESIGN.md §9) must not be hooked in as
    # matching — that union would cross a negative edge and corrupt the
    # frontier's working state.  This matches Algorithm 3, which skips
    # deducible pairs instead of inserting the optimistic label.
    ded_now = _deduce_lookup_impl(state.roots, state.neg_keys, u, v, n)
    pub = state.published & unknown & (ded_now != NEG)
    # sorted index ⇒ a real key, if any, sits at slot 0; the count of real
    # keys is invariant under re-keying, so one check covers every round
    has_neg = _has_keys(state.neg_keys)
    roots0 = _union_impl(state.roots, u, v, pub, n)
    negk0 = jax.lax.cond(
        jnp.any(pub) & has_neg,
        lambda nk: _rekey_impl(nk, roots0, n), lambda nk: nk,
        state.neg_keys)
    frontier0 = jnp.zeros((P,), dtype=bool)
    undecided0 = unknown & ~state.published

    def round_body(st):
        roots, negk, frontier, undecided, _ = st
        ru, rv = roots[u], roots[v]
        keys = canonical_keys(ru, rv, n)
        neg_hit = _in_sorted(negk, keys)
        # a candidate: undecided, endpoints in different clusters, no neg edge
        cand = undecided & (ru != rv) & ~neg_hit
        # pairs that became deducible drop out of contention permanently
        undecided = undecided & cand
        # each cluster's min-priority incident candidate edge is in the forest
        p = jnp.where(cand, prio, inf)
        best = jnp.full((n,), inf, dtype=jnp.int32)
        best = best.at[ru].min(p)
        best = best.at[rv].min(p)
        win = cand & ((best[ru] == prio) | (best[rv] == prio))
        frontier = frontier | win
        undecided = undecided & ~win
        progress = jnp.any(win)
        roots = jax.lax.cond(
            progress, lambda r: _union_impl(r, u, v, win, n), lambda r: r,
            roots)
        negk = jax.lax.cond(
            progress & has_neg,
            lambda nk: _rekey_impl(nk, roots, n), lambda nk: nk,
            negk)
        return roots, negk, frontier, undecided, progress

    def cond(st):
        return st[4]

    st = (roots0, negk0, frontier0, undecided0, jnp.bool_(True))
    _, _, frontier, _, _ = jax.lax.while_loop(cond, round_body, st)
    return frontier


def _mark_published_impl(state: SessionState, mask: jax.Array) -> SessionState:
    return dataclasses.replace(state, published=state.published | mask)


# ---------------------------------------------------------------------------
# On-device round engine (DESIGN.md §13): refresh -> frontier -> fold ->
# deduce advanced k rounds inside one donated-buffer while_loop, so a
# simulated crowd wave costs one dispatch instead of 3+ host round-trips.
# ---------------------------------------------------------------------------
# exit codes reported by `session_run_rounds`:
ROUNDS_RUNNING = 0   # budget exhausted mid-stream — more rounds remain
ROUNDS_DONE = 1      # no UNKNOWN labels left on entry to a round
ROUNDS_EMPTY = 2     # empty frontier with UNKNOWNs left (host must deduce
                     # or declare the session stuck — mirrors the legacy
                     # empty-frontier branch)
ROUNDS_CONFLICT = 3  # §9 screen fired — state is pre-fold; the host replays
                     # the round through the exact sequential path


def _select_state(pred, a: SessionState, b: SessionState) -> SessionState:
    """Per-leaf ``where`` over two states (vmap-safe branchless select)."""
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


def _run_rounds_impl(state: SessionState, answers: jax.Array,
                     prior: jax.Array, adaptive: jax.Array,
                     rounds_allowed: jax.Array, max_rounds: int):
    """Advance up to ``min(rounds_allowed, max_rounds)`` labeling rounds on
    device.  ``answers`` is the precomputed (order-independent) crowd answer
    per pair slot; each round folds exactly the frontier's slice of it —
    bit-identical to the host loop that refreshes, selects, uploads those
    answers and folds, because that is literally the loop body.

    The loop exits early on completion, an empty frontier, or a §9 conflict
    screen (the exact sequential replay cannot live under ``vmap`` — the
    host runs that one round through the legacy path instead).  On conflict
    the carried state is the *pre-fold* refreshed state; refresh is
    idempotent, so the legacy replay of the same round starts bit-identical.

    Returns ``(state, crowdsourced, round_sizes, rounds_done, code)``.
    """
    from .ordering import _refresh_masked_impl  # circular import (see §10)
    P = state.u.shape[0]
    ra = jnp.minimum(jnp.asarray(rounds_allowed, jnp.int32), max_rounds)

    def cond(carry):
        _, _, _, r, code = carry
        return (code == ROUNDS_RUNNING) & (r < ra)

    def body(carry):
        st0, crowd, sizes, r, code = carry
        done0 = ~jnp.any(st0.labels == UNKNOWN)
        st = _refresh_masked_impl(st0, prior, adaptive)
        frontier = _frontier_impl(st)
        updates = jnp.where(frontier, answers, UNKNOWN)
        new, pos_new, neg_new, roots_opt, has_conflict = _screen_impl(
            st, updates)
        labels, roots, negk, cmask = _apply_fast(st, updates, new, pos_new,
                                                 neg_new, roots_opt)
        folded = _finish_apply(st, labels, roots, negk, cmask, new,
                               count_round=True,
                               keep_conflicts_published=False)
        folded = _deduce_impl(folded)
        empty = ~jnp.any(frontier)
        conflict = has_conflict & ~done0
        advanced = ~done0 & ~conflict & ~empty
        nxt = _select_state(done0, st0,
                            _select_state(conflict, st, folded))
        crowd = jnp.where(advanced, crowd | frontier, crowd)
        cnt = frontier.sum(dtype=jnp.int32)
        sizes = jnp.where(advanced, sizes.at[r].set(cnt), sizes)
        code = jnp.where(done0, ROUNDS_DONE,
               jnp.where(conflict, ROUNDS_CONFLICT,
               jnp.where(empty, ROUNDS_EMPTY,
                         ROUNDS_RUNNING))).astype(jnp.int32)
        r = r + advanced.astype(jnp.int32)
        return nxt, crowd, sizes, r, code

    carry = (state, jnp.zeros((P,), bool),
             jnp.zeros((max_rounds,), jnp.int32),
             jnp.int32(0), jnp.int32(ROUNDS_RUNNING))
    return jax.lax.while_loop(cond, body, carry)


# jitted public entry points (counted host dispatches)
_session_frontier_jit = engine_jit("frontier", _frontier_impl)
_session_frontier_batch_jit = engine_jit("frontier_batch",
                                         jax.vmap(_frontier_impl))


def _apply_one(state, updates, keep_conflicts_published):
    return _apply_impl(state, updates, count_round=True,
                       keep_conflicts_published=keep_conflicts_published)


def _batched(step: str, fn, donate: bool = False):
    """vmap over (state, updates) with the static policy flag closed over,
    jitted as ``engine_<step>``.  ``donate`` hands the stacked state's
    buffers to XLA for in-place reuse (DESIGN.md §13) — only safe for
    variants whose callers never touch the input state again."""
    def call(state, updates, keep_conflicts_published):
        return jax.vmap(functools.partial(
            fn, keep_conflicts_published=keep_conflicts_published))(
                state, updates)
    return engine_jit(step, call,
                      static_argnames=("keep_conflicts_published",),
                      donate_argnums=(0,) if donate else ())


# Donation discipline (DESIGN.md §13): state-in/state-out transformations
# donate the input state so XLA updates buffers in place instead of copying
# ~(2P + n) words per round.  NOT donated: the speculative fast variants
# (their caller re-dispatches the exact fold with the ORIGINAL state when a
# screen flag fires), frontier/gains (read-only), mark_published/append
# (cheap, callers often keep the old state), grow (shape-changing outputs
# can't alias — XLA warns the donated buffers are unusable), and
# session_from_labels (inputs are plain arrays the caller owns).
_session_apply_jit = engine_jit(
    "apply", _apply_one, static_argnames=("keep_conflicts_published",),
    donate_argnums=(0,))
# exact batched variants: under vmap the screening cond lowers to a select
# that executes BOTH branches, including the O(P^2) sequential replay — used
# only as the fallback when a speculative fast fold's screen actually fired
_session_apply_batch_jit = _batched("apply_batch", _apply_one, donate=True)
_session_apply_fast_batch_jit = _batched(
    "apply_fast_batch",
    functools.partial(_apply_fast_flagged_impl, count_round=True))
_session_deduce_jit = engine_jit("deduce", _deduce_impl, donate_argnums=(0,))
_session_deduce_batch_jit = engine_jit(
    "deduce_batch", jax.vmap(_deduce_impl), donate_argnums=(0,))
_session_fold_jit = engine_jit(
    "fold", _fold_impl, static_argnames=("keep_conflicts_published",),
    donate_argnums=(0,))
_session_fold_batch_jit = _batched("fold_batch", _fold_impl, donate=True)
_session_fold_fast_batch_jit = _batched("fold_fast_batch",
                                        _fold_fast_flagged_impl)
_session_seed_jit = engine_jit("seed", _seed_labels_impl, donate_argnums=(0,))
_session_seed_batch_jit = engine_jit(
    "seed_batch", jax.vmap(_seed_labels_impl), donate_argnums=(0,))
_session_seed_fast_batch_jit = engine_jit(
    "seed_fast_batch", jax.vmap(_seed_labels_fast_flagged_impl))
_session_mark_published_jit = engine_jit("mark_published",
                                         _mark_published_impl)
_session_mark_published_batch_jit = engine_jit(
    "mark_published_batch", jax.vmap(_mark_published_impl))
_session_trust_graph_jit = engine_jit("trust_graph", _trust_graph_impl,
                                      donate_argnums=(0,))
_session_trust_graph_batch_jit = engine_jit(
    "trust_graph_batch", jax.vmap(_trust_graph_impl), donate_argnums=(0,))
_session_run_rounds_jit = engine_jit(
    "run_rounds", _run_rounds_impl, static_argnames=("max_rounds",),
    donate_argnums=(0,))


def _run_rounds_batch(state, answers, prior, adaptive, rounds_allowed,
                      max_rounds):
    return jax.vmap(functools.partial(
        _run_rounds_impl, max_rounds=max_rounds))(
            state, answers, prior, adaptive, rounds_allowed)


_session_run_rounds_batch_jit = engine_jit(
    "run_rounds_batch", _run_rounds_batch, static_argnames=("max_rounds",),
    donate_argnums=(0,))


def session_frontier(state: SessionState) -> jax.Array:
    """(P,) bool mask of pairs to crowdsource now, from the live state."""
    engine_dispatches.add()
    return _session_frontier_jit(state)


def session_frontier_batch(state: SessionState) -> jax.Array:
    """(B, P) stacked frontier masks, one dispatch for B sessions."""
    engine_dispatches.add()
    return _session_frontier_batch_jit(state)


def session_apply_answers(state: SessionState, updates,
                          keep_conflicts_published: bool = False
                          ) -> Tuple[SessionState, jax.Array]:
    """Fold crowd answers (UNKNOWN = nothing landed) into the state.
    Returns ``(state, conflict_mask)`` — rejected contradictory answers are
    flagged in the mask and counted in ``state.conflicts`` (DESIGN.md §9)."""
    engine_dispatches.add()
    return _session_apply_jit(state, updates, keep_conflicts_published)


def session_apply_answers_batch(state: SessionState, updates,
                                keep_conflicts_published: bool = False
                                ) -> Tuple[SessionState, jax.Array]:
    """Speculative-fast batched apply: one dispatch takes the parallel path
    for all B sessions and returns per-session screen flags; only when some
    session's stream actually conflicted does a second dispatch re-run the
    exact (sequential-replay) fold — so conflict-free serving rounds cost
    the same as the pre-§9 path."""
    engine_dispatches.add()
    new_state, cmask, flags = _session_apply_fast_batch_jit(
        state, updates, keep_conflicts_published)
    if not bool(jnp.any(flags)):
        return new_state, cmask
    engine_dispatches.add()
    return _session_apply_batch_jit(state, updates, keep_conflicts_published)


def session_deduce(state: SessionState) -> SessionState:
    """One deduction sweep; skips in-flight (published) pairs."""
    engine_dispatches.add()
    return _session_deduce_jit(state)


def session_deduce_batch(state: SessionState) -> SessionState:
    engine_dispatches.add()
    return _session_deduce_batch_jit(state)


def session_fold_answers(state: SessionState, updates,
                         keep_conflicts_published: bool = False
                         ) -> Tuple[SessionState, jax.Array]:
    """apply_answers + deduce fused into a single device dispatch.

    The fold is agnostic to where an answer came from: per-pair ballots,
    requery escalations, and agreed cluster-task verdicts (DESIGN.md §15)
    all arrive as the same (P,) engine-encoded update vector and pass
    through the same conflict screen — which is exactly why cluster-task
    decoding is conflict-screen-identical to submitting the covered pairs
    individually (property-tested in tests/test_crowd.py).

    Returns ``(state, conflict_mask)``."""
    engine_dispatches.add()
    return _session_fold_jit(state, updates, keep_conflicts_published)


def session_fold_answers_batch(state: SessionState, updates,
                               keep_conflicts_published: bool = False
                               ) -> Tuple[SessionState, jax.Array]:
    """Speculative-fast batched fold (see ``session_apply_answers_batch``):
    the conflict-free common case is one parallel dispatch; the exact fold
    re-runs only when a screen flag fired."""
    engine_dispatches.add()
    new_state, cmask, flags = _session_fold_fast_batch_jit(
        state, updates, keep_conflicts_published)
    if not bool(jnp.any(flags)):
        return new_state, cmask
    engine_dispatches.add()
    return _session_fold_batch_jit(state, updates, keep_conflicts_published)


def session_seed_labels(state: SessionState, seeds
                        ) -> Tuple[SessionState, jax.Array]:
    """Warm-start fold of cached cluster verdicts (DESIGN.md §14): one
    dispatch applies + deduces the (P,) int32 ``seeds`` exactly like
    ``session_fold_answers`` but WITHOUT advancing ``rounds`` — seeded
    labels were paid for by an earlier query, not this session's crowd.
    Returns ``(state, conflict_mask)``; contradictory seeds are rejected by
    the §9 screen and flagged so the caller never counts them as hits.  The
    input state is donated."""
    engine_dispatches.add()
    return _session_seed_jit(state, seeds)


def session_seed_labels_batch(state: SessionState, seeds
                              ) -> Tuple[SessionState, jax.Array]:
    """Speculative-fast batched seed fold (see ``session_fold_answers_batch``):
    the conflict-free common case is one parallel dispatch; the exact fold
    re-runs only when a screen flag fired."""
    engine_dispatches.add()
    new_state, cmask, flags = _session_seed_fast_batch_jit(state, seeds)
    if not bool(jnp.any(flags)):
        return new_state, cmask
    engine_dispatches.add()
    return _session_seed_batch_jit(state, seeds)


def session_mark_published(state: SessionState, mask) -> SessionState:
    """Record pairs as posted to the crowd (in-flight)."""
    engine_dispatches.add()
    return _session_mark_published_jit(state, mask)


def session_mark_published_batch(state: SessionState, mask) -> SessionState:
    engine_dispatches.add()
    return _session_mark_published_batch_jit(state, mask)


def session_trust_graph(state: SessionState, mask) -> SessionState:
    """Resolve requery-exhausted pairs: un-publish ``mask`` and deduce their
    labels from the graph (one dispatch, DESIGN.md §9)."""
    engine_dispatches.add()
    return _session_trust_graph_jit(state, mask)


def session_trust_graph_batch(state: SessionState, mask) -> SessionState:
    engine_dispatches.add()
    return _session_trust_graph_batch_jit(state, mask)


def session_run_rounds(state: SessionState, answers, max_rounds: int,
                       prior=None, adaptive: bool = False,
                       rounds_allowed=None):
    """Advance up to ``max_rounds`` labeling rounds in ONE device dispatch
    (DESIGN.md §13): refresh -> frontier -> fold -> deduce iterated inside a
    donated-buffer ``while_loop``, bit-identical to driving the per-round
    entry points from the host with the same ``answers``.

    ``answers`` is (P,) int32 — the crowd's answer for every pair slot
    (available up front when answers are order-independent, e.g. a replayed
    or deterministic crowd); each round folds only the frontier's slice.
    ``rounds_allowed`` (defaults to ``max_rounds``) caps rounds dynamically
    (budget scheduling) without recompiling.  The input ``state`` is
    donated — callers must not touch it afterwards.

    Returns ``(state, crowdsourced, round_sizes, rounds_done, code)`` with
    ``code`` one of the ``ROUNDS_*`` constants.
    """
    P = state.u.shape[0]
    if prior is None:
        prior = jnp.zeros((P,), jnp.float32)
    if rounds_allowed is None:
        rounds_allowed = max_rounds
    engine_dispatches.add()
    return _session_run_rounds_jit(
        state, jnp.asarray(answers), jnp.asarray(prior, jnp.float32),
        jnp.asarray(adaptive, bool),
        jnp.asarray(rounds_allowed, jnp.int32), max_rounds=max_rounds)


def session_run_rounds_batch(state: SessionState, answers, max_rounds: int,
                             prior=None, adaptive=None,
                             rounds_allowed=None):
    """Advance B stacked sessions up to ``max_rounds`` rounds each in ONE
    dispatch — the cross-lane megabatch the serving layer drives a whole
    simulated crowd wave with.  Per-session ``adaptive`` (B,) bool and
    ``rounds_allowed`` (B,) int32 preserve each lane's ordering policy and
    budget; finished sessions are held fixed by the vmapped ``while_loop``
    (batched results equal the unbatched ones, property-tested).  The input
    ``state`` is donated."""
    B, P = state.u.shape
    if prior is None:
        prior = jnp.zeros((B, P), jnp.float32)
    if adaptive is None:
        adaptive = np.zeros(B, bool)
    if rounds_allowed is None:
        rounds_allowed = np.full(B, max_rounds, np.int32)
    engine_dispatches.add()
    return _session_run_rounds_batch_jit(
        state, jnp.asarray(answers), jnp.asarray(prior, jnp.float32),
        jnp.asarray(adaptive, bool),
        jnp.asarray(rounds_allowed, jnp.int32), max_rounds=max_rounds)


# ---------------------------------------------------------------------------
# Thin from-scratch wrappers (oracle parity tests; historical signatures)
# ---------------------------------------------------------------------------
@engine_jit("boruvka_frontier", static_argnames=("n_objects",))
def _boruvka_frontier_jit(u, v, labels, published, n_objects):
    return _frontier_impl(
        _state_from_labels_impl(u, v, labels, published, n_objects))


def boruvka_frontier(u: jax.Array, v: jax.Array, labels: jax.Array,
                     published: jax.Array, n_objects: int) -> jax.Array:
    """Returns a bool mask of pairs to crowdsource now.

    Thin from-scratch wrapper: rebuilds a :class:`SessionState` from the
    label arrays, then runs the state frontier.  The rebuilt state carries
    the positional priority ``arange(P)`` (the caller passes pairs already
    in labeling order), so ``i < j`` means pair i precedes pair j in ω —
    the static-order reference the live-priority path (DESIGN.md §10) is
    property-tested against.
    """
    engine_dispatches.add()
    return _boruvka_frontier_jit(u, v, labels, published, n_objects)


@engine_jit("boruvka_frontier_batch", static_argnames=("n_objects",))
def _boruvka_frontier_batch_jit(u, v, labels, published, n_objects):
    def one(uu, vv, ll, pp):
        return _frontier_impl(
            _state_from_labels_impl(uu, vv, ll, pp, n_objects))
    return jax.vmap(one)(u, v, labels, published)


def boruvka_frontier_batch(u: jax.Array, v: jax.Array, labels: jax.Array,
                           published: jax.Array, n_objects: int) -> jax.Array:
    """(B, P) stacked sessions -> (B, P) bool frontier masks (from scratch).

    The vmapped ``while_loop`` iterates until every session's frontier
    converges; already-converged sessions are held fixed by the batching
    rule, so per-session results equal the unbatched ``boruvka_frontier``.
    """
    engine_dispatches.add()
    return _boruvka_frontier_batch_jit(u, v, labels, published, n_objects)


@engine_jit("deduce_sessions", static_argnames=("n_objects",))
def _deduce_sessions_jit(u, v, labels, n_objects):
    def one(uu, vv, ll):
        st = _state_from_labels_impl(uu, vv, ll,
                                     jnp.zeros(ll.shape, bool), n_objects)
        return _deduce_impl(st).labels
    return jax.vmap(one)(u, v, labels)


def deduce_sessions(u: jax.Array, v: jax.Array, labels: jax.Array,
                    n_objects: int) -> jax.Array:
    """One deduction sweep over B stacked sessions, from scratch: every
    UNKNOWN pair whose label follows from the POS/NEG evidence is filled in.
    Returns the updated (B, P) label array."""
    engine_dispatches.add()
    return _deduce_sessions_jit(u, v, labels, n_objects)


# ---------------------------------------------------------------------------
# Multi-session packing (DESIGN.md §7)
# ---------------------------------------------------------------------------
def pack_sessions(sessions, pair_capacity: int = 0, object_capacity: int = 0):
    """Pack ragged sessions [(u, v, n_objects), ...] into stacked arrays.

    Returns (U, V, labels0, valid) with shapes (B, P_cap) / (B, P_cap);
    padded slots hold the inert pre-labeled POS self-loop (0, 0)."""
    B = len(sessions)
    p_cap = max(pair_capacity, max(len(u) for u, _, _ in sessions))
    U = np.zeros((B, p_cap), np.int32)
    V = np.zeros((B, p_cap), np.int32)
    labels0 = np.full((B, p_cap), POS, np.int32)
    valid = np.zeros((B, p_cap), bool)
    for b, (u, v, _) in enumerate(sessions):
        p = len(u)
        U[b, :p] = u
        V[b, :p] = v
        labels0[b, :p] = UNKNOWN
        valid[b, :p] = True
    n_cap = max(object_capacity, max(n for _, _, n in sessions))
    return U, V, labels0, valid, n_cap


def label_parallel_jax_batch(
    sessions,
    crowd_fn,
    pair_capacity: int = 0,
    object_capacity: int = 0,
) -> list:
    """Advance B independent join sessions with one device dispatch per round.

    ``sessions`` — list of ``(u, v, n_objects)``; pairs already in labeling
    order (position = priority), exactly as ``label_parallel_jax`` expects.
    ``crowd_fn(b, idx_array) -> int32 array of {NEG, POS}`` labels session
    ``b``'s frontier.  Optional capacities let callers pad to stable shapes
    (one jit cache entry across waves).

    The whole batch lives in one stacked :class:`SessionState`: sessions are
    packed once up front, every round is one frontier dispatch + one fused
    apply+deduce dispatch over the persistent state (DESIGN.md §8).
    Contradictory crowd answers are dropped at the fold and counted
    (DESIGN.md §9); the rejected pair gets its deduced label instead.

    Returns ``[(labels, crowdsourced_mask, round_sizes, n_conflicts), ...]``
    per session, identical to running ``label_parallel_jax`` on each
    session alone.
    """
    B = len(sessions)
    U, V, labels0, valid, n_cap = pack_sessions(
        sessions, pair_capacity, object_capacity)
    state = make_session_state_batch(U, V, labels0, n_cap)
    crowdsourced = np.zeros(labels0.shape, dtype=bool)
    rounds: list = [[] for _ in range(B)]
    labels_host = labels0.copy()
    while (labels_host == UNKNOWN).any():
        frontier = np.asarray(session_frontier_batch(state))
        if not frontier.any():
            # everything left (in every session) is deducible
            state = session_deduce_batch(state)
            labels_host = np.asarray(state.labels)
            assert not (labels_host == UNKNOWN).any(), "engine stuck"
            break
        updates = np.full(labels0.shape, UNKNOWN, np.int32)
        for b in range(B):
            idx = np.nonzero(frontier[b])[0]
            if len(idx) == 0:
                continue
            rounds[b].append(len(idx))
            crowdsourced[b, idx] = True
            updates[b, idx] = crowd_fn(b, idx)
        engine_dispatches.add()  # updates upload
        state, _ = session_fold_answers_batch(state, jnp.asarray(updates))
        labels_host = np.asarray(state.labels)
    conflicts = np.asarray(state.conflicts)
    return [
        (labels_host[b, valid[b]], crowdsourced[b, valid[b]], rounds[b],
         int(conflicts[b, valid[b]].sum()))
        for b in range(B)
    ]


# ---------------------------------------------------------------------------
# Full batch-parallel labeling loop (host-driven, device inner loops).
# Kept deliberately from-scratch per round: this is the reference the
# incremental session-state path is property-tested bit-identical against.
# ---------------------------------------------------------------------------
def label_parallel_jax(
    u: np.ndarray,
    v: np.ndarray,
    n_objects: int,
    crowd_fn,
    prior: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, list, int]:
    """Iterate: frontier -> crowd -> deduce, entirely with the array engine.

    ``crowd_fn(idx_array) -> int32 array of {NEG, POS}`` labels the frontier.
    Crowd answers contradicting the accumulated evidence are dropped at the
    conflict-aware fold (the pair gets its deduced label) and counted.
    With ``prior`` (the per-pair machine likelihoods) the labeling order is
    *adaptive* (DESIGN.md §10): priorities are refreshed from the live
    posterior before every frontier instead of staying positional.
    Returns (labels, crowdsourced_mask, per-round frontier sizes,
    n_conflicts).
    """
    P = len(u)
    uj = jnp.asarray(u, jnp.int32)
    vj = jnp.asarray(v, jnp.int32)
    prior_j = None if prior is None else jnp.asarray(prior, jnp.float32)
    labels = jnp.full((P,), UNKNOWN, jnp.int32)
    crowdsourced = np.zeros(P, dtype=bool)
    published = jnp.zeros((P,), dtype=bool)
    rounds = []
    n_conflicts = 0
    while bool(jnp.any(labels == UNKNOWN)):
        if prior_j is None:
            frontier = boruvka_frontier(uj, vj, labels, published, n_objects)
        else:
            from .ordering import session_refresh_priorities

            st = session_from_labels(uj, vj, labels, published, n_objects)
            st = session_refresh_priorities(st, prior_j)
            frontier = session_frontier(st)
        idx = np.nonzero(np.asarray(frontier))[0]
        if len(idx) == 0:
            # everything left is deducible
            state = session_from_labels(uj, vj, labels, published, n_objects)
            state = session_deduce(state)
            labels = state.labels
            assert not bool(jnp.any(labels == UNKNOWN)), "engine stuck"
            break
        rounds.append(len(idx))
        crowdsourced[idx] = True
        got = crowd_fn(idx)
        updates = np.full(P, UNKNOWN, np.int32)
        updates[idx] = np.asarray(got, np.int32)
        # from-scratch rebuild + conflict-aware fold (apply + deduce sweep)
        state = session_from_labels(uj, vj, labels, published, n_objects)
        engine_dispatches.add()  # updates upload
        state, cmask = session_fold_answers(state, jnp.asarray(updates))
        labels = state.labels
        n_conflicts += int(np.asarray(cmask).sum())
    return np.asarray(labels), crowdsourced, rounds, n_conflicts
