"""JoinService — streaming join requests over the persistent session engine.

The serving counterpart of ``ServeEngine`` for the paper's pipeline
(DESIGN.md §7, §8): join requests queue up, get packed into a fixed number of
session *lanes*, and every lane carries a device-resident
:class:`~repro.core.jax_graph.SessionState` that is packed **once** at lane
open and updated incrementally — no per-round re-pack, no from-scratch
component/neg-key rebuilds.  All crowd I/O goes through a
:class:`~repro.core.crowd.CrowdGateway` (batched ``post`` / ``poll``), never
a per-pair host loop.

Two serving disciplines over the same state machinery:

* **Round barrier** (``async_mode=False``, the default): every engine round
  is one batched frontier dispatch over bucket-grouped stacked lane states,
  one gateway post per lane, a full gateway drain, and one fused
  apply+deduce dispatch.  A lane whose session fully labels is finalized and
  refilled from the queue mid-wave — the same continuous lane-refill design
  ``ServeEngine`` uses for decode lanes.
* **Asynchronous ID/NF** (``async_mode=True``): the event-driven regime of
  §5.2, lifted from ``core/parallel.py``'s host simulator into serving.  A
  lane folds answers the moment the gateway delivers them; a returned
  non-matching answer (or a drained lane) triggers an immediate deduce +
  re-frontier + post instead of waiting for the round barrier, and with
  ``nf=True`` the gateway steers workers to probable-non-matching pairs
  first.  With a ``LatencyModel`` attached, ``sim_minutes`` on the results
  reports the simulated platform wall clock.

Noisy crowds make answers *conflict* with transitivity (DESIGN.md §9).
Every fold screens answers against the live state; a contradictory answer
is rejected, counted (``JoinSessionResult.n_conflicts``), and resolved per
``conflict_policy``:

* ``"drop"`` (default, the sequential oracle's semantics): the rejected
  answer is discarded and the pair takes its deduced label.
* ``"requery"``: the rejected pair stays in flight and goes back through
  the gateway with an escalated assignment count (3-way → 5-way); if the
  escalated answer still contradicts the graph, the pair is *exhausted*
  and the graph's deduced label wins (trust-the-graph).

Shapes are bucketed to powers of two (pair and object capacities) at lane
open, so lane churn reuses a handful of jit cache entries instead of
recompiling per request mix.

The machine phase plugs in through :meth:`submit_embeddings`, which runs the
mesh-sharded candidate generator (``sharded_candidates``) and feeds the
resulting pairs straight into a session lane.

**Streaming ingest** (DESIGN.md §11): a production service receives objects
continuously — new records must be scored against the live corpus and their
pairs folded into sessions that already have crowd work in flight.
:meth:`append` routes arrival epochs into an open request; at the next
ingest point its lane *grows* in place (``session_grow`` +
``session_append_pairs`` — capacities re-bucketed, neg-key index re-encoded
under the larger object universe, published bits and gateway tickets
untouched), migrates to the matching capacity bucket group, and the new
pairs enter the priority machinery (merged expected ranks, or the adaptive
posterior refresh).  :meth:`submit_stream` packages a k-epoch arrival
schedule; with the default up-front schedule the grown state is
bit-identical to a batch-built one, so the run matches a single-shot
:meth:`submit` label-for-label (the differential harness in
``tests/test_streaming.py``).  :meth:`submit_embeddings`
(``streaming=True``) + :meth:`append_embeddings` run the machine phase
incrementally: a cached :class:`StreamingCandidateIndex` scores only
new-vs-corpus and new-vs-new blocks instead of rescoring the cross product.
"""
from __future__ import annotations

import collections
import dataclasses
import os
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.crowd import CostModel, Crowd, CrowdGateway, LatencyModel, \
    PerfectCrowd
from repro.core.jax_graph import (
    ROUNDS_CONFLICT, ROUNDS_DONE, ROUNDS_EMPTY, ROUNDS_RUNNING,
    UNKNOWN, POS, SessionState, engine_dispatches, make_session_state,
    next_pow2, pair_keys_fit, session_append_pairs, session_apply_answers,
    session_deduce, session_fold_answers, session_fold_answers_batch,
    session_frontier, session_frontier_batch, session_grow,
    session_mark_published, session_mark_published_batch,
    session_run_rounds_batch, session_seed_labels, session_trust_graph,
    session_trust_graph_batch)
from repro.core.metrics import Quality, quality
from repro.core.ordering import (session_gains, session_gains_batch,
                                 session_refresh_priorities,
                                 session_refresh_priorities_batch)
from repro.core.pairs import PairSet
from repro.core.sorting import get_order, validate_order


@dataclasses.dataclass
class JoinRequest:
    """One join submission. ``_admit`` is the single admission gate for every
    construction path (``submit``, ``submit_embeddings``, the plan executor):
    it resolves the ``None`` fields below to the service defaults, validates,
    assigns the rid, and enqueues — so a request object built anywhere gets
    identical treatment."""

    rid: Optional[int]
    pairs: PairSet                 # machine-phase candidates
    crowd: Optional[Crowd] = None  # None -> PerfectCrowd
    order: Optional[str] = None    # None -> service default
    total_true_matches: Optional[int] = None
    # budget-aware scheduling (DESIGN.md §10): crowd spend is capped at
    # budget_cents, priced per assignment; None -> service default
    budget_cents: Optional[float] = None
    cost_per_assignment: Optional[float] = None
    # cross-query warm start (DESIGN.md §14): (P,) int32 {UNKNOWN, NEG, POS}
    # in the request's pair order — verdicts recovered from a ClusterCache.
    # Seeded pairs fold into the session at lane open WITHOUT being posted to
    # the gateway, so spend accounting never bills them.
    seed_labels: Optional[np.ndarray] = None
    # admission-control provenance (DESIGN.md §16), set by the service:
    # whether this request waited in the queue behind fully-occupied lanes,
    # and whether its budget was clamped to the remaining global envelope
    admission_deferred: bool = False
    envelope_clamped: bool = False


@dataclasses.dataclass
class JoinSessionResult:
    """Served outcome of one join request.

    Carries the decoded labels (request pair order), which pairs the crowd
    answered vs the graph deduced, round/cost/latency accounting, and the
    §9/§10/§14/§15 provenance counters.  Retrieved from
    ``JoinService.run()``'s ``{rid: result}`` map.

    Example::

        >>> res = service.run()[rid]
        >>> res.n_crowdsourced + res.n_deduced == len(res.labels)
        True
    """

    rid: int
    labels: np.ndarray             # (P,) bool over the request's pairs
    crowdsourced: np.ndarray       # (P,) bool
    n_rounds: int
    round_sizes: List[int]
    n_hits: int
    cost_cents: float
    quality: Optional[Quality]
    sim_minutes: Optional[float] = None  # gateway clock at completion
    # device-side answer-fold counter (SessionState.rounds): equals n_rounds
    # under the round barrier; under async ID/NF it counts poll events that
    # landed answers, i.e. how often the lane re-engaged the engine
    fold_rounds: int = 0
    # error-tolerance accounting (DESIGN.md §9)
    n_conflicts: int = 0           # contradictory answers rejected at the fold
    n_requeried: int = 0           # rejected pairs re-posted with escalation
    # budget accounting (DESIGN.md §10): gateway assignment-level spend and
    # whether the session stopped because it ran out of budget (remaining
    # pairs resolved by trusting the graph — undeducible ones report
    # non-matching)
    n_spent_cents: float = 0.0
    stopped_on_budget: bool = False
    # cross-query cache provenance (DESIGN.md §14): pairs resolved by seeded
    # cluster verdicts at lane open — never posted, never billed.  Counted in
    # neither ``crowdsourced`` nor the gateway spend.
    n_cache_hits: int = 0
    # multi-pair task accounting (DESIGN.md §15): cluster tasks posted for
    # this request; their decoded pair verdicts are counted in
    # ``crowdsourced`` like any other answer.  ``n_cluster_pairs`` is the
    # subset of ``crowdsourced`` resolved by agreed cluster verdicts
    # (disagreements escalated to pair ballots are excluded), and
    # ``n_cluster_cents`` the total cluster-task spend at the §15 price
    n_cluster_tasks: int = 0
    n_cluster_pairs: int = 0
    n_cluster_cents: float = 0.0
    # admission-control provenance (DESIGN.md §16): the request queued
    # behind fully-occupied lanes before opening, and/or its budget was
    # clamped down to the remaining global spend envelope
    admission_deferred: bool = False
    envelope_clamped: bool = False

    @property
    def n_crowdsourced(self) -> int:
        """Pairs answered by the crowd (pair tasks + cluster verdicts)."""
        return int(self.crowdsourced.sum())

    @property
    def n_deduced(self) -> int:
        """Pairs labeled by transitive deduction instead of the crowd."""
        return len(self.labels) - self.n_crowdsourced


@dataclasses.dataclass
class _Lane:
    req: JoinRequest
    perm: np.ndarray               # labeling order over the request's pairs
    ordered: PairSet               # req.pairs.take(perm)
    p: int                         # true pair count (before capacity padding)
    state: SessionState            # device-resident, packed once at open
    labels_host: np.ndarray        # (p,) int32 mirror for done/progress checks
    crowdsourced: np.ndarray       # (p,) bool, ordered
    round_sizes: List[int]
    prior_host: np.ndarray         # (p_cap,) f32 machine likelihood, padded
    prior_dev: jax.Array           # device copy for single-lane dispatches
    adaptive: bool                 # live posterior re-ranking (DESIGN.md §10)
    rate_cents: float              # per-assignment price for this session
    per_pair_cents: float          # expected price of one crowd question
    budget_cents: Optional[float]  # None = unlimited
    in_flight: int = 0             # pairs posted to the gateway, unanswered
    n_requeried: int = 0           # escalated re-posts for rejected answers
    budget_stopped: bool = False   # out of budget; graph resolved the rest
    # on-device round engine (DESIGN.md §13): the crowd's order-independent
    # answer per ordered pair slot (None when the crowd is stateful), and
    # whether the fused path is still trusted for this lane (a §9 conflict
    # screen drops the lane back to the exact per-round path for good)
    answers_host: Optional[np.ndarray] = None
    fused_ok: bool = True
    # cross-query cache provenance (DESIGN.md §14)
    n_cache_hits: int = 0
    # cluster-task scheduling (DESIGN.md §15): host mirror of which ordered
    # pair slots have an unanswered gateway task out (pair or cluster) —
    # the harvest planner must not cover a pair twice
    inflight_host: Optional[np.ndarray] = None
    n_cluster_tasks: int = 0
    n_cluster_cents: float = 0.0

    @property
    def done(self) -> bool:
        if self.budget_stopped:
            return self.in_flight == 0
        return not (self.labels_host == UNKNOWN).any()

    @property
    def bucket(self) -> Tuple[int, int]:
        """jit-cache key: (pair capacity, object capacity)."""
        return (int(self.state.u.shape[0]), self.state.n_objects)

    def affordable(self, gateway: CrowdGateway) -> Optional[int]:
        """How many more crowd questions the budget buys (None = unlimited)."""
        if self.budget_cents is None or self.per_pair_cents <= 0:
            return None
        rem = self.budget_cents - gateway.spent_cents(self.req.rid)
        return max(int(rem // self.per_pair_cents), 0)


@dataclasses.dataclass
class _EmbeddingStream:
    """Per-request incremental machine phase (DESIGN.md §11): the cached
    scoring index plus the row -> global-object-id maps.  Ids are assigned
    at arrival (the initial corpus keeps the historical a-row i -> i,
    b-row j -> n_a + j layout), so appended rows never collide with ids the
    live session already uses."""

    index: object                  # StreamingCandidateIndex
    truth_fn: Optional[object]     # truth_fn(rows, cols) over global rows
    ids_a: np.ndarray              # (N,) int32 global object id per a-row
    ids_b: np.ndarray              # (M,) int32 global object id per b-row
    next_id: int                   # first unassigned object id


@dataclasses.dataclass
class AdmissionPolicy:
    """Global admission envelope for new submissions (DESIGN.md §16).

    ``max_pending`` caps the submit queue (the QPS envelope: lanes busy AND
    the queue full means the service is saturated — further submits shed
    with :class:`AdmissionError` instead of growing an unbounded backlog).
    ``global_budget_cents`` is a service-wide crowd-spend envelope shared
    by every session: each admitted request reserves its budget against it
    (requests without a budget of their own are clamped to whatever
    remains, reported via ``JoinSessionResult.envelope_clamped``), and a
    submission the exhausted envelope cannot fund at all is shed.
    """

    max_pending: Optional[int] = None
    global_budget_cents: Optional[float] = None


class AdmissionError(RuntimeError):
    """A submission was shed by the admission envelope (DESIGN.md §16):
    the queue is at ``max_pending`` or the global crowd-budget envelope
    has no cents left to reserve.  The request was NOT enqueued; retry
    after sessions finish, or raise the envelope."""


class ServiceKilled(RuntimeError):
    """Injected mid-run crash (recovery tests and the kill/restore
    benchmark stage): raised right after a checkpoint commits when
    ``JoinService._crash_after_checkpoints`` is set, so a run dies at a
    deterministic point with a restorable checkpoint on disk."""


def _bucket(n: int, floor: int = 8) -> int:
    """Next power of two >= n (>= floor) — stable jit cache keys."""
    return next_pow2(n, floor)


def _object_bucket(n_objects: int) -> int:
    """Object capacity of a lane: the power-of-two bucket, except where
    the bucket would need two-word pair keys and ``n_objects`` itself fits
    one word — such a lane keeps one-word keys at its raw size."""
    n_cap = _bucket(n_objects)
    if not pair_keys_fit(n_cap) and pair_keys_fit(n_objects):
        return n_objects
    return n_cap


def _stack_states(states: List[SessionState]) -> SessionState:
    engine_dispatches.add()  # device-side restack of the lane group
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _index_state(stacked: SessionState, b: int) -> SessionState:
    return jax.tree_util.tree_map(lambda x: x[b], stacked)


class JoinService:
    """Accepts streaming join requests; drives frontier -> crowd -> deduce
    over up to ``lanes`` persistent device-resident session states.

    ``latency`` attaches a simulated asynchronous crowd platform (see
    :class:`CrowdGateway`); ``async_mode=True`` switches from round-barrier
    rounds to the event-driven ID/NF discipline; ``nf`` steers the simulated
    workers to probable-non-matching pairs first (requires a latency model —
    immediate-mode steering would be a silent no-op).  ``conflict_policy``
    picks how rejected contradictory answers resolve (DESIGN.md §9):
    ``"drop"`` (oracle semantics — deduced label wins immediately) or
    ``"requery"`` (escalate through the gateway, then trust the graph).

    Adaptive ordering + budget scheduling (DESIGN.md §10): ``order`` is the
    default labeling order for submitted requests (``"adaptive"`` refreshes
    per-pair priorities from the live posterior between rounds);
    ``budget_cents`` / ``cost_per_assignment`` are session defaults — a
    budgeted session stops publishing once its gateway spend exhausts the
    budget and resolves remaining pairs by trusting the graph;
    ``slots_per_round`` caps the crowd questions posted per round-barrier
    round across ALL lanes, allocated by marginal expected-deduction gain.

    Worker quality + cluster tasks (DESIGN.md §15): ``aggregation="em"``
    makes the gateway collapse ballots by reliability-weighted voting (a
    streaming Dawid–Skene :class:`~repro.core.crowd.WorkerModel`) instead
    of naive majority; ``cluster_tasks=True`` lets the scheduler post
    CrowdER-style multi-pair tasks — up to ``cluster_size`` objects
    partitioned by ``cluster_assignments`` distinct workers, agreed
    verdicts landing and disagreements escalating to pair ballots —
    whenever a task's expected correct labels
    per cent beat the pair-task rate.  Cluster tasks compose with budgets,
    the slot allocator and both serving disciplines; the fused megabatch
    path (§13) stands down while they are enabled, since a cluster task's
    harvest set depends on live host-side coverage.

    Example::

        >>> service = JoinService(lanes=2, aggregation="em",
        ...                       cluster_tasks=True, cluster_size=8)
        >>> rid = service.submit(pairs, crowd=NoisyCrowd(n_workers=25))
        >>> result = service.run()[rid]
    """

    def __init__(self, lanes: int = 4, cost: Optional[CostModel] = None,
                 latency: Optional[LatencyModel] = None,
                 async_mode: bool = False, nf: bool = False,
                 conflict_policy: str = "drop", order: str = "expected",
                 budget_cents: Optional[float] = None,
                 cost_per_assignment: Optional[float] = None,
                 slots_per_round: Optional[int] = None,
                 fused_rounds: bool = True,
                 aggregation: str = "majority",
                 cluster_tasks: bool = False, cluster_size: int = 8,
                 cluster_assignments: int = 2,
                 admission: Optional[AdmissionPolicy] = None,
                 cluster_cache=None, cache_path: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1, checkpoint_keep: int = 3):
        if conflict_policy not in ("drop", "requery"):
            raise ValueError(
                f"conflict_policy must be 'drop' or 'requery', "
                f"got {conflict_policy!r}")
        if nf and latency is None:
            raise ValueError(
                "nf=True requires a LatencyModel: non-matching-first steers "
                "worker pickup order, which does not exist in immediate mode")
        validate_order(order)
        if slots_per_round is not None and slots_per_round < 1:
            raise ValueError(
                f"slots_per_round must be positive, got {slots_per_round} — "
                "a zero-slot round could never make progress")
        if aggregation not in ("majority", "em"):
            raise ValueError(
                f"aggregation must be 'majority' or 'em', got "
                f"{aggregation!r}")
        if cluster_size < 3:
            raise ValueError(
                f"cluster_size must be at least 3, got {cluster_size} — a "
                "2-object task is just a pair question at cluster pricing")
        if cluster_assignments < 1:
            raise ValueError(
                f"cluster_assignments must be positive, "
                f"got {cluster_assignments}")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be positive, got {checkpoint_every}"
                " — a non-positive cadence would never checkpoint")
        self.lanes = lanes
        self.cost = cost or CostModel()
        self.latency = latency
        self.async_mode = async_mode
        self.nf = nf
        self.conflict_policy = conflict_policy
        self.order = order
        self.budget_cents = budget_cents
        self.cost_per_assignment = cost_per_assignment
        self.slots_per_round = slots_per_round
        self.aggregation = aggregation
        self.cluster_tasks = cluster_tasks
        self.cluster_size = cluster_size
        self.cluster_assignments = cluster_assignments
        # on-device round engine (DESIGN.md §13): when every active lane's
        # crowd wave can be simulated on device (order-independent answers,
        # immediate transport, no budget/slot caps), one megabatch dispatch
        # advances k rounds across ALL lanes instead of 3+ dispatches/round
        self.fused_rounds = fused_rounds
        self.queue: Deque[JoinRequest] = collections.deque()
        self.results: Dict[int, JoinSessionResult] = {}
        self._next_rid = 0
        # round-barrier group cache: bucket -> (lanes, stacked state).  While
        # a group's membership is unchanged the stacked state IS the lanes'
        # state (no per-round restack/unstack); it is written back to the
        # lanes only when membership changes or a lane finishes.
        self._stacks: Dict[Tuple[int, int],
                           Tuple[Tuple[_Lane, ...], SessionState]] = {}
        # stacked machine priors per group — static per lane between ingests,
        # so the upload happens once per group membership, not once per round
        self._prior_stacks: Dict[Tuple[int, int],
                                 Tuple[Tuple[_Lane, ...], jax.Array]] = {}
        # streaming ingest (DESIGN.md §11): arrival epochs queued per rid,
        # consumed at the lane's next ingest point; interleaved streams
        # release one epoch per engine round instead of all at once
        self._pending_arrivals: Dict[int, Deque[PairSet]] = {}
        self._stream_interleave: Dict[int, bool] = {}
        # incremental machine phase: cached embedding index per streaming rid
        self._streams: Dict[int, "_EmbeddingStream"] = {}
        # admission control (DESIGN.md §16): queue/budget envelope + shed
        # counter; the envelope tracks finalized spend plus the budgets
        # reserved by admitted-but-unfinished requests
        self.admission = admission
        self.n_shed = 0
        self._envelope_spent = 0.0
        self._envelope_reserved = 0.0
        # cross-query cluster cache wired into the service (DESIGN.md §14):
        # submit_embeddings seeds new requests from it and deposits their
        # verdicts back at finalize; with cache_path set the cache persists
        # (atomically) after every deposit and reloads at construction
        if cluster_cache is None and cache_path is not None:
            from repro.plan.cache import ClusterCache
            cluster_cache = (ClusterCache.load(cache_path)
                             if os.path.exists(cache_path) else ClusterCache())
        self.cluster_cache = cluster_cache
        self.cache_path = cache_path
        self._cache_fps: Dict[int, Tuple[List[str], List[str]]] = {}
        # durable serving state (DESIGN.md §16): periodic checkpoints of
        # lanes + gateway + ledgers through train/checkpoint.py; restore()
        # rebuilds the service from the latest one.  _crash_after_checkpoints
        # is the deterministic kill switch the recovery tests/bench use.
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.checkpoint_keep = checkpoint_keep
        self._ckpt = None
        if checkpoint_dir is not None:
            from repro.train.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(checkpoint_dir,
                                           keep=checkpoint_keep)
        self._ckpt_step = 0
        self._ckpt_tick = 0
        self._crash_after_checkpoints: Optional[int] = None
        self._resume: Optional[Tuple[List[_Lane], CrowdGateway]] = None
        self.last_recovery: Optional[dict] = None

    # -- request ingestion ---------------------------------------------------
    def _admit(self, req: JoinRequest) -> int:
        """Single admission gate for every submission path — ``submit``,
        ``submit_embeddings``, and the plan executor (DESIGN.md §14) all
        route through here instead of each carrying its own copy of the
        validation/default plumbing.  Resolves ``None`` fields to the
        service defaults, validates order and seed shape, screens rid
        collisions (an explicit rid colliding with a queued or served
        request is rejected — a silent overwrite would drop the earlier
        result), and enqueues.  Returns the assigned rid.

        Admission control (DESIGN.md §16): with an :class:`AdmissionPolicy`
        attached, a submit that finds the queue at ``max_pending`` or the
        global budget envelope empty is *shed* — counted in ``n_shed`` and
        raised as :class:`AdmissionError` without enqueueing anything.
        Admitted requests reserve their budget against the envelope; a
        request asking for more than remains (or for no cap at all) is
        clamped to the remainder and reports ``envelope_clamped``."""
        with obs.span("join.admit", req.rid):
            remaining = None
            if self.admission is not None:
                pol = self.admission
                if pol.max_pending is not None and \
                        len(self.queue) >= pol.max_pending:
                    self.n_shed += 1
                    raise AdmissionError(
                        f"admission queue full ({len(self.queue)} >= "
                        f"max_pending={pol.max_pending}) — request shed; "
                        "retry after sessions finish")
                if pol.global_budget_cents is not None:
                    remaining = (pol.global_budget_cents - self._envelope_spent
                                 - self._envelope_reserved)
                    if remaining <= 1e-9:
                        self.n_shed += 1
                        raise AdmissionError(
                            "crowd-budget envelope exhausted "
                            f"({pol.global_budget_cents:.2f} cents "
                            "committed) — request shed")
            req.order = validate_order(self.order if req.order is None
                                       else req.order)
            if req.crowd is None:
                req.crowd = PerfectCrowd()
            if req.budget_cents is None:
                req.budget_cents = self.budget_cents
            if req.cost_per_assignment is None:
                req.cost_per_assignment = self.cost_per_assignment
            if req.seed_labels is not None and \
                    len(req.seed_labels) != len(req.pairs):
                raise ValueError(
                    f"seed_labels length {len(req.seed_labels)} != pair count "
                    f"{len(req.pairs)} — seeds are per-pair verdicts in the "
                    "request's pair order")
            if req.rid is None:
                req.rid = self._next_rid
            elif req.rid in self.results or \
                    any(r.rid == req.rid for r in self.queue):
                raise ValueError(
                    f"duplicate join request rid {req.rid}: already "
                    f"{'served' if req.rid in self.results else 'queued'} — "
                    "pick a fresh rid (or omit it for an auto-assigned one)")
            self._next_rid = max(self._next_rid, req.rid) + 1
            obs.set_rid(req.rid)
            if remaining is not None:
                if req.budget_cents is None or req.budget_cents > remaining:
                    req.budget_cents = remaining
                    req.envelope_clamped = True
                self._envelope_reserved += req.budget_cents
            self.queue.append(req)
            return req.rid

    def submit(self, pairs: PairSet, crowd: Optional[Crowd] = None,
               order: Optional[str] = None, rid: Optional[int] = None,
               total_true_matches: Optional[int] = None,
               budget_cents: Optional[float] = None,
               cost_per_assignment: Optional[float] = None,
               seed_labels: Optional[np.ndarray] = None) -> int:
        """Enqueue a join over pre-scored candidate pairs; returns the rid.
        ``order`` / ``budget_cents`` / ``cost_per_assignment`` default to the
        service-level settings when omitted.  ``seed_labels`` warm-starts the
        session from cached cross-query verdicts (DESIGN.md §14)."""
        with obs.span("join.submit", rid):
            rid = self._admit(JoinRequest(
                rid, pairs, crowd, order, total_true_matches,
                budget_cents=budget_cents,
                cost_per_assignment=cost_per_assignment,
                seed_labels=seed_labels))
            obs.set_rid(rid)
        return rid

    @staticmethod
    def _check_candidate_overflow(cand) -> None:
        """Capacity overflow is never silent; the error reports the
        post-growth per-device capacity that provably fits — what a
        streaming caller should re-submit (or keep appending) with."""
        if cand.n_dropped:
            raise RuntimeError(
                f"candidate buffers overflowed: {cand.n_dropped} candidates "
                f"dropped at per-device capacity {cand.capacity} — re-submit "
                f"with capacity={cand.suggested_capacity} (the post-growth "
                "per-device capacity this workload needs) or raise the "
                "threshold")

    def submit_embeddings(self, emb_a: jax.Array, emb_b: jax.Array,
                          threshold: float, mesh,
                          crowd: Optional[Crowd] = None,
                          truth_fn=None, order: Optional[str] = None,
                          capacity: Optional[int] = None,
                          impl: str = "auto",
                          total_true_matches: Optional[int] = None,
                          budget_cents: Optional[float] = None,
                          cost_per_assignment: Optional[float] = None,
                          streaming: bool = False,
                          blocking=None) -> int:
        """Machine phase + enqueue: score (emb_a x emb_b) on the mesh with
        the sharded kernel driver, keep pairs above ``threshold`` (cosine,
        mapped to [0, 1] likelihood), and queue the session.

        ``truth_fn(rows, cols) -> bool array`` attaches ground truth (for
        simulated crowds / quality accounting).  ``capacity`` bounds the
        per-device candidate buffers (default: lossless).  Join keys are
        offset so the two sides share one object universe: a-row i -> i,
        b-row j -> N + j.

        ``total_true_matches`` is the dataset-wide true-match count for
        recall (the paper's §6.4 definition): without it, recall is computed
        against above-threshold candidates only, so a true match the machine
        phase filtered out silently inflates quality.

        ``streaming=True`` keeps the scored corpus cached in a
        :class:`StreamingCandidateIndex` so later
        :meth:`append_embeddings` calls score only the new-vs-corpus and
        new-vs-new blocks (DESIGN.md §11); ``truth_fn`` is retained and must
        then accept global row/col indices into the grown corpora.

        ``blocking`` (a :class:`BlockingConfig`, DESIGN.md §12) puts the
        LSH blocking stage in front of the scorer: only bucket-colliding
        pairs are scored, through the fused compaction kernel — the blocked
        path runs on the local device (``mesh`` is ignored), and with
        ``streaming=True`` later arrivals hash into the existing buckets so
        only touched buckets rescore.  Blocking trades recall at the
        threshold boundary for scored cells; size the config with
        ``BlockingConfig.for_recall``.
        """
        from repro.kernels.pair_scores.blocking import blocked_candidates
        from repro.kernels.pair_scores.sharded import (
            StreamingCandidateIndex, sharded_candidates)

        with obs.span("join.submit"):
            with obs.span("join.machine"):
                if streaming:
                    index = StreamingCandidateIndex(threshold, mesh,
                                                    capacity=capacity,
                                                    impl=impl,
                                                    blocking=blocking)
                    cand = index.append(emb_a, emb_b)
                    if cand.n_dropped:
                        # reject atomically BEFORE surfacing the overflow: a
                        # raise that left the partially-compacted epoch in
                        # the index would make a retry at suggested_capacity
                        # score the corpus as "already seen" and return no
                        # candidates at all
                        index.rollback_append()
                elif blocking is not None:
                    cand = blocked_candidates(emb_a, emb_b, threshold,
                                              config=blocking,
                                              capacity=capacity, impl=impl)
                else:
                    cand = sharded_candidates(emb_a, emb_b, threshold, mesh,
                                              capacity=capacity, impl=impl)
                self._check_candidate_overflow(cand)
            n_a = int(emb_a.shape[0])
            n_b = int(emb_b.shape[0])
            truth = None
            if truth_fn is not None:
                truth = np.asarray(truth_fn(cand.rows, cand.cols), bool)
            pairs = PairSet(
                u=cand.rows,
                v=cand.cols + n_a,
                likelihood=(cand.scores + 1.0) / 2.0,
                truth=truth,
                n_objects=n_a + n_b,
            )
            seed_labels = None
            fps = None
            if self.cluster_cache is not None:
                # auto seed/deposit wiring (DESIGN.md §14/§16): fingerprint
                # the candidate rows, warm-start from cached cross-query
                # verdicts, and remember the fingerprints so _finalize can
                # deposit this request's verdicts back.  An all-UNKNOWN seed
                # is harmless — lane open skips the seed fold when nothing
                # is known.
                from repro.plan.algebra import row_fingerprints
                fa = row_fingerprints(obs.to_host(emb_a))
                fb = row_fingerprints(obs.to_host(emb_b))
                fps = ([fa[int(i)] for i in np.asarray(cand.rows)],
                       [fb[int(j)] for j in np.asarray(cand.cols)])
                seed_labels = self.cluster_cache.seed(fps[0], fps[1])
            rid = self._admit(JoinRequest(
                None, pairs, crowd, order, total_true_matches,
                budget_cents=budget_cents,
                cost_per_assignment=cost_per_assignment,
                seed_labels=seed_labels))
            obs.set_rid(rid)
            if fps is not None:
                self._cache_fps[rid] = fps
            if streaming:
                self._streams[rid] = _EmbeddingStream(
                    index=index, truth_fn=truth_fn,
                    ids_a=np.arange(n_a, dtype=np.int32),
                    ids_b=np.arange(n_a, n_a + n_b, dtype=np.int32),
                    next_id=n_a + n_b)
            return rid

    # -- streaming ingest (DESIGN.md §11) ------------------------------------
    def append(self, rid: int, pairs: PairSet) -> None:
        """Queue an arrival epoch for an open streaming request: the pairs
        (ids in the request's shared object universe; new ids allowed) are
        folded into the live lane at its next ingest point — the session
        grows in place, in-flight crowd work and budget accounting carry
        over untouched.  Empty epochs are a no-op."""
        if rid in self.results:
            raise ValueError(
                f"cannot append to rid {rid}: the request already finished "
                "— submit the new pairs as a fresh request")
        if not any(r.rid == rid for r in self.queue) and \
                rid not in self._pending_arrivals:
            raise ValueError(f"cannot append to unknown rid {rid}")
        if len(pairs) == 0:
            return
        self._pending_arrivals.setdefault(rid,
                                          collections.deque()).append(pairs)

    def submit_stream(self, epochs, crowd: Optional[Crowd] = None,
                      order: Optional[str] = None, rid: Optional[int] = None,
                      total_true_matches: Optional[int] = None,
                      budget_cents: Optional[float] = None,
                      cost_per_assignment: Optional[float] = None,
                      interleave: bool = False) -> int:
        """Enqueue a join whose candidate pairs arrive over k epochs
        (DESIGN.md §11).  The first epoch opens the request; the rest are
        queued as arrivals.  With the default up-front schedule every epoch
        is ingested before labeling begins, and the grown session state is
        bit-identical to one built from the concatenated pairs — so the run
        matches a single-shot :meth:`submit` of the concatenation
        label-for-label, root-for-root, and crowdsourced-pair-for-pair.
        ``interleave=True`` instead releases one epoch per engine round, so
        arrivals land while earlier answers are still in flight (counts may
        then differ from the batch run — the labeling schedule differs — but
        labels stay exact and budgets/tickets carry over)."""
        epochs = list(epochs)
        if not epochs:
            raise ValueError("submit_stream needs at least one epoch")
        rid = self.submit(epochs[0], crowd, order, rid, total_true_matches,
                          budget_cents=budget_cents,
                          cost_per_assignment=cost_per_assignment)
        self._stream_interleave[rid] = interleave
        for epoch in epochs[1:]:
            self.append(rid, epoch)
        return rid

    def append_embeddings(self, rid: int,
                          new_a: Optional[jax.Array] = None,
                          new_b: Optional[jax.Array] = None) -> None:
        """Incremental machine phase + append: score the arriving rows
        against the cached corpus (new-vs-corpus and new-vs-new blocks
        only), assign the new rows fresh object ids, and queue the resulting
        candidate pairs as an arrival epoch for ``rid`` (which must have
        been submitted with ``streaming=True``)."""
        stream = self._streams.get(rid)
        if stream is None:
            raise ValueError(
                f"rid {rid} has no cached embedding index — submit it with "
                "submit_embeddings(..., streaming=True)")
        cand = stream.index.append(new_a, new_b)
        if cand.n_dropped:
            # reject the epoch atomically: the index must forget rows whose
            # candidates were never ingested, or the stream's row -> id maps
            # desync and every later epoch skips the ghost rows
            stream.index.rollback_append()
            raise RuntimeError(
                f"candidate buffers overflowed: {cand.n_dropped} candidates "
                f"dropped at per-device capacity {cand.capacity} — the "
                "epoch was rolled back (the stream stays usable); re-submit "
                f"the request with capacity={cand.suggested_capacity} (the "
                "post-growth per-device capacity this workload needs) or "
                "split the arrival into smaller epochs")
        if new_a is not None and len(new_a):
            fresh = np.arange(stream.next_id, stream.next_id + len(new_a),
                              dtype=np.int32)
            stream.ids_a = np.concatenate([stream.ids_a, fresh])
            stream.next_id += len(new_a)
        if new_b is not None and len(new_b):
            fresh = np.arange(stream.next_id, stream.next_id + len(new_b),
                              dtype=np.int32)
            stream.ids_b = np.concatenate([stream.ids_b, fresh])
            stream.next_id += len(new_b)
        truth = None
        if stream.truth_fn is not None:
            truth = np.asarray(stream.truth_fn(cand.rows, cand.cols), bool)
        self.append(rid, PairSet(
            u=stream.ids_a[cand.rows],
            v=stream.ids_b[cand.cols],
            likelihood=(cand.scores + 1.0) / 2.0,
            truth=truth,
            n_objects=stream.next_id,
        ))

    # -- lane lifecycle ------------------------------------------------------
    def _open_lane(self, req: JoinRequest) -> _Lane:
        with obs.span("join.open_lane", req.rid):
            perm = get_order(req.pairs, req.order)
            ordered = req.pairs.take(perm)
            P = len(ordered)
            p_cap = _bucket(P)
            n_cap = _object_bucket(ordered.n_objects)
            if not pair_keys_fit(n_cap):
                obs.wide_key_lanes.add()
            state = make_session_state(ordered.u, ordered.v,
                                       ordered.n_objects, pair_capacity=p_cap,
                                       object_capacity=n_cap)
            labels_host = np.full(P, UNKNOWN, np.int32)
            n_cache_hits = 0
            if req.seed_labels is not None:
                # cross-query warm start (DESIGN.md §14): fold cached cluster
                # verdicts before the first frontier, so seeded pairs (and
                # whatever deduction reaches from them) never get crowdsourced.
                # Seeds are never posted to the gateway — spend excludes them.
                seeds = np.full(p_cap, UNKNOWN, np.int32)
                seeds[:P] = np.asarray(req.seed_labels, np.int32)[perm]
                if (seeds != UNKNOWN).any():
                    engine_dispatches.add()  # seed upload
                    state, cmask = session_seed_labels(state,
                                                       jnp.asarray(seeds))
                    n_cache_hits = int(((seeds[:P] != UNKNOWN)
                                        & ~obs.to_host(cmask)[:P]).sum())
                    labels_host = obs.to_host(state.labels)[:P]
            prior_host = np.zeros(p_cap, np.float32)
            prior_host[:P] = ordered.likelihood
            rate = (req.cost_per_assignment
                    if req.cost_per_assignment is not None
                    else self.cost.cents_per_assignment)
            engine_dispatches.add()  # prior upload
            return _Lane(
                req=req,
                perm=perm,
                ordered=ordered,
                p=P,
                state=state,
                labels_host=labels_host,
                n_cache_hits=n_cache_hits,
                crowdsourced=np.zeros(P, bool),
                round_sizes=[],
                prior_host=prior_host,
                prior_dev=jnp.asarray(prior_host),
                adaptive=req.order == "adaptive",
                rate_cents=float(rate),
                per_pair_cents=float(rate)
                * getattr(req.crowd, "n_assignments", 1),
                budget_cents=req.budget_cents,
                answers_host=req.crowd.precomputed_answers(ordered),
                inflight_host=np.zeros(p_cap, bool),
            )

    # -- lane growth (DESIGN.md §11) -----------------------------------------
    def _flush_stacks(self) -> None:
        """Materialize every cached group stack back into its lanes and drop
        the caches — lane states must be authoritative before any lane grows
        (growth changes a lane's bucket, so its old group is stale)."""
        for entry in self._stacks.values():
            self._writeback(entry)
        self._stacks.clear()
        self._prior_stacks.clear()

    def _ingest(self, lane: _Lane, new_pairs: PairSet) -> None:
        """Fold an arrival epoch into a live lane: grow the device state to
        the new capacity bucket (``_object_bucket``: a universe that grows
        past one-word pair keys re-encodes its neg-key index in two words),
        claim padded slots for the new pairs,
        and refresh the priority layout.  Published bits, gateway tickets,
        spend accounting, and every already-labeled pair carry over
        untouched — existing pair slots never move."""
        req = lane.req
        offset = lane.p
        perm_new = get_order(new_pairs, req.order)
        ordered_new = new_pairs.take(perm_new)
        req.pairs = req.pairs.concat(new_pairs)
        lane.perm = np.concatenate([lane.perm, offset + perm_new])
        lane.ordered = lane.ordered.concat(ordered_new)
        new_p = offset + len(new_pairs)
        p_cap = max(int(lane.state.u.shape[0]), _bucket(new_p))
        n_cap = lane.state.n_objects
        if lane.ordered.n_objects > n_cap:
            n_cap = _object_bucket(lane.ordered.n_objects)
        if (p_cap, n_cap) != (int(lane.state.u.shape[0]),
                              lane.state.n_objects):
            lane.state = session_grow(lane.state, p_cap, n_cap)
        new_u = np.zeros(p_cap, np.int32)
        new_v = np.zeros(p_cap, np.int32)
        mask = np.zeros(p_cap, bool)
        new_u[offset:new_p] = ordered_new.u
        new_v[offset:new_p] = ordered_new.v
        mask[offset:new_p] = True
        engine_dispatches.add()  # appended-pairs upload
        lane.state = session_append_pairs(lane.state, new_u, new_v, mask)
        # merged expected-rank priorities: a likelihood-ranked lane must key
        # selection on the pair's rank in the FULL accumulated candidate
        # set, not its arrival position — this is what makes the up-front
        # stream schedule reproduce the batch run's frontier exactly.
        # (Padded slots rank after every real pair; frozen pairs' values are
        # irrelevant to selection, which only compares pending ranks.)
        if req.order in ("expected", "adaptive"):
            lik = lane.ordered.likelihood
            rank = np.empty(new_p, np.float32)
            rank[np.argsort(-lik, kind="stable")] = np.arange(
                new_p, dtype=np.float32)
            prio = np.concatenate(
                [rank, np.arange(new_p, p_cap, dtype=np.float32)])
            engine_dispatches.add()  # priority upload
            lane.state = dataclasses.replace(lane.state,
                                             priority=jnp.asarray(prio))
        prior_host = np.zeros(p_cap, np.float32)
        prior_host[:new_p] = lane.ordered.likelihood
        lane.prior_host = prior_host
        engine_dispatches.add()  # prior re-upload
        lane.prior_dev = jnp.asarray(prior_host)
        lane.labels_host = np.concatenate(
            [lane.labels_host,
             np.full(len(new_pairs), UNKNOWN, np.int32)])
        lane.crowdsourced = np.concatenate(
            [lane.crowdsourced, np.zeros(len(new_pairs), bool)])
        inflight = np.zeros(p_cap, bool)
        inflight[:len(lane.inflight_host)] = lane.inflight_host
        lane.inflight_host = inflight
        lane.p = new_p
        lane.answers_host = req.crowd.precomputed_answers(lane.ordered)

    def _ingest_pending(self, lane: _Lane) -> bool:
        """Consume queued arrival epochs for this lane — all of them for the
        default up-front schedule, one per call for an interleaved stream.
        Ends with a deduce sweep so arrivals the accumulated evidence
        already pins down never wedge a frontier-empty round.  (A
        budget-stopped lane still ingests: its arrivals resolve the same
        trust-the-graph way as the pairs the budget ran out on.)"""
        pending = self._pending_arrivals.get(lane.req.rid)
        if not pending:
            return False
        n = 1 if self._stream_interleave.get(lane.req.rid) else len(pending)
        for _ in range(n):
            self._ingest(lane, pending.popleft())
        if not pending:
            del self._pending_arrivals[lane.req.rid]
        self._sweep_lane(lane)
        return True

    def _finalize(self, lane: _Lane, sim_minutes: Optional[float],
                  gateway: Optional[CrowdGateway]) -> None:
        req = lane.req
        with obs.span("join.finalize", req.rid):
            P = len(req.pairs)
            labels = np.zeros(P, bool)
            crowdsourced = np.zeros(P, bool)
            labels[lane.perm] = lane.labels_host == POS
            crowdsourced[lane.perm] = lane.crowdsourced
            q = None
            if req.pairs.truth is not None:
                ttm = req.total_true_matches
                if ttm is None:
                    ttm = int(req.pairs.truth.sum())
                q = quality(req.pairs, labels, ttm)
            n_crowd = int(crowdsourced.sum())
            self.results[req.rid] = res = JoinSessionResult(
                rid=req.rid,
                labels=labels,
                crowdsourced=crowdsourced,
                n_rounds=len(lane.round_sizes),
                round_sizes=lane.round_sizes,
                n_hits=self.cost.n_hits(n_crowd),
                cost_cents=self.cost.cost_cents(n_crowd),
                quality=q,
                sim_minutes=sim_minutes,
                fold_rounds=int(obs.to_host(lane.state.rounds)),
                n_conflicts=int(
                    obs.to_host(lane.state.conflicts)[:lane.p].sum()),
                n_requeried=lane.n_requeried,
                n_spent_cents=gateway.spent_cents(req.rid) if gateway else 0.0,
                stopped_on_budget=lane.budget_stopped,
                n_cache_hits=lane.n_cache_hits,
                n_cluster_tasks=lane.n_cluster_tasks,
                n_cluster_pairs=(gateway.cluster_pairs(req.rid) if gateway
                                 else 0),
                n_cluster_cents=lane.n_cluster_cents,
                admission_deferred=req.admission_deferred,
                envelope_clamped=req.envelope_clamped,
            )
            # cross-query deposit (DESIGN.md §14/§16): hand the finished
            # session's verdicts to the cluster cache under the fingerprints
            # recorded at submit, then persist atomically.  UNKNOWN verdicts
            # (budget-stopped pairs) deposit nothing; pairs appended after
            # submit have no fingerprints and are sliced off.
            fps = self._cache_fps.pop(req.rid, None)
            if fps is not None and self.cluster_cache is not None:
                verdicts = np.full(P, UNKNOWN, np.int32)
                verdicts[lane.perm] = lane.labels_host
                self.cluster_cache.deposit(fps[0], fps[1],
                                           verdicts[: len(fps[0])])
                if self.cache_path is not None:
                    self.cluster_cache.save(self.cache_path)
            # admission envelope (DESIGN.md §16): the reservation made at admit
            # converts into realized spend — the difference returns to the pool
            if self.admission is not None and \
                    self.admission.global_budget_cents is not None:
                self._envelope_reserved = max(
                    0.0, self._envelope_reserved - (req.budget_cents or 0.0))
                self._envelope_spent += res.n_spent_cents
            self._streams.pop(req.rid, None)
            self._stream_interleave.pop(req.rid, None)

    def _retire_done(self, active: List[_Lane],
                     gateway: Optional[CrowdGateway]) -> List[_Lane]:
        still: List[_Lane] = []
        sim = gateway.now_minutes if self.latency is not None else None
        for lane in active:
            # a lane with arrival epochs still queued is not finished, even
            # when every pair it has seen so far is labeled
            if lane.done and not self._pending_arrivals.get(lane.req.rid):
                self._finalize(lane, sim, gateway)
            else:
                still.append(lane)
        return still

    # -- round-barrier engine ------------------------------------------------
    def _writeback(self, entry: Tuple[Tuple[_Lane, ...], SessionState]) -> None:
        """Materialize a cached group's stacked state back into its lanes."""
        lanes, stacked = entry
        engine_dispatches.add()  # per-lane gathers out of the stack
        for b, lane in enumerate(lanes):
            lane.state = _index_state(stacked, b)

    def _group_stack(self, key: Tuple[int, int],
                     lanes: List[_Lane]) -> SessionState:
        """The group's stacked state: reused as long as membership holds."""
        entry = self._stacks.get(key)
        if entry is not None:
            # identity comparison: _Lane holds arrays, dataclass __eq__ would
            # compare them elementwise
            if len(entry[0]) == len(lanes) and \
                    all(a is b for a, b in zip(entry[0], lanes)):
                return entry[1]
            self._writeback(entry)  # membership changed: sync old members
            del self._stacks[key]
        return _stack_states([l.state for l in lanes])

    def _group_priors(self, key: Tuple[int, int],
                      lanes: List[_Lane]) -> jax.Array:
        """The group's stacked (B, P) machine priors, uploaded once per
        membership (the priors never change after lane open)."""
        entry = self._prior_stacks.get(key)
        if entry is not None and len(entry[0]) == len(lanes) and \
                all(a is b for a, b in zip(entry[0], lanes)):
            return entry[1]
        engine_dispatches.add()  # priors upload
        priors = jnp.asarray(np.stack([l.prior_host for l in lanes]))
        self._prior_stacks[key] = (tuple(lanes), priors)
        return priors

    def _allocate(self, staged, gateway: CrowdGateway):
        """Budget-aware slot allocation (DESIGN.md §10): given each group's
        frontier, decide which pairs actually post this round.  With no
        budgeted lane and no ``slots_per_round`` cap the whole frontier
        posts (no extra dispatches).  Otherwise every frontier pair is
        scored by its marginal expected-deduction gain (one batched gains
        dispatch per group), each budgeted lane is capped at what its
        remaining budget affords, and the global ``slots_per_round`` cap
        keeps the highest-gain pairs across ALL lanes.  Mutates each
        stage's mask in place to the posted set; returns the lanes whose
        budget affords nothing more (to be budget-stopped after the fold)."""
        stops: List[_Lane] = []
        constrained = self.slots_per_round is not None or any(
            lane.budget_cents is not None
            for _, lanes, _, _ in staged for lane in lanes)
        if not constrained:
            return stops
        cands = []  # (-gain, stage index, lane index, pair index)
        for si, (key, lanes, stacked, frontier) in enumerate(staged):
            if not frontier.any():
                continue
            if all(lane.adaptive for lane in lanes):
                # the refresh already wrote -gain into every pending pair's
                # priority, and the frontier only selects pending pairs —
                # read it back instead of paying a second gains dispatch
                gains = -obs.to_host(stacked.priority)
            else:
                gains = obs.to_host(session_gains_batch(
                    stacked, self._group_priors(key, lanes)))
            for b, lane in enumerate(lanes):
                idx = np.nonzero(frontier[b])[0]
                if len(idx) == 0:
                    continue
                afford = lane.affordable(gateway)
                if afford == 0:
                    stops.append(lane)
                    continue
                if afford is not None and afford < len(idx):
                    # keep the highest-gain affordable questions
                    idx = idx[np.argsort(-gains[b, idx],
                                         kind="stable")][:afford]
                cands.extend((-float(gains[b, i]), si, b, int(i))
                             for i in idx)
        cands.sort()
        if self.slots_per_round is not None:
            cands = cands[: self.slots_per_round]
        for stage in staged:
            stage[3] = np.zeros_like(stage[3])
        for _, si, b, i in cands:
            staged[si][3][b, i] = True
        return stops

    def _budget_stop(self, lane: _Lane) -> None:
        """Out of budget: pull every still-unlabeled unpublished pair out of
        contention and let deduction label what the graph already pins down
        (``session_trust_graph``); the rest stay UNKNOWN and finalize as
        non-matching.  One dispatch."""
        mask = obs.to_host(lane.state.labels) == UNKNOWN
        mask &= ~obs.to_host(lane.state.published)
        engine_dispatches.add()  # mask upload
        lane.state = session_trust_graph(lane.state, jnp.asarray(mask))
        lane.labels_host = obs.to_host(lane.state.labels)[:lane.p]
        lane.budget_stopped = True

    # -- cluster-task scheduling (DESIGN.md §15) -----------------------------
    def _task_info(self, lane: _Lane,
                   gateway: CrowdGateway) -> Tuple[float, float]:
        """Accuracy inputs of the §15 information-per-cent rule: the
        expected accuracy of an *agreed* cluster verdict (the reliability
        model's best-known worker error when EM aggregation has history,
        else the crowd's base rate, raised to the ``cluster_assignments``
        agreement power — all partitioning workers must coherently err for
        a wrong verdict to land) and the expected correct labels per cent
        of a pair task (majority-vote accuracy over ``n_assignments``
        votes)."""
        crowd = lane.req.crowd
        k = getattr(crowd, "n_assignments", 1)
        pair_cents = max(lane.rate_cents * k, 1e-9)
        try:
            acc_pair = 1.0 - crowd.pair_error_rate()
        except AttributeError:
            acc_pair = 1.0
        wm = gateway.worker_model
        best = wm.best_workers(limit=1) if wm is not None else []
        if best:
            err_one = wm.error_rate(best[0])
        else:
            err_one = min(getattr(crowd, "error_rate", 0.0), 0.5)
        acc_task = 1.0 - err_one ** self.cluster_assignments
        return acc_task, acc_pair / pair_cents

    def _plan_tasks(self, lane: _Lane, idx: np.ndarray,
                    gateway: CrowdGateway):
        """Split a lane's allocated frontier into cluster tasks and leftover
        pair tasks (DESIGN.md §15).  Around each frontier pair, greedily
        grow an object set (up to ``cluster_size``) that maximizes covered
        *frontier* pairs — the questions the engine actually scheduled this
        round; every other pending pair inside the set rides along as free
        harvest (the CrowdER effect: a partition answers all its internal
        pairs at one task price).  The task posts iff its expected correct
        scheduled labels per cent, ``acc_one * frontier_covered /
        task_cents``, beats the pair-task rate ``acc_pair / pair_cents``
        (and, for budgeted lanes, the remaining budget affords it) —
        valuing only frontier coverage keeps the scheduler honest about
        transitivity: harvested pairs deduction would have labeled for free
        are not counted as value.  Returns ``(clusters, pair_idx)`` where
        clusters is a list of ``(n_objects, covered_indices)``."""
        idx = np.asarray(idx, int)
        if not self.cluster_tasks or len(idx) == 0:
            return [], idx
        p = lane.p
        pending = lane.labels_host == UNKNOWN
        pending &= ~lane.inflight_host[:p]
        u = np.asarray(lane.ordered.u)
        v = np.asarray(lane.ordered.v)
        acc_one, pair_info = self._task_info(lane, gateway)
        is_frontier = np.zeros(p, bool)
        is_frontier[idx] = True
        nbr: Dict[int, List[int]] = {}
        for j in np.nonzero(pending)[0]:
            nbr.setdefault(int(u[j]), []).append(int(j))
            nbr.setdefault(int(v[j]), []).append(int(j))
        taken = np.zeros(p, bool)
        budget = lane.budget_cents
        spent = gateway.spent_cents(lane.req.rid) if budget is not None \
            else 0.0
        planned = 0.0
        clusters: List[Tuple[int, np.ndarray]] = []
        pair_idx: List[int] = []
        for j in (int(i) for i in idx):
            if taken[j]:
                continue  # harvested by an earlier cluster this round
            objs = {int(u[j]), int(v[j])}
            while len(objs) < self.cluster_size:
                # gain = (frontier pairs, pending pairs) object o would add
                gain: Dict[int, List[int]] = {}
                for o in objs:
                    for q in nbr.get(o, ()):
                        if taken[q]:
                            continue
                        other = int(v[q]) if int(u[q]) == o else int(u[q])
                        if other not in objs:
                            g = gain.setdefault(other, [0, 0])
                            g[0] += int(is_frontier[q])
                            g[1] += 1
                if not gain:
                    break
                best = max(gain.items(),
                           key=lambda kv: (kv[1][0], kv[1][1], -kv[0]))
                if best[1][0] == 0 and len(objs) >= 3:
                    # no scheduled question left to batch: stop growing so
                    # the task price stays matched to its frontier value
                    break
                objs.add(best[0])
            cov = sorted({q for o in objs for q in nbr.get(o, ())
                          if not taken[q]
                          and int(u[q]) in objs and int(v[q]) in objs})
            fcov = int(sum(is_frontier[q] for q in cov))
            cents = (self.cost.cluster_task_cents(len(objs), lane.rate_cents)
                     * self.cluster_assignments)
            ok = (acc_one * fcov / max(cents, 1e-9) >= pair_info
                  and (budget is None
                       or spent + planned + cents <= budget + 1e-9))
            if ok:
                cov = np.asarray(cov, int)
                taken[cov] = True
                planned += cents
                clusters.append((len(objs), cov))
            else:
                pair_idx.append(j)
        return clusters, np.asarray(pair_idx, int)

    def _post_lane(self, lane: _Lane, clusters, pair_idx: np.ndarray,
                   gateway: CrowdGateway) -> int:
        """Post one lane's planned round: every cluster task, then the
        leftover pair batch.  Marks coverage (``crowdsourced``,
        ``inflight_host``) and bills cluster tasks at their §15 task price.
        Returns the total pairs posted."""
        total = 0
        for n_objects, cov in clusters:
            lane.crowdsourced[cov] = True
            lane.inflight_host[cov] = True
            cents = (self.cost.cluster_task_cents(n_objects, lane.rate_cents)
                     * self.cluster_assignments)
            with obs.span("join.gateway.post", lane.req.rid):
                gateway.post_cluster(
                    lane.req.rid, lane.ordered, cov, lane.req.crowd,
                    cents=cents, n_assignments=self.cluster_assignments,
                    pair_cents_per_assignment=lane.rate_cents)
            lane.n_cluster_tasks += 1
            lane.n_cluster_cents += cents
            total += len(cov)
        if len(pair_idx):
            lane.crowdsourced[pair_idx] = True
            lane.inflight_host[pair_idx] = True
            with obs.span("join.gateway.post", lane.req.rid):
                gateway.post(lane.req.rid, lane.ordered, pair_idx,
                             lane.req.crowd,
                             cents_per_assignment=lane.rate_cents)
            total += len(pair_idx)
        return total

    # -- on-device round engine (DESIGN.md §13) ------------------------------
    # rounds folded per megabatch dispatch; static so every wave shares one
    # jit cache entry per capacity bucket
    FUSED_ROUNDS_PER_DISPATCH = 8

    def _fused_eligible(self, lane: _Lane) -> bool:
        """True when this lane's next crowd wave can be simulated entirely on
        device: answers must be order-independent (``answers_host``), the
        transport immediate (a latency model makes answer arrival part of
        the semantics), budgets/slot caps unconstrained (they re-decide per
        round on host), no arrival epochs pending (they grow the state
        mid-wave), no prior §9 conflict on this lane (the exact replay
        is host-driven), and cluster tasks disabled — a cluster task's
        harvest set depends on live host-side coverage (§15), which the
        device wave cannot consult, so mixed scheduling falls back to the
        exact per-round paths."""
        return (self.fused_rounds
                and not self.cluster_tasks
                and self.latency is None
                and self.slots_per_round is None
                and lane.budget_cents is None
                and not lane.budget_stopped
                and lane.fused_ok
                and lane.answers_host is not None
                and not self._pending_arrivals.get(lane.req.rid))

    def _drive_fused(self, active: List[_Lane],
                     gateway: CrowdGateway) -> bool:
        """Advance every active lane a whole crowd wave with amortized <1
        dispatch per round: grow the lanes to one shared capacity bucket,
        stack them into a cross-lane megabatch, and loop
        ``session_run_rounds_batch`` (k rounds per dispatch) until no lane
        is mid-stream.  Gateway traffic — billing, ``n_asked``, tickets —
        is replayed after the device rounds: answers are order-independent,
        so posting the crowdsourced pairs late produces the identical
        ledger the per-round path would have.  A lane whose §9 screen fires
        exits pre-fold with ``fused_ok=False`` (nothing posted for the
        conflicted round) and re-runs it through the exact legacy path.
        Returns True iff any lane made progress."""
        with obs.span("join.drive_fused"):
            self._flush_stacks()
            p_cap = max(int(l.state.u.shape[0]) for l in active)
            n_cap = max(l.state.n_objects for l in active)
            for lane in active:
                if (int(lane.state.u.shape[0]),
                        lane.state.n_objects) != (p_cap, n_cap):
                    lane.state = session_grow(lane.state, p_cap, n_cap)
            B = len(active)
            stacked = _stack_states([l.state for l in active])
            answers = np.full((B, p_cap), UNKNOWN, np.int32)
            priors = np.zeros((B, p_cap), np.float32)
            for b, lane in enumerate(active):
                answers[b, :lane.p] = lane.answers_host[:lane.p]
                priors[b, :len(lane.prior_host)] = lane.prior_host
            engine_dispatches.add(2)  # answers + priors upload
            answers_dev = jnp.asarray(answers)
            priors_dev = jnp.asarray(priors)
            adaptive = np.array([l.adaptive for l in active])
            K = self.FUSED_ROUNDS_PER_DISPATCH
            progress = False
            running = True
            while running:
                with obs.span("join.engine_dispatch"):
                    stacked, crowd_new, sizes, rdone, codes = \
                        session_run_rounds_batch(stacked, answers_dev, K,
                                                 prior=priors_dev,
                                                 adaptive=adaptive)
                    crowd_new = obs.to_host(crowd_new)
                    sizes = obs.to_host(sizes)
                    rdone = obs.to_host(rdone)
                    codes = obs.to_host(codes)
                    labels = obs.to_host(stacked.labels)
                running = False
                stuck: List[int] = []
                for b, lane in enumerate(active):
                    for r in range(int(rdone[b])):
                        lane.round_sizes.append(int(sizes[b, r]))
                    idx = np.nonzero(crowd_new[b, :lane.p])[0]
                    if len(idx):
                        # replay the wave's gateway traffic: per-pair billing
                        # and ask bookkeeping are order-independent, so one
                        # post covers the rounds just simulated
                        lane.crowdsourced[idx] = True
                        with obs.span("join.gateway.post", lane.req.rid):
                            gateway.post(lane.req.rid, lane.ordered, idx,
                                         lane.req.crowd,
                                         cents_per_assignment=lane.rate_cents)
                        progress = True
                    new = labels[b, :lane.p]
                    progress |= bool((new != lane.labels_host).any())
                    lane.labels_host = new
                    code = int(codes[b])
                    if code == ROUNDS_CONFLICT:
                        lane.fused_ok = False
                    elif (new == UNKNOWN).any():
                        if code == ROUNDS_EMPTY:
                            stuck.append(lane.req.rid)
                        else:  # ROUNDS_RUNNING: wave continues next dispatch
                            running = True
                # consume the replayed posts (immediate mode)
                with obs.span("join.gateway.drain"):
                    gateway.drain()
                if stuck:
                    raise RuntimeError(
                        "join engine stuck: no frontier and nothing deducible "
                        f"for rids {stuck}")
            engine_dispatches.add()  # per-lane gathers out of the stack
            for b, lane in enumerate(active):
                lane.state = _index_state(stacked, b)
            return progress

    def _step(self, active: List[_Lane], gateway: CrowdGateway) -> bool:
        """One engine round over the occupied lanes: an optional batched
        priority refresh (adaptive lanes), batched frontier over
        bucket-grouped stacked states, budget/slot allocation, one gateway
        post per lane, a full gateway drain (the round barrier), one fused
        apply+deduce dispatch.  Under ``conflict_policy="requery"`` the
        round keeps draining and folding until every rejected answer has
        been escalated to resolution (re-answered clean, or exhausted and
        trusted to the graph).  Returns True iff any lane made progress
        (crowdsourced, deduced, or budget-stopped at least one pair)."""
        with obs.span("join.step"):
            requery = self.conflict_policy == "requery"
            groups: Dict[Tuple[int, int], List[_Lane]] = {}
            for lane in active:
                groups.setdefault(lane.bucket, []).append(lane)
            staged = []
            for key, lanes in groups.items():
                stacked = self._group_stack(key, lanes)
                if any(lane.adaptive for lane in lanes):
                    # fold posterior-refreshed priorities into the live states
                    # before selection (DESIGN.md §10), one dispatch per group
                    engine_dispatches.add()
                    stacked = session_refresh_priorities_batch(
                        stacked, self._group_priors(key, lanes),
                        np.array([l.adaptive for l in lanes]))
                frontier = obs.to_host(session_frontier_batch(stacked))
                if self.cluster_tasks:
                    # the harvest planner widens the posted mask in place
                    frontier = np.array(frontier)
                staged.append([key, lanes, stacked, frontier])
            budget_stops = self._allocate(staged, gateway)
            # cluster-task planning (DESIGN.md §15): split each lane's
            # allocated frontier into cluster harvests + leftover pairs, and
            # widen the posted mask with the harvested extras so the publish
            # below gates deduction off every pair with an answer inbound
            plans: Dict[Tuple[int, int], Tuple[list, np.ndarray]] = {}
            for si, stage in enumerate(staged):
                _, lanes, _, posted = stage
                for b, lane in enumerate(lanes):
                    idx = np.nonzero(posted[b])[0]
                    if len(idx) == 0:
                        continue
                    clusters, pair_idx = self._plan_tasks(lane, idx, gateway)
                    plans[(si, b)] = (clusters, pair_idx)
                    for _, cov in clusters:
                        posted[b, cov] = True
            for stage in staged:
                key, lanes, stacked, posted = stage
                if requery and posted.any():
                    # published bits gate the fused deduce off still-contested
                    # pairs, so a rejected answer can wait for its escalation
                    engine_dispatches.add()  # posted-mask upload
                    stacked = session_mark_published_batch(
                        stacked, jnp.asarray(posted))
                    stage[2] = stacked
            # post every lane's allocation, then drain: the barrier spans lanes
            for si, (_, lanes, _, posted) in enumerate(staged):
                for b, lane in enumerate(lanes):
                    plan = plans.get((si, b))
                    if plan is None:
                        continue
                    n = self._post_lane(lane, plan[0], plan[1], gateway)
                    if n:
                        lane.round_sizes.append(n)
            # fold/escalate until no group has a conflict awaiting an answer
            pending = True
            while pending:
                pending = False
                answers: Dict[int, List] = {}
                with obs.span("join.gateway.drain"):
                    drained = gateway.drain()
                for ans in drained:
                    answers.setdefault(ans.rid, []).append(ans)
                for stage in staged:
                    key, lanes, stacked, frontier = stage
                    B, p_cap = frontier.shape
                    updates = np.full((B, p_cap), UNKNOWN, np.int32)
                    landed = False
                    for b, lane in enumerate(lanes):
                        for ans in answers.get(lane.req.rid, ()):
                            updates[b, ans.index] = ans.label
                            lane.inflight_host[ans.index] = False
                            landed = True
                    if not landed:
                        continue  # nothing for this group this pass
                    engine_dispatches.add()  # updates upload
                    stacked, cmask = session_fold_answers_batch(
                        stacked, jnp.asarray(updates),
                        keep_conflicts_published=requery)
                    if requery:
                        cmask = obs.to_host(cmask)
                        exhausted_mask = np.zeros(cmask.shape, bool)
                        trust = False
                        for b, lane in enumerate(lanes):
                            cidx = np.nonzero(cmask[b, :lane.p])[0]
                            if len(cidx) == 0:
                                continue
                            with obs.span("join.gateway.post",
                                          lane.req.rid):
                                ticket, exhausted = gateway.requery(
                                    lane.req.rid, lane.ordered, cidx,
                                    lane.req.crowd,
                                    cents_per_assignment=lane.rate_cents,
                                    budget_cents=lane.budget_cents)
                            lane.n_requeried += len(ticket.indices)
                            if ticket.indices:
                                lane.inflight_host[list(ticket.indices)] = True
                            pending |= bool(ticket.indices)
                            if exhausted:
                                exhausted_mask[b, exhausted] = True
                                trust = True
                        if trust:
                            # escalation ladder exhausted: the graph outvotes
                            # the crowd — un-publish + deduce in one dispatch
                            stacked = session_trust_graph_batch(
                                stacked, jnp.asarray(exhausted_mask))
                    stage[2] = stacked
            progress = False
            stop_set = set(id(l) for l in budget_stops)
            for key, lanes, stacked, _ in staged:
                self._stacks[key] = (tuple(lanes), stacked)
                labels = obs.to_host(stacked.labels)
                for b, lane in enumerate(lanes):
                    new = labels[b, :lane.p]
                    progress |= bool((new != lane.labels_host).any())
                    lane.labels_host = new
                    if id(lane) in stop_set and (new == UNKNOWN).any():
                        # budget exhausted with pairs still open: trust the
                        # graph for the remainder (DESIGN.md §10) and finalize
                        lane.state = _index_state(stacked, b)
                        self._budget_stop(lane)
                        progress = True
                    elif lane.done:  # leaving the group: materialize its state
                        lane.state = _index_state(stacked, b)
            return progress

    # -- asynchronous ID/NF engine -------------------------------------------
    def _publish(self, lane: _Lane, gateway: CrowdGateway) -> int:
        """Select the lane's current frontier and post it (instant decision:
        in-flight pairs are assumed matching but never re-posted).  Adaptive
        lanes refresh priorities from the live posterior first; budgeted
        lanes post only what the remaining budget affords (highest marginal
        gain first) and budget-stop when it affords nothing."""
        if lane.budget_stopped:
            return 0
        if lane.adaptive:
            lane.state = session_refresh_priorities(lane.state,
                                                    lane.prior_dev)
        frontier = obs.to_host(session_frontier(lane.state))
        idx = np.nonzero(frontier)[0]
        if len(idx) == 0:
            return 0
        afford = lane.affordable(gateway)
        if afford == 0:
            self._budget_stop(lane)
            return 0
        if afford is not None and afford < len(idx):
            if lane.adaptive:
                # the refresh above already wrote -gain into every pending
                # pair's priority — read it back, no second dispatch
                gains = -obs.to_host(lane.state.priority)
            else:
                gains = obs.to_host(session_gains(lane.state,
                                                  lane.prior_dev))
            idx = idx[np.argsort(-gains[idx], kind="stable")][:afford]
            frontier = np.zeros_like(frontier)
            frontier[idx] = True
        # cluster-task planning (DESIGN.md §15): harvested extras publish
        # alongside the frontier so in-flight verdicts gate deduction
        clusters, pair_idx = self._plan_tasks(lane, idx, gateway)
        if clusters:
            frontier = np.array(frontier)
            for _, cov in clusters:
                frontier[cov] = True
        engine_dispatches.add()  # frontier-mask upload
        lane.state = session_mark_published(lane.state, jnp.asarray(frontier))
        n = self._post_lane(lane, clusters, pair_idx, gateway)
        lane.round_sizes.append(n)
        lane.in_flight += n
        return n

    def _sweep_lane(self, lane: _Lane) -> None:
        """Deduce everything the lane's evidence pins down (skipping pairs
        whose answers are still in flight) and refresh the host mirror."""
        lane.state = session_deduce(lane.state)
        lane.labels_host = obs.to_host(lane.state.labels)[:lane.p]

    def _handle_conflicts(self, lane: _Lane, cidx: np.ndarray,
                          gateway: CrowdGateway) -> None:
        """Requery-policy escalation for pairs whose answers were rejected:
        re-post through the gateway (they stay published, so deduction holds
        off), and let the graph label the exhausted ones (DESIGN.md §9).
        Under the drop policy the fold already settled them — nothing to do."""
        if self.conflict_policy != "requery":
            return
        with obs.span("join.gateway.post", lane.req.rid):
            ticket, exhausted = gateway.requery(
                lane.req.rid, lane.ordered, cidx, lane.req.crowd,
                cents_per_assignment=lane.rate_cents,
                budget_cents=lane.budget_cents)
        lane.n_requeried += len(ticket.indices)
        lane.in_flight += len(ticket.indices)
        if ticket.indices:
            lane.inflight_host[list(ticket.indices)] = True
        if exhausted:
            mask = np.zeros(lane.state.u.shape[0], bool)
            mask[exhausted] = True
            engine_dispatches.add()  # exhausted-mask upload
            lane.state = session_trust_graph(lane.state, jnp.asarray(mask))

    def _run_async(self) -> Dict[int, JoinSessionResult]:
        """Event-driven serving (§5.2 lifted into the service): lanes fold
        answers as the gateway delivers them; a non-matching answer or a
        drained lane triggers deduce + re-frontier + post immediately."""
        gateway, active = self._resume_run_state()
        while self.queue or active or gateway.in_flight:
            self._checkpoint_tick(active, gateway)
            refilled = False
            while self.queue and len(active) < self.lanes:
                lane = self._open_lane(self.queue.popleft())
                active.append(lane)
                refilled = True
            for r in self.queue:  # still queued behind fully-occupied lanes
                r.admission_deferred = True
            if any(self._pending_arrivals.get(l.req.rid) for l in active):
                # arrivals are ingested before a fresh lane's first publish
                # (up-front streams) and once per event-loop pass for
                # interleaved streams; a lane that went idle waiting on its
                # next epoch re-publishes immediately
                for lane in active:
                    if self._ingest_pending(lane) and lane.in_flight == 0 \
                            and lane.round_sizes and not lane.done:
                        self._publish(lane, gateway)
            if refilled:
                # zero-pair sessions are born done — finalize without posting
                active = self._retire_done(active, gateway)
            if active and gateway.in_flight == 0 and \
                    all(self._fused_eligible(lane) and lane.in_flight == 0
                        for lane in active):
                # on-device round engine (DESIGN.md §13): with an immediate
                # gateway and nothing in flight, the event-driven discipline
                # degenerates to per-lane round barriers — the same wave the
                # fused megabatch simulates.  A conflicted lane drops back to
                # the event loop below with its fused_ok cleared.
                if self._drive_fused(active, gateway):
                    active = self._retire_done(active, gateway)
                    continue
            if refilled:
                for lane in active:
                    if lane.in_flight == 0 and not lane.round_sizes:
                        self._publish(lane, gateway)
            with obs.span("join.gateway.drain"):
                answers = gateway.poll()
            if not answers:
                if not active and not gateway.in_flight:
                    continue  # queue may still refill
                # platform drained: sweep + republish every stuck lane
                posted = 0
                for lane in list(active):
                    if lane.in_flight:
                        continue
                    self._sweep_lane(lane)
                    if not lane.done:
                        posted += self._publish(lane, gateway)
                active = self._retire_done(active, gateway)
                if not answers and not posted and not gateway.in_flight \
                        and active:
                    if any(self._pending_arrivals.get(l.req.rid)
                           for l in active):
                        continue  # queued arrival epochs ingest next pass
                    raise RuntimeError(
                        "join engine stuck: no frontier and nothing "
                        f"deducible for rids {[l.req.rid for l in active]}")
                continue
            by_rid: Dict[int, List] = {}
            for ans in answers:
                by_rid.setdefault(ans.rid, []).append(ans)
            lanes_by_rid = {l.req.rid: l for l in active}
            keep_pub = self.conflict_policy == "requery"
            for rid, got in by_rid.items():
                lane = lanes_by_rid.get(rid)
                if lane is None:
                    continue  # lane already finalized (answer raced retire)
                p_cap = lane.state.u.shape[0]
                updates = np.full(p_cap, UNKNOWN, np.int32)
                for ans in got:
                    updates[ans.index] = ans.label
                    lane.inflight_host[ans.index] = False
                lane.in_flight -= len(got)
                engine_dispatches.add()  # updates upload
                any_neg = any(ans.label != POS for ans in got)
                fold_now = any_neg or lane.in_flight == 0
                if fold_now:
                    # §5.2: a returned MATCH agrees with the optimistic
                    # assumption — selection can only change on NEG (or when
                    # the lane drains); fold + deduce + re-select at once.
                    lane.state, cmask = session_fold_answers(
                        lane.state, jnp.asarray(updates),
                        keep_conflicts_published=keep_pub)
                else:
                    lane.state, cmask = session_apply_answers(
                        lane.state, jnp.asarray(updates),
                        keep_conflicts_published=keep_pub)
                cidx = np.nonzero(obs.to_host(cmask)[:lane.p])[0]
                if len(cidx):
                    self._handle_conflicts(lane, cidx, gateway)
                    if not fold_now:
                        # a rejected answer is a NEG-grade event: the
                        # optimistic assumption broke even though every
                        # returned label read MATCH — deduce + re-select
                        self._sweep_lane(lane)
                        fold_now = True
                lane.labels_host = obs.to_host(lane.state.labels)[:lane.p]
                if fold_now and not lane.done:
                    self._publish(lane, gateway)
            active = self._retire_done(active, gateway)
        return dict(self.results)

    # -- durable serving state (DESIGN.md §16) -------------------------------
    def _resume_run_state(self) -> Tuple[CrowdGateway, List[_Lane]]:
        """The run loop's starting state: a fresh gateway and empty lane set
        normally, or the lanes + gateway rebuilt by :meth:`restore` — the
        resumed run picks up mid-wave with tickets still in flight."""
        if self._resume is not None:
            active, gateway = self._resume
            self._resume = None
            return gateway, list(active)
        return CrowdGateway(latency=self.latency, nf=self.nf,
                            aggregation=self.aggregation), []

    def _checkpoint_tick(self, active: List[_Lane],
                         gateway: CrowdGateway) -> None:
        """Cadenced checkpoint hook at the top of every run-loop pass:
        every ``checkpoint_every``-th pass commits a checkpoint (the first
        pass always does, so even a run killed in its first wave restores
        to an admitted queue instead of nothing)."""
        if self._ckpt is None:
            return
        tick = self._ckpt_tick
        self._ckpt_tick += 1
        if tick % self.checkpoint_every:
            return
        self._checkpoint_now(active, gateway)

    def _checkpoint_now(self, active: List[_Lane],
                        gateway: CrowdGateway) -> None:
        """Commit one checkpoint of the full serving state — lanes (device
        states pulled to host), queue, results, arrival epochs, gateway
        tickets/ledgers, envelope counters — through the atomic
        ``CheckpointManager`` path.  Group stacks are flushed first so lane
        states are authoritative; flushing is a pure writeback, so the
        capture never perturbs the run's semantics."""
        from repro.serve import recovery
        with obs.span("join.checkpoint"):
            self._flush_stacks()
            tree, side = recovery.capture_service(self, active, gateway)
            self._ckpt.save(self._ckpt_step, tree, sidecar=side)
        self._ckpt_step += 1
        if self._crash_after_checkpoints is not None and \
                self._ckpt_step >= self._crash_after_checkpoints:
            raise ServiceKilled(
                f"injected crash after checkpoint {self._ckpt_step - 1} "
                f"(step dir committed under {self.checkpoint_dir})")

    @classmethod
    def restore(cls, checkpoint_dir: str,
                step: Optional[int] = None,
                cluster_cache=None) -> "JoinService":
        """Rebuild a service from the latest (or given) checkpoint under
        ``checkpoint_dir`` (DESIGN.md §16): configuration, queued and
        in-progress requests, finished results, spend ledgers, and the
        gateway's in-flight tickets all come back; calling :meth:`run` on
        the restored service resumes mid-wave and produces labels identical
        to an uninterrupted run — without re-billing any answered pair.
        ``cluster_cache`` overrides the cache handle (by default the saved
        ``cache_path`` is reloaded).  ``service.last_recovery`` reports
        what was recovered."""
        from repro.serve import recovery
        return recovery.restore_service(cls, checkpoint_dir, step=step,
                                        cluster_cache=cluster_cache)

    # -- entry point ---------------------------------------------------------
    def run(self) -> Dict[int, JoinSessionResult]:
        """Drain the queue: lanes are refilled the moment a session finishes
        (continuous batching).  Returns {rid: result} for everything served."""
        with obs.span("join.run"):
            if self.async_mode:
                return self._run_async()
            gateway, active = self._resume_run_state()
            self._stacks.clear()  # drop any cache left by an aborted run
            self._prior_stacks.clear()
            while self.queue or active:
                self._checkpoint_tick(active, gateway)
                while self.queue and len(active) < self.lanes:
                    active.append(self._open_lane(self.queue.popleft()))
                # still queued behind fully-occupied lanes
                for r in self.queue:
                    r.admission_deferred = True
                if any(self._pending_arrivals.get(l.req.rid) for l in active):
                    # arrival epochs land before the round's frontier: lane
                    # states must be authoritative (not cached in a group
                    # stack) while they grow and re-bucket.  Arrivals for
                    # rids still waiting in the queue don't disturb the group
                    # caches.
                    self._flush_stacks()
                    for lane in active:
                        self._ingest_pending(lane)
                # zero-pair sessions are born done — finalize without a step
                active = self._retire_done(active, gateway)
                if not active:
                    continue
                if all(lane.done for lane in active):
                    # every open lane is just waiting on queued arrival epochs
                    # (interleaved streams); ingest resumes next iteration
                    continue
                if all(self._fused_eligible(lane) for lane in active):
                    # on-device round engine (DESIGN.md §13): the whole crowd
                    # wave runs as megabatch dispatches across all lanes.  No
                    # progress means every lane conflicted on its next round —
                    # fall through to the exact per-round path, which replays
                    # that round with the full §9 conflict machinery.
                    if self._drive_fused(active, gateway):
                        active = self._retire_done(active, gateway)
                        continue
                if not self._step(active, gateway):
                    raise RuntimeError(
                        "join engine stuck: no frontier and nothing deducible "
                        f"for rids {[l.req.rid for l in active]}")
                active = self._retire_done(active, gateway)
            self._stacks.clear()
            self._prior_stacks.clear()
        return dict(self.results)
