"""Crowd platform simulators (§2.1, §6.4) and worker-quality model (§15).

The paper assumes correct answers for the algorithmic sections (§2.1) and uses
a real AMT deployment with 3-way majority vote, 20-pair HIT batching and
qualification tests for §6.4.  We implement both regimes, plus the per-worker
reliability layer of DESIGN.md §15:

* :class:`PerfectCrowd` — always returns ground truth (§2.1 assumption; also
  what the paper "simulated" for the Table 1 latency comparison).
* :class:`NoisyCrowd` — each of ``n_assignments`` workers flips the true label
  with prob ``error_rate`` (reduced by a qualification-test pass rate), final
  label by majority vote — the §6.4 deployment model.  With ``n_workers`` set
  it simulates a *heterogeneous* pool whose per-worker error rates are drawn
  from a Beta distribution, so the reliability estimator has something real
  to recover.
* :class:`WorkerModel` — streaming Dawid–Skene estimator over the binary
  match/non-match label space: per-worker error rates tracked online from
  ballots, log-odds weighted vote aggregation replacing naive majority.
* :class:`ClusterTask` — CrowdER-style multi-pair task: one worker partitions
  k objects, harvesting up to k·(k−1)/2 pair verdicts for the price of one
  assignment-scaled task.
* :class:`LatencyModel` — lognormal per-assignment completion times over a
  finite worker pool, used by the event-driven simulator for Table 1/2 wall
  clock and Figure 16.
* :class:`CrowdGateway` — the batched, optionally-asynchronous transport the
  serving layer talks to (DESIGN.md §8): ``post(pairs) -> ticket``,
  ``poll() -> answers``, with in-flight tracking.  With a
  :class:`LatencyModel` attached it simulates an asynchronous platform
  (finite worker pool, lognormal per-assignment minutes, optional
  non-matching-first steering), which is what lets the §5.2 instant-decision
  / non-matching-first optimizations run in the serving path instead of only
  in ``core/parallel.py``'s host simulator.  ``aggregation="em"`` swaps the
  per-ballot majority collapse for :class:`WorkerModel` weighted voting.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import gateway_ballot_pairs, gateway_bulk_pairs
from .cluster_graph import MATCH, NEG, NON_MATCH, POS
from .pairs import PairSet


@dataclasses.dataclass(frozen=True)
class Ballot:
    """One completed crowd question: votes plus the workers who cast them.

    Args (fields):
        label: the crowd's own majority collapse of the votes, as a paper
            label string (``MATCH`` / ``NON_MATCH``).  Transport-level
            aggregation (e.g. :class:`WorkerModel`) may overrule it.
        votes: per-assignment votes in engine encoding (POS / NEG), one
            per worker.
        workers: stable worker ids, aligned with ``votes`` — the handle the
            reliability model keys its error estimates on.

    Example::

        >>> b = Ballot(label=MATCH, votes=(POS, POS, NEG), workers=(4, 7, 9))
        >>> b.workers[b.votes.index(NEG)]
        9
    """

    label: str
    votes: Tuple[int, ...]
    workers: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class ClusterTask:
    """CrowdER-style multi-pair request: one worker partitions ``n_objects``.

    A cluster task shows a single worker the distinct objects behind a set of
    candidate pairs and asks for a partition into groups of matching records;
    the partition decodes into one POS/NEG verdict per covered pair — up to
    k·(k−1)/2 pair labels for one task's price (DESIGN.md §15).  The decoded
    verdicts are transitively consistent *within the task* by construction
    (they come from a partition), so they fold through the conflict-screened
    ``session_fold_answers`` path exactly like pair answers.

    Args (fields):
        rid: request id the task belongs to.
        indices: candidate-pair indices covered by the task (every pair has
            both endpoints inside the task's object set).
        n_objects: number of distinct objects shown to the worker.
        cents: total price charged for the task.
    """

    rid: int
    indices: Tuple[int, ...]
    n_objects: int
    cents: float


class Crowd:
    """Interface: label pair index ``i`` of a :class:`~repro.core.PairSet`.

    Concrete crowds implement :meth:`ask`; the richer entry points
    (:meth:`ask_votes`, :meth:`ask_ballot`, :meth:`ask_cluster`) have
    default implementations in terms of it that deterministic crowds
    inherit unchanged.  ``n_asked`` counts questions for the §6 cost
    accounting.
    """

    n_asked: int = 0

    def ask(self, pairs: PairSet, i: int) -> str:
        """Label one pair.

        Args:
            pairs: the candidate :class:`~repro.core.PairSet`.
            i: pair index into ``pairs``.

        Returns:
            A paper label string — ``MATCH`` or ``NON_MATCH``.
        """
        raise NotImplementedError

    def ask_votes(self, pairs: PairSet, i: int,
                  n_assignments: Optional[int] = None
                  ) -> Tuple[str, Tuple[int, ...]]:
        """Majority label plus the per-assignment votes behind it, in engine
        encoding (POS / NEG).  ``n_assignments`` overrides the platform
        default — the requery escalation path (DESIGN.md §9) re-posts
        rejected pairs with more assignments.  Deterministic crowds have a
        single unanimous vote.

        Args:
            pairs: the candidate pair set.
            i: pair index to label.
            n_assignments: per-question assignment-count override.

        Returns:
            ``(label, votes)`` — paper label string and engine-encoded votes.
        """
        lab = self.ask(pairs, i)
        return lab, (POS if lab == MATCH else NEG,)

    def ask_ballot(self, pairs: PairSet, i: int,
                   n_assignments: Optional[int] = None,
                   exclude: Sequence[int] = ()) -> Ballot:
        """Like :meth:`ask_votes` but every vote carries a stable worker id.

        The default implementation wraps :meth:`ask_votes` and mints fresh
        worker ids from a per-crowd counter (each assignment is a previously
        unseen worker), so deterministic crowds keep byte-identical behaviour.
        Pool-backed crowds (:class:`NoisyCrowd` with ``n_workers``) override
        this to draw real workers and honour ``exclude``.

        Args:
            pairs: the candidate pair set.
            i: pair index to label.
            n_assignments: per-question assignment-count override.
            exclude: worker ids to avoid when the pool allows it — the
                requery path routes escalations to fresh workers.

        Returns:
            A :class:`Ballot` with label, votes, and aligned worker ids.

        Example::

            >>> ballot = PerfectCrowd().ask_ballot(pairs, 0)
            >>> len(ballot.votes) == len(ballot.workers) == 1
            True
        """
        del exclude  # anonymous fresh workers by construction
        lab, votes = self.ask_votes(pairs, i, n_assignments)
        return Ballot(label=lab, votes=votes,
                      workers=self._fresh_workers(len(votes)))

    def ask_cluster(self, pairs: PairSet, indices: Sequence[int],
                    prefer: Sequence[int] = (),
                    exclude: Sequence[int] = ()
                    ) -> Tuple[Tuple[int, ...], int]:
        """Simulate one :class:`ClusterTask`: a single worker partitions the
        objects behind ``indices`` and the partition decodes to pair verdicts.

        The default implementation is noise-free: it reconstructs the truth
        partition restricted to the task (union–find over the truth-POS pairs
        among ``indices`` — exact, because ground truth is transitive) and
        decodes it, so :class:`PerfectCrowd` cluster answers equal its pair
        answers.  :class:`NoisyCrowd` overrides this with per-object worker
        noise.

        Args:
            pairs: the candidate pair set (must carry ground truth).
            indices: pair indices covered by the task; both endpoints of
                every pair must lie in the task's object set.
            prefer: worker ids to favour, most trusted first (ignored by
                crowds without a worker pool).
            exclude: worker ids that must not answer — the gateway passes
                the workers who already took an assignment of the same task.

        Returns:
            ``(labels, worker)`` — one engine-encoded POS/NEG verdict per
            entry of ``indices``, and the id of the worker who answered.
        """
        del prefer, exclude  # fresh-worker crowds never repeat a worker
        if pairs.truth is None:
            raise ValueError(
                "ask_cluster needs ground truth to simulate the partition")
        idx = tuple(int(i) for i in indices)
        self.n_asked += len(idx)
        labels = tuple(POS if bool(pairs.truth[i]) else NEG for i in idx)
        return labels, self._fresh_workers(1)[0]

    def precomputed_answers(self, pairs: PairSet) -> Optional[np.ndarray]:
        """Every pair's answer up front (engine encoding), or ``None``.

        Non-None only when answers are independent of the ask order — the
        contract the on-device round engine (DESIGN.md §13) needs to fold k
        rounds without surfacing each frontier to the host first.  Stateful
        crowds (e.g. :class:`NoisyCrowd`'s rng stream) must return ``None``;
        per-pair ``ask`` bookkeeping (``n_asked``, billing) still runs when
        the serving layer replays the posts afterwards.

        Args:
            pairs: the candidate pair set.

        Returns:
            An int32 POS/NEG array over all pairs, or ``None`` when answers
            depend on ask order.
        """
        return None

    def ask_bulk(self, pairs: PairSet, indices: np.ndarray,
                 answers: Optional[np.ndarray] = None
                 ) -> Optional[Tuple[np.ndarray, int]]:
        """Answer many pairs in one call, or ``None`` to be asked per pair.

        Non-None only for a crowd whose every ballot is one vote by a fresh
        worker, independent of ask order: the gateway then posts the batch
        as arrays (DESIGN.md §8).  The call must leave the crowd as
        ``len(indices)`` in-order :meth:`ask_ballot` calls would — the
        question counter and the fresh-worker counter each advance by
        ``len(indices)``, pair ``indices[j]`` answered by worker
        ``first + j``.

        Args:
            pairs: the candidate pair set.
            indices: int pair indices to label, in ask order.
            answers: the crowd's :meth:`precomputed_answers` at
                ``indices``, when the caller already holds them.

        Returns:
            ``(labels, first)`` — int32 POS/NEG labels aligned with
            ``indices`` and the first worker id — or ``None``.
        """
        return None

    def reset(self) -> None:
        """Zero the question counter (and the fresh-worker id counter)."""
        self.n_asked = 0
        self._worker_seq = 0

    def _fresh_workers(self, k: int) -> Tuple[int, ...]:
        start = getattr(self, "_worker_seq", 0)
        self._worker_seq = start + k
        return tuple(range(start, start + k))

    # -- persistence (DESIGN.md §16) ------------------------------------
    def state_dict(self) -> dict:
        """JSON-able snapshot of the crowd's mutable state.

        Subclasses with more state (rng streams, worker pools) extend the
        base dict; together with :func:`crowd_from_state` this is what lets
        a restored service replay the exact same answer stream an
        uninterrupted run would have seen.

        Returns:
            A dict of plain JSON types.
        """
        return {"n_asked": int(self.n_asked),
                "worker_seq": int(getattr(self, "_worker_seq", 0))}

    def load_state_dict(self, state: dict) -> None:
        """Restore a :meth:`state_dict` snapshot in place.

        Args:
            state: dict produced by :meth:`state_dict`.
        """
        self.n_asked = int(state.get("n_asked", 0))
        self._worker_seq = int(state.get("worker_seq", 0))


_CROWD_CLASSES: Dict[str, type] = {}


def register_crowd(cls: type) -> type:
    """Register a :class:`Crowd` subclass for checkpoint restore.

    The serving checkpoint stores crowds as ``{"class": name, "state":
    state_dict()}``; restore looks the class up here.  Usable as a
    decorator; the built-in crowds are pre-registered.

    Args:
        cls: the crowd class to register.

    Returns:
        ``cls`` unchanged.
    """
    _CROWD_CLASSES[cls.__name__] = cls
    return cls


def crowd_to_state(crowd: Crowd) -> dict:
    """Serialize a crowd to ``{"class": ..., "state": ...}`` (JSON-able).

    Args:
        crowd: any registered :class:`Crowd`.

    Returns:
        A payload :func:`crowd_from_state` accepts.
    """
    return {"class": type(crowd).__name__, "state": crowd.state_dict()}


def crowd_from_state(payload: dict) -> Crowd:
    """Rebuild a crowd from :func:`crowd_to_state` output.

    The instance is created without running ``__init__`` (constructors
    consume rng draws / validate ctor-time arguments that the snapshot
    already reflects) and then restored via ``load_state_dict``.

    Args:
        payload: ``{"class": name, "state": state_dict}``.

    Returns:
        A crowd whose future answers match the snapshotted instance's.
    """
    name = payload["class"]
    cls = _CROWD_CLASSES.get(name)
    if cls is None:
        raise KeyError(
            f"unknown crowd class {name!r} — register it with "
            "repro.core.crowd.register_crowd before restoring")
    crowd = cls.__new__(cls)
    crowd.load_state_dict(payload["state"])
    return crowd


def _rng_to_state(rng: np.random.Generator) -> dict:
    return rng.bit_generator.state


def _rng_from_state(state: dict) -> np.random.Generator:
    rng = np.random.default_rng()
    rng.bit_generator.state = state
    return rng


@register_crowd
class PerfectCrowd(Crowd):
    """Ground-truth oracle crowd — the §2.1 assumption.

    Every question returns the pair's truth label with a single unanimous
    vote; ``precomputed_answers`` exposes the whole answer table so the
    on-device round engine can fold multiple rounds per dispatch.

    Example::

        >>> crowd = PerfectCrowd()
        >>> crowd.ask(pairs, 0) in (MATCH, NON_MATCH)
        True
    """

    def ask(self, pairs: PairSet, i: int) -> str:
        """Return the ground-truth label of pair ``i``.

        Args:
            pairs: the candidate pair set (must carry ground truth).
            i: pair index to label.

        Returns:
            ``MATCH`` or ``NON_MATCH`` — the truth label.
        """
        self.n_asked += 1
        return pairs.truth_label(i)

    def precomputed_answers(self, pairs: PairSet) -> Optional[np.ndarray]:
        """Whole answer table up front — truth in engine encoding.

        Args:
            pairs: the candidate pair set.

        Returns:
            int32 POS/NEG array over all pairs, or ``None`` without truth.
        """
        if pairs.truth is None:
            return None
        return np.where(np.asarray(pairs.truth, bool), POS, NEG
                        ).astype(np.int32)

    def ask_bulk(self, pairs: PairSet, indices: np.ndarray,
                 answers: Optional[np.ndarray] = None
                 ) -> Optional[Tuple[np.ndarray, int]]:
        """Truth labels of many pairs at once (see :meth:`Crowd.ask_bulk`).

        ``None`` when a subclass overrides :meth:`ask`, :meth:`ask_votes`
        or :meth:`ask_ballot` — its per-pair hook must keep being called —
        or when there is no ground truth (the per-pair path raises).

        Args:
            pairs: the candidate pair set.
            indices: int pair indices to label, in ask order.
            answers: ``precomputed_answers(pairs)[indices]``, if held.

        Returns:
            ``(labels, first)`` or ``None``.
        """
        cls = type(self)
        if (cls.ask is not PerfectCrowd.ask
                or cls.ask_votes is not Crowd.ask_votes
                or cls.ask_ballot is not Crowd.ask_ballot
                or pairs.truth is None):
            return None
        if answers is None:
            answers = np.where(np.asarray(pairs.truth, bool)[indices],
                               POS, NEG)
        labels = np.array(answers, np.int32)
        if labels.shape != np.shape(indices):
            raise ValueError(
                f"answers of shape {labels.shape} do not align with "
                f"indices of shape {np.shape(indices)}")
        first = getattr(self, "_worker_seq", 0)
        self._worker_seq = first + len(labels)
        self.n_asked += len(labels)
        return labels, first


@register_crowd
class NoisyCrowd(Crowd):
    """§6.4 deployment model: majority vote over error-prone workers.

    Each of ``n_assignments`` workers flips the true label with probability
    ``error_rate`` (reduced 30% by the qualification-test screen); the
    crowd's own label is the majority vote.  With ``n_workers`` set, the
    crowd simulates a *heterogeneous* finite pool: per-worker error rates
    are drawn once from a Beta distribution centred on the (qualified)
    ``error_rate``, ballots name the workers who voted, and cluster tasks
    go to the most trusted worker the caller prefers — the ground truth a
    :class:`WorkerModel` is supposed to recover.

    Args:
        error_rate: base per-assignment error probability.
        n_assignments: default votes per pair question (odd, for majority).
        qualification: model the §6.4 qualification test as a 0.7×
            multiplicative error reduction.
        seed: rng seed (worker draws, error draws, cluster noise).
        n_workers: size of the heterogeneous worker pool; ``None`` keeps the
            homogeneous stream byte-identical to earlier revisions.
        worker_concentration: Beta concentration of the per-worker error
            distribution (higher = tighter around the mean).

    Example::

        >>> crowd = NoisyCrowd(error_rate=0.1, n_workers=25, seed=0)
        >>> ballot = crowd.ask_ballot(pairs, 0)
        >>> sorted(set(ballot.workers)) == sorted(ballot.workers)  # distinct
        True
    """

    def __init__(self, error_rate: float = 0.05, n_assignments: int = 3,
                 qualification: bool = True, seed: int = 0,
                 n_workers: Optional[int] = None,
                 worker_concentration: float = 12.0):
        # qualification tests (§6.4) screen the worst workers: model as a
        # multiplicative reduction of the base error rate.
        _require_odd(n_assignments)
        self.error_rate = error_rate * (0.7 if qualification else 1.0)
        self.n_assignments = n_assignments
        self.rng = np.random.default_rng(seed)
        self.n_asked = 0
        self.n_workers = n_workers
        if n_workers is not None:
            if n_workers < n_assignments:
                raise ValueError(
                    f"worker pool of {n_workers} cannot cover "
                    f"{n_assignments} distinct assignments per pair")
            mean = min(max(self.error_rate, 1e-3), 0.45)
            c = worker_concentration
            self.worker_errors = np.clip(
                self.rng.beta(mean * c, (1.0 - mean) * c, size=n_workers),
                1e-3, 0.49)
        else:
            self.worker_errors = None

    def ask(self, pairs: PairSet, i: int) -> str:
        """Majority-vote label for pair ``i`` (see :meth:`ask_votes`).

        Args:
            pairs: the candidate pair set (must carry ground truth).
            i: pair index to label.

        Returns:
            ``MATCH`` or ``NON_MATCH`` — the majority of the noisy votes.
        """
        return self.ask_votes(pairs, i)[0]

    def ask_votes(self, pairs: PairSet, i: int,
                  n_assignments: Optional[int] = None
                  ) -> Tuple[str, Tuple[int, ...]]:
        """Noisy majority vote: each worker flips the truth independently.

        Args:
            pairs: the candidate pair set (must carry ground truth).
            i: pair index to label.
            n_assignments: odd per-question override of the vote count.

        Returns:
            ``(label, votes)`` — majority paper label and the engine-encoded
            per-assignment votes behind it.
        """
        b = self.ask_ballot(pairs, i, n_assignments)
        return b.label, b.votes

    def ask_ballot(self, pairs: PairSet, i: int,
                   n_assignments: Optional[int] = None,
                   exclude: Sequence[int] = ()) -> Ballot:
        """Noisy ballot with worker identities.

        Homogeneous mode (``n_workers=None``) draws one uniform variate per
        assignment — the exact rng stream of earlier revisions — and mints
        fresh anonymous worker ids.  Pool mode samples ``k`` distinct
        workers (avoiding ``exclude`` while the pool allows; when fewer than
        ``k`` unseen workers remain, previously seen ones top the ballot up,
        so escalation never deadlocks) and flips each vote with that
        worker's own error rate.

        Args:
            pairs: the candidate pair set (must carry ground truth).
            i: pair index to label.
            n_assignments: odd per-question override of the vote count.
            exclude: worker ids the requery path wants routed around.

        Returns:
            A :class:`Ballot`; its ``label`` is the unweighted majority.
        """
        k = self.n_assignments if n_assignments is None else n_assignments
        _require_odd(k)
        self.n_asked += 1
        true_match = bool(pairs.truth[i])
        if self.worker_errors is None:
            workers = self._fresh_workers(k)
            correct = self.rng.random(k) >= self.error_rate
        else:
            workers = tuple(self._pick_workers(k, exclude))
            errs = self.worker_errors[list(workers)]
            correct = self.rng.random(k) >= errs
        # correct True = worker answers the truth; vote is the worker's label
        votes = tuple(
            (POS if true_match else NEG) if c else (NEG if true_match else POS)
            for c in correct)
        maj_correct = int(correct.sum()) * 2 > k
        match = true_match if maj_correct else not true_match
        return Ballot(label=MATCH if match else NON_MATCH, votes=votes,
                      workers=workers)

    def ask_cluster(self, pairs: PairSet, indices: Sequence[int],
                    prefer: Sequence[int] = (),
                    exclude: Sequence[int] = ()
                    ) -> Tuple[Tuple[int, ...], int]:
        """One worker partitions the task's objects, with per-object noise.

        The truth partition restricted to the task is rebuilt by union–find
        over the truth-POS pairs among ``indices`` (exact: truth is
        transitive), then each object is independently *misplaced* with the
        worker's error probability — moved to a uniformly random other group
        or split into a fresh singleton.  The decoded verdicts are therefore
        noisy but transitively consistent within the task, the CrowdER
        failure mode (a misfiled record corrupts all its incident pairs at
        once, coherently).

        Args:
            pairs: the candidate pair set (must carry ground truth).
            indices: covered pair indices; endpoints define the object set.
            prefer: worker ids to favour, most trusted first.  Pool mode
                sends the task to the first preferred worker in range;
                without a pool (or no usable preference) a fresh or random
                worker answers.
            exclude: worker ids that must not answer — distinct assignments
                of the same task go to distinct workers.

        Returns:
            ``(labels, worker)`` — engine-encoded verdicts aligned with
            ``indices`` and the answering worker's id.
        """
        if pairs.truth is None:
            raise ValueError(
                "ask_cluster needs ground truth to simulate the partition")
        idx = [int(i) for i in indices]
        self.n_asked += len(idx)
        banned = {int(w) for w in exclude}
        if self.worker_errors is None:
            worker = self._fresh_workers(1)[0]
            err = self.error_rate
        else:
            usable = [int(w) for w in prefer
                      if 0 <= int(w) < self.n_workers
                      and int(w) not in banned]
            worker = usable[0] if usable else self._pick_workers(1, banned)[0]
            err = float(self.worker_errors[worker])
        u = np.asarray(pairs.u)[idx]
        v = np.asarray(pairs.v)[idx]
        objs = {o: j for j, o in enumerate(np.unique(np.concatenate([u, v])))}
        parent = list(range(len(objs)))

        def find(a: int) -> int:
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        for j, i in enumerate(idx):
            if bool(pairs.truth[i]):
                ra, rb = find(objs[int(u[j])]), find(objs[int(v[j])])
                if ra != rb:
                    parent[ra] = rb
        group = [find(a) for a in range(len(objs))]
        next_group = len(objs)  # fresh singleton id space
        for a in range(len(objs)):
            if self.rng.random() < err:
                others = sorted(set(group) - {group[a]}) + [next_group]
                group[a] = int(others[int(self.rng.integers(len(others)))])
                next_group += 1
        labels = tuple(
            POS if group[objs[int(u[j])]] == group[objs[int(v[j])]] else NEG
            for j in range(len(idx)))
        return labels, int(worker)

    def _pick_workers(self, k: int, exclude: Sequence[int]) -> List[int]:
        banned = {int(w) for w in exclude}
        fresh = np.array([w for w in range(self.n_workers)
                          if w not in banned], dtype=int)
        if len(fresh) >= k:
            return [int(w) for w in
                    self.rng.choice(fresh, size=k, replace=False)]
        # pool exhausted: take every unseen worker, top up from the rest
        rest = np.array(sorted(banned & set(range(self.n_workers))),
                        dtype=int)
        top_up = self.rng.choice(rest, size=k - len(fresh), replace=False)
        return [int(w) for w in fresh] + [int(w) for w in top_up]

    def pair_error_rate(self, n_assignments: Optional[int] = None) -> float:
        """Analytic majority-vote error for sanity checks.  The closed form
        counts strict worker-error majorities, which is exact only for odd
        ``k`` — enforced at construction (a tied even-``k`` vote would
        silently resolve to the wrong label).

        Args:
            n_assignments: odd vote count (defaults to the platform's).

        Returns:
            Probability that the majority label is wrong.
        """
        e = self.error_rate
        k = self.n_assignments if n_assignments is None else n_assignments
        _require_odd(k)
        return sum(
            math.comb(k, j) * e**j * (1 - e) ** (k - j)
            for j in range(k // 2 + 1, k + 1)
        )

    def expected_minority_fraction(self) -> float:
        """Analytic E[minority votes / k] — the inter-worker disagreement a
        platform can *measure* without ground truth; compare with the
        gateway's ``measured_disagreement``.

        Returns:
            Expected fraction of votes landing in the ballot minority.
        """
        e, k = self.error_rate, self.n_assignments
        return sum(
            math.comb(k, j) * e**j * (1 - e) ** (k - j) * min(j, k - j) / k
            for j in range(k + 1)
        )

    def state_dict(self) -> dict:
        """Snapshot including the rng stream and the frozen worker pool.

        ``error_rate`` is stored *post*-qualification (the ctor already
        applied the 0.7× screen) and ``worker_errors`` as drawn, so restore
        reproduces the instance without replaying ctor-time rng draws.

        Returns:
            A dict of plain JSON types.
        """
        state = super().state_dict()
        state.update(
            error_rate=float(self.error_rate),
            n_assignments=int(self.n_assignments),
            n_workers=(None if self.n_workers is None
                       else int(self.n_workers)),
            worker_errors=(None if self.worker_errors is None
                           else [float(e) for e in self.worker_errors]),
            rng=_rng_to_state(self.rng),
        )
        return state

    def load_state_dict(self, state: dict) -> None:
        """Restore a :meth:`state_dict` snapshot in place.

        Args:
            state: dict produced by :meth:`state_dict`.
        """
        super().load_state_dict(state)
        self.error_rate = float(state["error_rate"])
        self.n_assignments = int(state["n_assignments"])
        self.n_workers = (None if state["n_workers"] is None
                          else int(state["n_workers"]))
        we = state["worker_errors"]
        self.worker_errors = (None if we is None
                              else np.asarray(we, np.float64))
        self.rng = _rng_from_state(state["rng"])


def _require_odd(n_assignments: int) -> None:
    if n_assignments < 1 or n_assignments % 2 == 0:
        raise ValueError(
            f"n_assignments must be odd and positive, got {n_assignments}: "
            "an even vote can tie, and a tie silently resolves to the wrong "
            "label (majority is defined as n_true * 2 > k); the analytic "
            "pair_error_rate also assumes odd k")


class WorkerModel:
    """Streaming Dawid–Skene estimator on the binary match label space (§15).

    Tracks one symmetric error rate per worker as damped pseudo-counts and
    aggregates ballots by log-odds weighted voting: vote ``v`` from worker
    ``w`` contributes ``±log((1-e_w)/e_w)`` to the POS score.  Online
    updates are the EM M-step against the aggregate's own posterior (soft,
    confidence-weighted), damped by a Beta prior of ``strength``
    pseudo-votes at ``prior_error`` so early ballots cannot saturate an
    estimate; :meth:`refit` runs full batch EM over every recorded ballot
    when convergence matters more than latency.

    Args:
        prior_error: prior mean error rate for an unseen worker.
        strength: prior weight in pseudo-votes (damping for streaming).
        min_error / max_error: clip range keeping log-odds weights finite.

    Example::

        >>> model = WorkerModel()
        >>> label = model.record(votes=(POS, POS, NEG), workers=(0, 1, 2))
        >>> label == POS  # uninformed weights reduce to majority
        True
    """

    def __init__(self, prior_error: float = 0.15, strength: float = 8.0,
                 min_error: float = 0.005, max_error: float = 0.45):
        if not 0.0 < prior_error < 0.5:
            raise ValueError(
                f"prior_error must be in (0, 0.5), got {prior_error}: at "
                "0.5 a worker carries no information and above it the "
                "weights invert")
        self.prior_error = prior_error
        self.strength = strength
        self.min_error = min_error
        self.max_error = max_error
        self._n: Dict[int, float] = {}        # soft vote counts per worker
        self._wrong: Dict[int, float] = {}    # soft error counts per worker
        self._ballots: List[Tuple[Tuple[int, ...], Tuple[int, ...]]] = []

    @property
    def workers(self) -> List[int]:
        """Ids of every worker seen so far, ascending."""
        return sorted(self._n)

    def n_votes(self, worker: int) -> float:
        """Soft count of votes recorded for ``worker``.

        Args:
            worker: stable worker id.

        Returns:
            Accumulated (fractional) vote count, 0.0 for unseen workers.
        """
        return self._n.get(int(worker), 0.0)

    def error_rate(self, worker: int) -> float:
        """Posterior-mean error estimate for one worker.

        Args:
            worker: stable worker id.

        Returns:
            ``(wrong + prior_error*strength) / (n + strength)``, clipped to
            ``[min_error, max_error]`` — unseen workers sit at the prior.
        """
        w = int(worker)
        e = ((self._wrong.get(w, 0.0) + self.prior_error * self.strength)
             / (self._n.get(w, 0.0) + self.strength))
        return float(min(max(e, self.min_error), self.max_error))

    def weight(self, worker: int) -> float:
        """Log-odds voting weight of one worker.

        Args:
            worker: stable worker id.

        Returns:
            ``log((1 - e) / e)`` for the worker's estimated error ``e`` —
            always positive (errors are clipped below 0.5), larger for more
            reliable workers.
        """
        e = self.error_rate(worker)
        return math.log((1.0 - e) / e)

    def score(self, votes: Sequence[int], workers: Sequence[int]) -> float:
        """Weighted POS log-odds of one ballot.

        Args:
            votes: engine-encoded POS/NEG votes.
            workers: worker ids aligned with ``votes``.

        Returns:
            Sum of signed per-worker weights; positive favours POS.
        """
        return sum((1.0 if v == POS else -1.0) * self.weight(w)
                   for v, w in zip(votes, workers))

    def aggregate(self, votes: Sequence[int],
                  workers: Sequence[int]) -> int:
        """Collapse a ballot to one engine label by weighted voting.

        Args:
            votes: engine-encoded POS/NEG votes.
            workers: worker ids aligned with ``votes``.

        Returns:
            POS or NEG.  An exactly tied weighted score falls back to the
            unweighted majority; a still-tied (even) ballot resolves NEG —
            the conservative default, matching the engine's pessimism about
            unproven matches.
        """
        s = self.score(votes, workers)
        if abs(s) > 1e-12:
            return POS if s > 0 else NEG
        n_pos = sum(v == POS for v in votes)
        return POS if 2 * n_pos > len(list(votes)) else NEG

    def record(self, votes: Sequence[int], workers: Sequence[int]) -> int:
        """Aggregate a ballot and fold it into the running estimates.

        The online M-step: the aggregated label's posterior confidence
        ``c = sigmoid(|score|)`` soft-assigns each vote ``c`` units of
        right/wrong evidence (and ``1-c`` of the opposite), so a coin-flip
        ballot moves no estimate while a confident one moves them almost a
        full vote.  The ballot is also stored for :meth:`refit`.

        Args:
            votes: engine-encoded POS/NEG votes.
            workers: worker ids aligned with ``votes``.

        Returns:
            The aggregated engine label (same as :meth:`aggregate`).
        """
        votes = tuple(int(v) for v in votes)
        workers = tuple(int(w) for w in workers)
        label = self.aggregate(votes, workers)
        conf = 1.0 / (1.0 + math.exp(-abs(self.score(votes, workers))))
        for v, w in zip(votes, workers):
            self._n[w] = self._n.get(w, 0.0) + 1.0
            wrong = conf if v != label else 1.0 - conf
            self._wrong[w] = self._wrong.get(w, 0.0) + wrong
        self._ballots.append((votes, workers))
        return label

    def refit(self, iters: int = 25) -> None:
        """Full Dawid–Skene EM over every recorded ballot.

        Re-estimates all error rates from scratch: the E-step computes each
        ballot's POS posterior under the current estimates (uniform class
        prior), the M-step recomputes soft right/wrong counts from those
        posteriors.  Replaces the streaming counts in place — call when a
        batch of ballots has landed and estimate quality matters (e.g.
        before routing a cluster task to the "best" worker).

        Args:
            iters: EM iterations (the binary model converges in a few).
        """
        if not self._ballots:
            return
        for _ in range(iters):
            n: Dict[int, float] = {}
            wrong: Dict[int, float] = {}
            for votes, workers in self._ballots:
                s = self.score(votes, workers)
                p_pos = 1.0 / (1.0 + math.exp(-s))
                for v, w in zip(votes, workers):
                    n[w] = n.get(w, 0.0) + 1.0
                    wrong[w] = wrong.get(w, 0.0) + (
                        p_pos if v == NEG else 1.0 - p_pos)
            self._n, self._wrong = n, wrong

    def best_workers(self, limit: int = 8,
                     min_votes: float = 4.0) -> List[int]:
        """Most trusted workers with enough history, best first.

        Args:
            limit: maximum ids to return.
            min_votes: minimum soft vote count before a worker qualifies
                (prior-dominated estimates are not trust).

        Returns:
            Up to ``limit`` worker ids sorted by ascending estimated error;
            empty while no worker has ``min_votes`` of history — callers
            fall back to platform-assigned workers.
        """
        ranked = sorted(
            (w for w, c in self._n.items() if c >= min_votes),
            key=lambda w: (self.error_rate(w), w))
        return ranked[:limit]

    def state_dict(self) -> dict:
        """JSON-able snapshot: prior config, soft counts, recorded ballots.

        Returns:
            A dict of plain JSON types (worker-id keys stringified).
        """
        return {
            "prior_error": float(self.prior_error),
            "strength": float(self.strength),
            "min_error": float(self.min_error),
            "max_error": float(self.max_error),
            "n": {str(w): float(c) for w, c in self._n.items()},
            "wrong": {str(w): float(c) for w, c in self._wrong.items()},
            "ballots": [[list(map(int, votes)), list(map(int, workers))]
                        for votes, workers in self._ballots],
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a :meth:`state_dict` snapshot in place.

        Args:
            state: dict produced by :meth:`state_dict`.
        """
        self.prior_error = float(state["prior_error"])
        self.strength = float(state["strength"])
        self.min_error = float(state["min_error"])
        self.max_error = float(state["max_error"])
        self._n = {int(w): float(c) for w, c in state["n"].items()}
        self._wrong = {int(w): float(c) for w, c in state["wrong"].items()}
        self._ballots = [(tuple(votes), tuple(workers))
                         for votes, workers in state["ballots"]]


@dataclasses.dataclass
class CostModel:
    """AMT accounting of §6.4: 2 cents/assignment, 20 pairs per HIT, 3
    assignments per HIT.  Cluster tasks (§15) price by object count: a
    CrowdER-style cluster HIT shows ``cluster_objects_per_assignment``
    objects for one assignment's price, so a k-object task costs
    ``k / cluster_objects_per_assignment`` assignments (floor one).
    """

    cents_per_assignment: float = 2.0
    pairs_per_hit: int = 20
    assignments_per_hit: int = 3
    cluster_objects_per_assignment: float = 5.0

    def n_hits(self, n_pairs: int) -> int:
        """HITs needed to cover ``n_pairs`` at ``pairs_per_hit`` each.

        Args:
            n_pairs: pair questions to batch.

        Returns:
            Ceiling HIT count.
        """
        return math.ceil(n_pairs / self.pairs_per_hit)

    def cost_cents(self, n_pairs: int) -> float:
        """Total §6.4 price of ``n_pairs`` pair questions.

        Args:
            n_pairs: pair questions to batch.

        Returns:
            ``n_hits * assignments_per_hit * cents_per_assignment``.
        """
        return self.n_hits(n_pairs) * self.assignments_per_hit * self.cents_per_assignment

    def cluster_task_cents(self, n_objects: int,
                           cents_per_assignment: Optional[float] = None
                           ) -> float:
        """Price of one k-object cluster task (§15).

        Args:
            n_objects: distinct objects shown to the worker.
            cents_per_assignment: rate override (defaults to the model's).

        Returns:
            ``rate * max(1, n_objects / cluster_objects_per_assignment)`` —
            a single worker's partition of k objects costs k/5 assignments
            by default, never less than one.
        """
        rate = (self.cents_per_assignment if cents_per_assignment is None
                else cents_per_assignment)
        return rate * max(1.0, n_objects / self.cluster_objects_per_assignment)


@dataclasses.dataclass
class LatencyModel:
    """Per-assignment completion latency (minutes), lognormal; a worker pool
    of ``n_workers`` draws available HIT-assignments (AMT assigns randomly)."""

    n_workers: int = 20
    mean_minutes: float = 30.0
    sigma: float = 1.0
    seed: int = 0

    def sampler(self) -> "np.random.Generator":
        """Fresh seeded rng for the event-driven simulator.

        Returns:
            A ``numpy.random.Generator`` seeded with ``seed``.
        """
        return np.random.default_rng(self.seed)

    def draw_minutes(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """Draw ``n`` lognormal completion times.

        Args:
            rng: generator (usually from :meth:`sampler`).
            n: number of draws.

        Returns:
            Array of ``n`` minutes with mean ``mean_minutes``.
        """
        mu = math.log(self.mean_minutes) - self.sigma**2 / 2
        return rng.lognormal(mu, self.sigma, size=n)


# ---------------------------------------------------------------------------
# CrowdGateway: batched, optionally-asynchronous crowd transport
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CrowdTicket:
    """Receipt for one posted batch of pairs (or one cluster task).

    Args (fields):
        tid: monotonically increasing ticket id.
        rid: request id the batch belongs to.
        indices: pair indices the ticket covers.
    """

    tid: int
    rid: int
    indices: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class CrowdAnswer:
    """One completed pair label, in engine encoding (POS / NEG).

    ``votes`` carries every per-assignment vote behind the label and
    ``workers`` the stable ids of who cast them (DESIGN.md §9/§15): the
    serving layer, the error-tolerance accounting and the reliability model
    all see the raw ballot, not just its collapse.  Cluster-decoded answers
    carry a single vote from the partitioning worker.
    """

    rid: int
    index: int
    label: int
    minutes: float      # simulated completion time (0.0 in immediate mode)
    votes: Tuple[int, ...] = ()   # per-assignment votes (POS / NEG)
    workers: Tuple[int, ...] = ()  # worker ids aligned with votes

    @property
    def n_assignments(self) -> int:
        """Number of assignments behind this answer."""
        return len(self.votes)

    @property
    def agreement(self) -> float:
        """Fraction of assignments that voted with the final label."""
        if not self.votes:
            return 1.0
        return sum(v == self.label for v in self.votes) / len(self.votes)


@dataclasses.dataclass
class _Task:
    # One unit of platform work a single worker picks up: a pair ballot
    # (singleton answers list) or a whole decoded cluster task.
    rid: int
    answers: List[Tuple[int, int, Tuple[int, ...], Tuple[int, ...]]]
    likelihood: float

    @property
    def n_answers(self) -> int:
        return len(self.answers)


@dataclasses.dataclass
class _Block:
    # One columnar post (immediate mode, deterministic crowd): the per-pair
    # tasks it stands for, in order — pair indices[j], label labels[j],
    # one vote by worker first_worker + j — built only when read.
    rid: int
    indices: np.ndarray
    labels: np.ndarray
    first_worker: int
    likelihood: np.ndarray

    @property
    def n_answers(self) -> int:
        return len(self.indices)

    @property
    def answers(self) -> List[Tuple[int, int, Tuple[int, ...],
                                    Tuple[int, ...]]]:
        w0 = self.first_worker
        return [(i, lab, (lab,), (w0 + j,)) for j, (i, lab) in
                enumerate(zip(self.indices.tolist(), self.labels.tolist()))]

    def tasks(self) -> List[_Task]:
        return [_Task(rid=self.rid, answers=[a], likelihood=lik)
                for a, lik in zip(self.answers, self.likelihood.tolist())]


class CrowdGateway:
    """Batched crowd transport with in-flight tracking (DESIGN.md §8).

    ``post(rid, pairs, indices, crowd) -> CrowdTicket`` hands a batch of
    candidate pairs to the platform; ``poll() -> [CrowdAnswer, ...]`` returns
    whatever has completed, and ``drain()`` blocks (advancing the simulated
    clock) until nothing is in flight.  Answers come back in engine encoding
    so the serving layer can fold them straight into a ``SessionState``.

    Two regimes:

    * ``latency=None`` — immediate mode: every posted pair's answer is
      available on the next ``poll`` at simulated time 0.  This is the
      transport for the round-barrier serving path; the per-pair
      ``crowd.ask`` loop lives here, batched per post, instead of in the
      service.  A ``post`` whose crowd answers in bulk
      (:meth:`Crowd.ask_bulk`) under majority aggregation skips that loop:
      the batch is held as arrays, with the same ledger, counters,
      seen workers and answers the loop would leave.
    * ``latency=LatencyModel`` — simulated asynchronous platform: a finite
      pool of ``latency.n_workers`` workers picks waiting tasks (uniformly at
      random, as AMT assigns — or lowest-likelihood-first when ``nf=True``,
      the §5.2 non-matching-first steering), each task completes after
      a lognormal number of minutes, and ``poll`` advances the clock to the
      next completion event.  ``now_minutes`` is the simulated wall clock.

    Vote aggregation (DESIGN.md §15): with ``aggregation="majority"`` (the
    default, bit-compatible with earlier revisions) each ballot collapses by
    unweighted majority.  With ``aggregation="em"`` the gateway owns a
    :class:`WorkerModel` and collapses ballots by reliability-weighted
    voting, updating the per-worker estimates online from every ballot.
    The model also routes work: requery escalations exclude workers already
    seen on the pair, and cluster tasks prefer the model's most trusted
    workers.

    Cluster tasks (§15): ``post_cluster`` posts one :class:`ClusterTask`
    whose decoded pair verdicts land together as ordinary answers.  A
    cluster task occupies one worker (one pickup in latency mode) and bills
    its task price, not per-pair assignments.  Cluster verdicts do NOT feed
    ``measured_disagreement`` or the worker model — a single worker's
    partition carries no inter-worker disagreement signal, and its k·(k−1)/2
    correlated verdicts would swamp the per-ballot statistics.

    Error tolerance (DESIGN.md §9): answers carry the per-assignment votes
    behind their label; ``requery(rid, pairs, indices, crowd)``
    re-posts pairs whose answers the engine rejected as contradictory, with
    an escalated assignment count (+2 per attempt: 3-way → 5-way) routed to
    fresh workers where the pool allows, and reports pairs past
    ``max_requeries`` as *exhausted* so the caller can fall back to trusting
    the graph.  ``measured_disagreement`` aggregates minority-vote fractions
    across every posted pair ballot — the empirical error signal a real
    platform can observe without ground truth.
    """

    def __init__(self, latency: Optional[LatencyModel] = None,
                 nf: bool = False, max_requeries: int = 1,
                 aggregation: str = "majority"):
        if latency is not None and latency.n_workers <= 0:
            raise ValueError(
                f"CrowdGateway needs a positive worker pool, got "
                f"n_workers={latency.n_workers} — in-flight pairs could "
                "never complete")
        if nf and latency is None:
            raise ValueError(
                "nf=True requires a LatencyModel: non-matching-first steers "
                "which waiting pair a worker picks up next, and the "
                "immediate-mode poll answers everything at once, so the "
                "steering would be a silent no-op")
        if aggregation not in ("majority", "em"):
            raise ValueError(
                f"aggregation must be 'majority' or 'em', got "
                f"{aggregation!r}")
        self.latency = latency
        self.nf = nf
        self.max_requeries = max_requeries
        self.aggregation = aggregation
        self.worker_model = WorkerModel() if aggregation == "em" else None
        # randomness (worker pick + assignment latency) exists only in
        # latency mode and is seeded by the LatencyModel
        self._rng = latency.sampler() if latency is not None else None
        # waiting: posted, not yet picked up by a worker (immediate mode:
        # not yet polled).
        self._waiting: List = []   # _Task and _Block, in post order
        self._block_extra = 0   # pairs in waiting blocks less their count
        # running: (t_done, seq, task) min-heap on t_done
        self._running: List[Tuple[float, int, _Task]] = []
        self._free_workers = latency.n_workers if latency is not None else 0
        self._now = 0.0
        self._seq = 0
        self._next_tid = 0
        # requery bookkeeping: attempts per (rid, index); worker routing:
        # ids already seen per (rid, index)
        self._attempts: dict = {}
        self._seen: Dict[Tuple[int, int], set] = {}
        # seen workers of columnar posts, per rid: (indices, first worker),
        # folded into _seen when read
        self._seen_bulk: Dict[int, List[Tuple[np.ndarray, int]]] = {}
        self.n_posted = 0
        self.n_answered = 0
        self.n_requeried = 0
        self.n_votes = 0
        self.n_minority_votes = 0
        self.n_cluster_tasks = 0
        self.n_cluster_pairs = 0
        self._cluster_pairs: Dict[int, int] = {}
        # per-request cost accounting (DESIGN.md §10): every assignment a
        # post/requery buys is priced at the caller's per-assignment rate,
        # so budget-capped sessions can check spend before publishing more
        self._spent_cents: dict = {}
        self._assignments: dict = {}

    def spent_cents(self, rid: int) -> float:
        """Cents spent on a request so far (assignment-level accounting).

        Args:
            rid: request id.

        Returns:
            Running spend in cents, 0.0 for unknown requests.
        """
        return self._spent_cents.get(rid, 0.0)

    def cluster_pairs(self, rid: int) -> int:
        """Pair verdicts a request resolved through cluster-task agreement.

        Disagreement escalations are excluded — those pairs were answered
        (and billed) as ordinary pair ballots.

        Args:
            rid: request id.

        Returns:
            Agreed cluster pair count, 0 for unknown requests.
        """
        return self._cluster_pairs.get(rid, 0)

    def assignments_posted(self, rid: int) -> int:
        """Total crowd assignments bought for a request so far.

        Cluster tasks count as one assignment per partitioning worker —
        not per decoded pair verdict.

        Args:
            rid: request id.

        Returns:
            Assignment count, 0 for unknown requests.
        """
        return self._assignments.get(rid, 0)

    @property
    def now_minutes(self) -> float:
        """Simulated platform wall clock in minutes."""
        return self._now

    @property
    def in_flight(self) -> int:
        """Tasks posted but not yet answered (waiting + running)."""
        return len(self._waiting) + self._block_extra + len(self._running)

    @property
    def measured_disagreement(self) -> float:
        """Observed minority-vote fraction over all posted pair ballots —
        the empirical counterpart of
        :meth:`NoisyCrowd.expected_minority_fraction`.  Cluster verdicts are
        excluded: a single worker's partition has no minority."""
        return self.n_minority_votes / max(self.n_votes, 1)

    def seen_workers(self, rid: int, index: int) -> Tuple[int, ...]:
        """Workers who have already answered a pair, ascending.

        Args:
            rid: request id.
            index: pair index.

        Returns:
            Sorted worker ids; empty for never-posted pairs.
        """
        if rid in self._seen_bulk:
            self._fold_seen(rid)
        return tuple(sorted(self._seen.get((rid, int(index)), ())))

    def _fold_seen(self, rid: int) -> None:
        for idx, first in self._seen_bulk.pop(rid):
            for j, i in enumerate(idx.tolist()):
                self._seen.setdefault((rid, i), set()).add(first + j)

    def _enqueue(self, rid: int, pairs: PairSet, indices, crowd: Crowd,
                 n_assignments: Optional[int] = None,
                 cents_per_assignment: float = 0.0) -> Tuple[int, ...]:
        indices = tuple(int(i) for i in indices)
        gateway_ballot_pairs.add(len(indices))
        for i in indices:
            ballot = crowd.ask_ballot(
                pairs, i, n_assignments,
                exclude=self.seen_workers(rid, i))
            if self.worker_model is not None:
                label = self.worker_model.record(ballot.votes, ballot.workers)
            else:
                label = POS if ballot.label == MATCH else NEG
            self._seen.setdefault((rid, i), set()).update(ballot.workers)
            self.n_votes += len(ballot.votes)
            self.n_minority_votes += sum(v != label for v in ballot.votes)
            self._assignments[rid] = (self._assignments.get(rid, 0)
                                      + len(ballot.votes))
            self._spent_cents[rid] = (self._spent_cents.get(rid, 0.0)
                                      + cents_per_assignment
                                      * len(ballot.votes))
            self._waiting.append(_Task(
                rid=rid,
                answers=[(i, label, ballot.votes, ballot.workers)],
                likelihood=float(pairs.likelihood[i])))
        self.n_posted += len(indices)
        if self.latency is not None:
            self._assign()
        return indices

    def _enqueue_bulk(self, rid: int, pairs: PairSet, indices, crowd: Crowd,
                      answers: Optional[np.ndarray],
                      cents_per_assignment: float
                      ) -> Optional[Tuple[int, ...]]:
        """The per-pair loop's effects for one post, as arrays; ``None``
        (nothing done) where the loop must run.  Each pair is one unanimous
        vote by a fresh worker, so the ledger depends only on the indices."""
        if self.latency is not None or self.worker_model is not None:
            return None
        idx = np.array(indices, np.int64)
        if not len(idx):
            return None
        got = crowd.ask_bulk(pairs, idx, answers)
        if got is None:
            return None
        labels, first = got
        n = len(idx)
        self._waiting.append(_Block(
            rid=rid, indices=idx, labels=labels, first_worker=first,
            likelihood=np.asarray(pairs.likelihood)[idx]))
        self._block_extra += n - 1
        self._seen_bulk.setdefault(rid, []).append((idx, first))
        self.n_votes += n
        self._assignments[rid] = self._assignments.get(rid, 0) + n
        # n in-order additions, as the loop makes them (cumsum accumulates
        # in order; a pairwise sum could differ in the last bit)
        spend = np.full(n + 1, cents_per_assignment, np.float64)
        spend[0] = self._spent_cents.get(rid, 0.0)
        self._spent_cents[rid] = float(np.cumsum(spend)[-1])
        self.n_posted += n
        gateway_bulk_pairs.add(n)
        return tuple(idx.tolist())

    def post(self, rid: int, pairs: PairSet, indices, crowd: Crowd,
             cents_per_assignment: float = 0.0,
             answers: Optional[np.ndarray] = None) -> CrowdTicket:
        """Post a batch of pair indices; the crowd is asked per pair here
        (batched transport), answers surface later via ``poll``.  Each
        assignment bought is charged at ``cents_per_assignment`` against the
        request's running spend (``spent_cents``).  In immediate mode under
        majority aggregation, a crowd that answers in bulk
        (:meth:`Crowd.ask_bulk`) is asked once for the whole batch, which
        is held as arrays; the ledger and the answers are the same.

        Args:
            rid: request id the batch belongs to.
            pairs: the candidate pair set.
            indices: pair indices to post.
            cents_per_assignment: billing rate for spend accounting.
            crowd: the :class:`Crowd` to ask.
            answers: the crowd's ``precomputed_answers`` at ``indices``,
                when the caller holds them.

        Returns:
            A :class:`CrowdTicket` over the posted indices.
        """
        posted = self._enqueue_bulk(rid, pairs, indices, crowd, answers,
                                    cents_per_assignment)
        if posted is None:
            posted = self._enqueue(rid, pairs, indices, crowd,
                                   cents_per_assignment=cents_per_assignment)
        tid = self._next_tid
        self._next_tid += 1
        return CrowdTicket(tid=tid, rid=rid, indices=posted)

    def post_cluster(self, rid: int, pairs: PairSet, indices, crowd: Crowd,
                     cents: float = 0.0, n_assignments: int = 1,
                     pair_cents_per_assignment: float = 0.0) -> CrowdTicket:
        """Post one :class:`ClusterTask` covering ``indices`` (§15).

        ``n_assignments`` distinct workers — the reliability model's most
        trusted candidates when EM aggregation is on, otherwise
        platform-assigned — each partition the objects behind the covered
        pairs.  Pair verdicts all assignments agree on land together as one
        multi-vote :class:`CrowdAnswer` batch; disagreed pairs escalate on
        the spot to ordinary per-pair ballots (billed at
        ``pair_cents_per_assignment``), so every covered index is answered
        exactly once and nothing deadlocks in flight.  The task itself bills
        ``cents`` total and ``n_assignments`` assignments, regardless of how
        many pair verdicts the partitions decode to.

        Args:
            rid: request id the task belongs to.
            pairs: the candidate pair set.
            indices: covered pair indices (endpoints span the object set).
            crowd: the :class:`Crowd` to ask (must implement
                :meth:`Crowd.ask_cluster`).
            cents: total task price (all assignments) for spend accounting.
            n_assignments: distinct workers asked to partition the task.
            pair_cents_per_assignment: billing rate for escalated
                disagreement ballots.

        Returns:
            A :class:`CrowdTicket` over the covered indices.
        """
        indices = tuple(int(i) for i in indices)
        prefer: Tuple[int, ...] = ()
        if self.worker_model is not None:
            prefer = tuple(self.worker_model.best_workers())
        verdicts: List[Tuple[Tuple[int, ...], int]] = []
        for _ in range(max(1, int(n_assignments))):
            asked = tuple(w for _, w in verdicts)
            labels, worker = crowd.ask_cluster(
                pairs, indices,
                prefer=tuple(w for w in prefer if w not in asked),
                exclude=asked)
            verdicts.append((labels, int(worker)))
        workers = tuple(w for _, w in verdicts)
        answers = []
        escalate = []
        for j, i in enumerate(indices):
            votes = tuple(int(lab[j]) for lab, _ in verdicts)
            if all(v == votes[0] for v in votes):
                answers.append((i, votes[0], votes, workers))
            else:
                escalate.append(i)
        for i in indices:
            self._seen.setdefault((rid, i), set()).update(workers)
        self._assignments[rid] = (self._assignments.get(rid, 0)
                                  + len(verdicts))
        self._spent_cents[rid] = self._spent_cents.get(rid, 0.0) + cents
        self.n_posted += len(indices) - len(escalate)  # _enqueue counts those
        self.n_cluster_tasks += 1
        self.n_cluster_pairs += len(answers)
        self._cluster_pairs[rid] = (self._cluster_pairs.get(rid, 0)
                                    + len(answers))
        if answers:
            likelihood = float(min(
                float(pairs.likelihood[i]) for i, *_ in answers))
            self._waiting.append(
                _Task(rid=rid, answers=answers, likelihood=likelihood))
        if escalate:
            self._enqueue(rid, pairs, escalate, crowd,
                          cents_per_assignment=pair_cents_per_assignment)
        if self.latency is not None:
            self._assign()
        tid = self._next_tid
        self._next_tid += 1
        return CrowdTicket(tid=tid, rid=rid, indices=indices)

    def requery(self, rid: int, pairs: PairSet, indices, crowd: Crowd,
                cents_per_assignment: float = 0.0,
                budget_cents: Optional[float] = None
                ) -> Tuple[CrowdTicket, List[int]]:
        """Escalation path for rejected answers (DESIGN.md §9): re-post each
        pair with ``crowd.n_assignments + 2 * attempt`` assignments (3-way →
        5-way by default), routed to workers who have not yet answered the
        pair where the pool allows (§15).  Pairs already requeried
        ``max_requeries`` times are NOT re-posted; they come back in the
        second element — exhausted, for the caller to resolve by trusting
        the graph.  With ``budget_cents`` set, escalations the remaining
        budget cannot cover are not bought either (DESIGN.md §10) — they
        come back exhausted the same way, so a budgeted session never
        overspends on requeries.

        Args:
            rid: request id.
            pairs: the candidate pair set.
            indices: rejected pair indices to escalate.
            crowd: the :class:`Crowd` to ask.
            cents_per_assignment: billing rate for spend accounting.
            budget_cents: hard spend cap; unaffordable escalations exhaust.

        Returns:
            ``(ticket over the re-posted pairs, exhausted indices)``.
        """
        base = getattr(crowd, "n_assignments", 1)
        by_escalation: dict = {}
        exhausted: List[int] = []
        planned_cents = 0.0
        for i in (int(j) for j in indices):
            attempt = self._attempts.get((rid, i), 0)
            if attempt >= self.max_requeries:
                exhausted.append(i)
                continue
            k = base + 2 * (attempt + 1)
            cost = cents_per_assignment * k
            if budget_cents is not None and \
                    self.spent_cents(rid) + planned_cents + cost > \
                    budget_cents + 1e-9:
                exhausted.append(i)  # unaffordable: the graph outvotes
                continue
            planned_cents += cost
            self._attempts[(rid, i)] = attempt + 1
            by_escalation.setdefault(k, []).append(i)
        posted: List[int] = []
        for k, idx in sorted(by_escalation.items()):
            posted.extend(self._enqueue(
                rid, pairs, idx, crowd, n_assignments=k,
                cents_per_assignment=cents_per_assignment))
        self.n_requeried += len(posted)
        tid = self._next_tid
        self._next_tid += 1
        return CrowdTicket(tid=tid, rid=rid, indices=tuple(posted)), exhausted

    def _assign(self) -> None:
        """Free workers pick up waiting tasks (NF: lowest likelihood first)."""
        while self._free_workers > 0 and self._waiting:
            if self.nf:
                k = min(range(len(self._waiting)),
                        key=lambda j: (self._waiting[j].likelihood,
                                       self._waiting[j].rid,
                                       self._waiting[j].answers[0][0]))
            else:
                k = int(self._rng.integers(len(self._waiting)))
            task = self._waiting.pop(k)
            dt = float(self.latency.draw_minutes(self._rng, 1)[0])
            heapq.heappush(self._running, (self._now + dt, self._seq, task))
            self._seq += 1
            self._free_workers -= 1

    def poll(self, collect: bool = True) -> List[CrowdAnswer]:
        """Surface completed answers.

        Immediate mode returns everything posted at simulated time 0.
        Latency mode advances the clock to the next completion event and
        returns the answers landing there (freed workers immediately pick
        up waiting tasks).  A cluster task's decoded verdicts land together
        at its single completion time.

        Args:
            collect: ``False`` consumes and counts the answers without
                building them, for a caller that does not read them.

        Returns:
            A list of :class:`CrowdAnswer` (possibly empty; always empty
            without ``collect``).
        """
        if self.latency is None:
            n = sum(t.n_answers for t in self._waiting)
            out = [CrowdAnswer(t.rid, i, lab, self._now, votes, workers)
                   for t in self._waiting
                   for i, lab, votes, workers in t.answers] if collect else []
            self._waiting.clear()
            self._block_extra = 0
            self.n_answered += n
            return out
        if not self._running:
            return []
        t0 = self._running[0][0]
        out: List[CrowdAnswer] = []
        while self._running and self._running[0][0] <= t0 + 1e-12:
            t, _, task = heapq.heappop(self._running)
            out.extend(CrowdAnswer(task.rid, i, lab, t, votes, workers)
                       for i, lab, votes, workers in task.answers)
            self._free_workers += 1
        self._now = max(self._now, t0)
        self._assign()
        self.n_answered += len(out)
        return out if collect else []

    def drain(self, collect: bool = True) -> List[CrowdAnswer]:
        """Poll until nothing is in flight (the round-barrier transport).

        Args:
            collect: ``False`` consumes the answers without building them
                (see :meth:`poll`).

        Returns:
            Every outstanding :class:`CrowdAnswer`, completion order.
        """
        out = self.poll(collect)
        while self.in_flight:
            out.extend(self.poll(collect))
        return out

    # -- persistence (DESIGN.md §16) ------------------------------------
    @staticmethod
    def _task_to_state(task: _Task) -> dict:
        return {"rid": int(task.rid),
                "likelihood": float(task.likelihood),
                "answers": [[int(i), int(lab), list(map(int, votes)),
                             list(map(int, workers))]
                            for i, lab, votes, workers in task.answers]}

    @staticmethod
    def _task_from_state(d: dict) -> _Task:
        return _Task(
            rid=int(d["rid"]),
            answers=[(int(i), int(lab), tuple(votes), tuple(workers))
                     for i, lab, votes, workers in d["answers"]],
            likelihood=float(d["likelihood"]))

    def state_dict(self) -> dict:
        """JSON-able snapshot of everything the platform remembers.

        Captures in-flight tasks (waiting + running, with their already-
        drawn answers and completion times — the crowd was asked and billed
        at post time, so these are paid-for tickets the restored service
        must not buy again), per-request spend/assignment ledgers, requery
        and seen-worker bookkeeping, disagreement counters, the simulated
        clock, the worker-pick rng stream, and the §15 worker-reliability
        model.

        Returns:
            A dict of plain JSON types.
        """
        for rid in list(self._seen_bulk):
            self._fold_seen(rid)
        waiting = [task for t in self._waiting
                   for task in (t.tasks() if isinstance(t, _Block) else [t])]
        return {
            "now": float(self._now),
            "seq": int(self._seq),
            "next_tid": int(self._next_tid),
            "rng": (None if self._rng is None else _rng_to_state(self._rng)),
            "waiting": [self._task_to_state(t) for t in waiting],
            "running": [[float(t), int(s), self._task_to_state(task)]
                        for t, s, task in self._running],
            "attempts": [[int(rid), int(i), int(n)]
                         for (rid, i), n in sorted(self._attempts.items())],
            "seen": [[int(rid), int(i), sorted(int(w) for w in ws)]
                     for (rid, i), ws in sorted(self._seen.items())],
            "counters": {
                "n_posted": int(self.n_posted),
                "n_answered": int(self.n_answered),
                "n_requeried": int(self.n_requeried),
                "n_votes": int(self.n_votes),
                "n_minority_votes": int(self.n_minority_votes),
                "n_cluster_tasks": int(self.n_cluster_tasks),
                "n_cluster_pairs": int(self.n_cluster_pairs),
            },
            "cluster_pairs": {str(r): int(n)
                              for r, n in self._cluster_pairs.items()},
            "spent_cents": {str(r): float(c)
                            for r, c in self._spent_cents.items()},
            "assignments": {str(r): int(n)
                            for r, n in self._assignments.items()},
            "worker_model": (None if self.worker_model is None
                             else self.worker_model.state_dict()),
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a :meth:`state_dict` snapshot into a gateway built with
        the same ``(latency, nf, aggregation)`` configuration.

        In-flight tickets are re-materialised exactly as checkpointed —
        waiting tasks back onto the platform queue, running tasks back onto
        the completion heap with their original finish times — and the
        worker pool's free count is recomputed, so the event stream (and
        therefore every label and every billed cent) continues as if the
        process had never died.

        Args:
            state: dict produced by :meth:`state_dict`.
        """
        self._now = float(state["now"])
        self._seq = int(state["seq"])
        self._next_tid = int(state["next_tid"])
        if state["rng"] is not None:
            self._rng = _rng_from_state(state["rng"])
        self._waiting = [self._task_from_state(d) for d in state["waiting"]]
        self._block_extra = 0
        self._running = [(float(t), int(s), self._task_from_state(d))
                         for t, s, d in state["running"]]
        heapq.heapify(self._running)
        if self.latency is not None:
            self._free_workers = self.latency.n_workers - len(self._running)
        self._attempts = {(int(rid), int(i)): int(n)
                          for rid, i, n in state["attempts"]}
        self._seen = {(int(rid), int(i)): set(ws)
                      for rid, i, ws in state["seen"]}
        self._seen_bulk = {}
        for k, v in state["counters"].items():
            setattr(self, k, int(v))
        self._cluster_pairs = {int(r): int(n)
                               for r, n in state["cluster_pairs"].items()}
        self._spent_cents = {int(r): float(c)
                             for r, c in state["spent_cents"].items()}
        self._assignments = {int(r): int(n)
                             for r, n in state["assignments"].items()}
        if state["worker_model"] is not None:
            if self.worker_model is None:
                self.worker_model = WorkerModel()
            self.worker_model.load_state_dict(state["worker_model"])
