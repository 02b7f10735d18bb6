"""Spans and counters of the join service (DESIGN.md §8).

Counters (:class:`Counter`) are always on.  ``engine_dispatches`` counts
the round engine's host->device dispatches (compiled-function launches
plus host-array uploads); ``host_syncs`` counts the device->host reads of
the served path, each made through :func:`to_host`; ``wide_key_lanes``
counts lanes opened with two-word pair keys (object universes past
46,340 ids).  Readers take the difference of two readings.

Spans are off by default: :func:`span` then returns one shared null context
and records nothing, so the served path pays one flag check per span.
After :func:`enable`, each span records its name, start and end
(``time.perf_counter_ns``), the span open around it and its request id in
memory (:func:`spans`), and enters ``jax.profiler.TraceAnnotation(name)``:
inside a profiler trace it lands on the ``/host:CPU`` plane, on the clock
of the device's ops.  :func:`enable` also records an anchor, a
``perf_counter_ns`` reading taken inside a ``join.clock`` annotation; call
it after ``jax.profiler.start_trace`` and the in-memory spans move onto the
trace's clock by one offset (the ``join.clock`` event's start less
:func:`anchor_ns`).

Spans nest on the thread that serves (the service is single-threaded);
every name starts with ``join.``.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, NamedTuple, Optional

import jax
import numpy as np


class Counter:
    """A running count; always on."""

    __slots__ = ("count",)

    def __init__(self) -> None:
        self.count = 0

    def add(self, n: int = 1) -> None:
        self.count += n

    def reset(self) -> None:
        self.count = 0


engine_dispatches = Counter()
host_syncs = Counter()
wide_key_lanes = Counter()


def to_host(x):
    """``x`` read to the host, counted once in ``host_syncs``: a
    ``jax.Array`` through ``np.asarray``, a tuple or list of them through
    one ``jax.device_get``.  A value already on the host passes through
    uncounted."""
    if isinstance(x, jax.Array):
        host_syncs.add()
        return np.asarray(x)
    if isinstance(x, (tuple, list)) and any(isinstance(v, jax.Array)
                                            for v in x):
        host_syncs.add()
        return jax.device_get(x)
    return x


class Span(NamedTuple):
    """One closed span; times in ``perf_counter_ns``."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]   # id of the span open around it
    rid: Optional[int]      # its request id, or its nearest ancestor's


class _Recorder:
    def __init__(self) -> None:
        self.on = False
        self.closed: List[tuple] = []
        self.open: List["_Span"] = []
        self.next_id = 0
        self.anchor_ns: Optional[int] = None


_REC = _Recorder()
_NULL = contextlib.nullcontext()


class _Span:
    __slots__ = ("id", "name", "rid", "parent", "start", "annotation")

    def __init__(self, name: str, rid: Optional[int]):
        self.name = name
        self.rid = rid

    def __enter__(self) -> "_Span":
        self.id = _REC.next_id
        _REC.next_id += 1
        self.parent = _REC.open[-1].id if _REC.open else None
        _REC.open.append(self)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _REC.open.pop()
        _REC.closed.append((self.id, self.name, self.start, end,
                            self.parent, self.rid))


def span(name: str, rid: Optional[int] = None):
    """Context manager timing one stage of the service as ``name``, for
    request ``rid`` if it serves one; a shared null context while off."""
    if not _REC.on:
        return _NULL
    return _Span(name, rid)


def set_rid(rid: int) -> None:
    """Give the innermost open span the request id ``rid``: for a span
    opened before its request had one."""
    if _REC.on and _REC.open:
        _REC.open[-1].rid = rid


def enable() -> None:
    """Turn spans on and record the clock anchor."""
    _REC.on = True
    with jax.profiler.TraceAnnotation("join.clock"):
        _REC.anchor_ns = time.perf_counter_ns()


def disable() -> None:
    _REC.on = False


def reset() -> None:
    """Forget the closed spans (call with no span open)."""
    _REC.closed.clear()
    _REC.next_id = 0


def anchor_ns() -> Optional[int]:
    """``perf_counter_ns`` inside the last ``join.clock`` annotation."""
    return _REC.anchor_ns


def spans() -> List[Span]:
    """The spans closed since the last :func:`reset`, in the order they
    opened.  A span with no request id of its own takes its nearest
    closed ancestor's."""
    out: dict = {}
    for sid, name, start, end, parent, rid in sorted(_REC.closed):
        if rid is None and parent in out:
            rid = out[parent].rid
        out[sid] = Span(sid, name, start, end, parent, rid)
    return list(out.values())
