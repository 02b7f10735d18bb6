"""Idle attribution to the program's ``join.*`` spans and the numbers read
from its spans and counters: by hand, on a hand-built ``Record``, and on a
small trace recorded on a TPU v5e (``data/spans.xplane.pb.gz``, written
by ``python3 bench/spans.py --sample`` and gzipped: two tiny sessions
through ``JoinService`` with spans on, one crowd answer held back 50 ms
inside ``join.gateway.post``)."""
from __future__ import annotations

import gzip
import os

import pytest

from bench import harness, spans, trace
from bench.tests.tiny import tiny_cell
from repro.obs import Span

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "spans.xplane.pb.gz")


def test_gaps_go_to_the_innermost_open_span():
    host = [("join.run", 0.0, 10.0), ("join.drive_fused", 1.0, 9.0),
            ("join.gateway.post", 2.0, 3.0)]
    gaps = [(2.2, 2.8), (4.0, 5.0), (9.5, 9.7), (11.0, 12.0)]
    got = dict(spans.credit_gaps(gaps, host))
    assert got == pytest.approx({"join.gateway.post": 0.6,
                                 "join.drive_fused": 1.0, "join.run": 0.2,
                                 "none": 1.0})
    assert spans.stage_share(list(got.items())) == pytest.approx(1.6 / 2.8)


def _span(i, name, t0, t1, rid=None, parent=None):
    return Span(i, name, int(t0 * 1e9), int(t1 * 1e9), parent, rid)


def test_numbers_from_spans_by_hand():
    got = [_span(0, "join.submit", 0.0, 5.0, rid=0),
           _span(1, "join.machine.signatures", 0.5, 1.5, rid=0, parent=0),
           _span(2, "join.machine.signatures", 1.5, 2.0, rid=0, parent=0),
           _span(3, "join.submit", 5.0, 9.0, rid=1),
           _span(4, "join.machine.signatures", 5.0, 7.0, rid=1, parent=3),
           _span(5, "join.run", 9.0, 12.0),
           _span(6, "join.gateway.post", 9.5, 10.0, rid=0, parent=5),
           _span(7, "join.gateway.drain", 10.0, 10.25, parent=5)]
    assert spans.lsh_signatures_s(got) == pytest.approx(1.75)
    assert spans.gateway_s_per_session(got, 2) == pytest.approx(0.375)
    assert spans.gateway_s_per_session(got[:5], 2) is None
    assert spans.lsh_signatures_s(got[5:]) is None


def _served(n_rounds):
    return harness.Served(pool_index=0, latency_s=1.0, n_pairs=1,
                          labels=None, crowdsourced=None, round_sizes=[],
                          n_rounds=n_rounds, spent_cents=0.0)


def _reduction(modules):
    return trace.Reduction(window_s=5.0, busy_s=1.0, ops=[],
                           modules=modules, idle=[], n_devices=1)


def test_engine_device_time_per_traced_round():
    """Two measured batches (10 and 20 rounds) then one traced batch of
    two sessions (3 + 5 rounds) holding 0.4 s of engine programs."""
    cell = tiny_cell("cora.perfect")
    reader = cell.reader("engine_device_s_per_round")
    rec = harness.Record(cell=cell, window_s=4.0)
    rec.spans = [("submit", 100.0, 100.1, 0), ("run", 100.1, 102.0, 1),
                 ("submit", 102.0, 102.1, 1), ("run", 102.1, 104.0, 1),
                 ("submit", 104.6, 104.7, 2), ("submit", 104.7, 104.8, 3),
                 ("run", 104.8, 106.0, 2)]
    rec.served = [_served(10), _served(20), _served(3), _served(5)]
    assert reader.read(rec) is None            # no trace
    rec.trace = _reduction([("jit_engine_run_rounds_batch", 0.3),
                            ("jit_engine_frontier", 0.1),
                            ("jit_gather", 7.0)])
    assert reader.read(rec) == pytest.approx(0.4 / 8)
    # a program without the engine's names reads nothing
    rec.trace = _reduction([("jit__run_rounds_batch", 0.3)])
    assert reader.read(rec) is None
    rec.trace = _reduction([("jit_engine_run_rounds_batch", 0.3)])
    rec.failed = 1
    assert reader.read(rec) is None


@pytest.fixture(scope="module")
def sample():
    from jax.profiler import ProfileData

    with gzip.open(SAMPLE) as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_idle_goes_to_the_held_back_post(sample):
    idle = spans.idle_by_span(sample)
    red = trace.reduce_profile(sample)
    assert sum(s for _, s in idle) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert all(n.startswith("join.") or n == "none" for n, _ in idle)
    name, held = idle[0]
    assert name == "join.gateway.post" and held >= 0.045
    assert spans.stage_share(idle) >= 0.8


def test_recorded_spans_name_the_engine_programs(sample):
    names = {n for n, _, _ in spans.host_spans(sample, "join.")}
    assert {"join.run", "join.drive_fused", "join.engine_dispatch",
            "join.gateway.post", "join.gateway.drain", "join.open_lane",
            "join.finalize", "join.submit", "join.admit"} <= names
    mods = trace.reduce_profile(sample).module_time()
    assert "jit_engine_run_rounds_batch" in mods
    assert not any(n == "jit_call" for n in mods)
