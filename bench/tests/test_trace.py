"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (``data/small.xplane.pb``: a 50 ms host sleep and
three calls of ``matmul_step`` inside ``bench.submit``, then two calls of
``sort_step`` inside ``bench.run``)."""
from __future__ import annotations

import os

import pytest

from bench import trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


def test_union_and_gaps_by_hand():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert trace.union_length(iv) == pytest.approx(3.0)
    assert trace.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                         (4.0, 5.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_names():
    assert trace.base_name("jit_pair_scores_compact(1124)") == \
        "jit_pair_scores_compact"
    assert trace.base_name("fusion.3") == "fusion"
    hlo = ('%pair_scores.1 = (f32[2560,22272]) custom-call(f32[2560,300] '
           '%pad.0), custom_call_target="tpu_custom_call"')
    assert trace.instruction(hlo) == ("pair_scores.1", True)
    assert trace.instruction("%sort.0 = s32[8] sort(s32[8] %x)") == \
        ("sort.0", False)


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(SMALL)


def test_recorded_trace_busy_and_idle_add_up(small):
    assert small.n_devices == 1
    assert 0.0 < small.busy_s < small.window_s
    idle = sum(s for _, s in small.idle)
    assert small.busy_s + idle == pytest.approx(small.window_s, rel=1e-6)
    assert 0.0 < small.idle_share < 1.0


def test_recorded_trace_names_the_host_sleep(small):
    longest = max(small.idle, key=lambda g: g[1])
    assert longest[0].startswith("bench.submit")
    assert longest[1] >= 0.045   # the 50 ms sleep, less clock offsets


def test_recorded_trace_device_time_by_program(small):
    mods = small.module_time()
    assert mods["jit_matmul_step"] > 0 and mods["jit_sort_step"] > 0
    assert sum(mods.values()) <= small.window_s
    names = [n for n, _ in small.modules]
    assert names.count("jit_matmul_step") == 3
    assert names.count("jit_sort_step") == 2
    top = small.breakdown()
    assert {n for n, _ in top["device_ops"]} >= {"jit_matmul_step",
                                                 "jit_sort_step"}
    assert len(top["idle_gaps"]) <= 10
