"""Roofline arithmetic against hand-computed operations and bytes."""
from __future__ import annotations

import pytest

from bench import roofline


def test_dense_call_work():
    # Walmart-Amazon: 2,554 x 22,074 records at D = 300
    flops, nbytes = roofline.dense_work(2554, 22074, 300)
    assert flops == 2 * 2554 * 22074 * 300 == 33_826_197_600
    assert nbytes == 4 * (2554 * 300 + 22074 * 300 + 2554 * 22074) \
        == 255_061_584
    t, bound = roofline.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(255_061_584 / 819e9)


def test_compact_call_work():
    # one call of 256 tiles of 16 x 128 at D = 300
    flops, nbytes = roofline.compact_work(256, 16, 128, 300)
    assert flops == 2 * 256 * 16 * 128 * 300 == 314_572_800
    assert nbytes == 4 * 256 * (16 * 300 + 128 * 300 + 16 * 128) \
        == 46_333_952
    t, bound = roofline.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(46_333_952 / 819e9)
    # three calls that took 1 ms of device time together
    assert roofline.share((flops, nbytes), 3, 1e-3, "TPU v5 lite") == \
        pytest.approx(100 * 3 * 46_333_952 / 819e9 / 1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_time(1.0, 1.0, "TPU v99")
