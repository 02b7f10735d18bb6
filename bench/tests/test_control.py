"""The control, the plain reference with one stated guarantee broken, put
in the program's place, must come out not correct (at a size a test run
holds; ``bench/control.py`` reads it at the cells' own size on the chip)."""
from __future__ import annotations

import pytest

from bench import check, control, harness
from bench.tests.tiny import tiny_cell


def test_positive_only_deduction_is_refused():
    cell = tiny_cell("cora.perfect")
    pool = harness.make_pool(cell, 2 ** 31 + 31)
    checks = check.judge(cell, check.readings(
        cell, pool, control.control_served(cell, pool)))
    assert checks["crowdsourced_mismatches"]["value"] > 0
    assert not all(c["ok"] for c in checks.values())


@pytest.mark.parametrize("name", ["wa.dense", "wa.blocked"])
def test_three_pass_scores_are_refused(name):
    cell = tiny_cell(name)
    cell.config = {**cell.config, "n_a": 256, "n_b": 2048, "n_matches": 96,
                   "dim": 300}
    pool = harness.make_pool(cell, 2 ** 31 + 33)
    checks = check.judge(cell, check.readings(
        cell, pool, control.control_served(cell, pool)))
    assert not checks["score_gap"]["ok"]
