"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip, drives the rest of a run on
the CPU at a tiny size with a broken ``JoinService`` in the program's
place, and expects ``correct`` false: once for each fault the cells can
have (one chip: no exchange between chips to leave out)."""
from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import tiny_cell
from repro.serve.join_service import JoinService

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
EMBEDDED = [c for c in CELLS if c.startswith("wa.")]


class _NewResults(JoinService):
    """Hands each ``run()``'s new results to ``corrupt`` first."""

    def run(self):
        seen = getattr(self, "_seen", set())
        res = super().run()
        new = [rid for rid in res if rid not in seen]
        self._seen = seen | set(new)
        return self.corrupt(res, new)


class AnswerAltered(_NewResults):
    """One label of one session flipped where it is produced."""

    def corrupt(self, res, new):
        res[new[0]].labels[0] = ~res[new[0]].labels[0]
        return res


class HalfBatchLeftOut(_NewResults):
    """Half of the batch's sessions never delivered."""

    def corrupt(self, res, new):
        for rid in new[: len(new) // 2]:
            del res[rid]
        return res


class StateUnchanged(_NewResults):
    """Every session returned as it was opened: nothing labelled, nothing
    asked."""

    def corrupt(self, res, new):
        for rid in new:
            res[rid].labels[:] = False
            res[rid].crowdsourced[:] = False
        return res


class ScoreAltered(JoinService):
    """The machine phase hands on one candidate's likelihood moved by
    1e-3."""

    def submit_embeddings(self, *args, **kwargs):
        rid = super().submit_embeddings(*args, **kwargs)
        self.queue[-1].pairs.likelihood[0] += np.float32(1e-3)
        return rid


class BelowThreshold(JoinService):
    """The machine phase serves every pair down to 0.05 below the
    threshold, each with its right score."""

    def submit_embeddings(self, a, b, threshold, **kwargs):
        return super().submit_embeddings(a, b, threshold - 0.05, **kwargs)


FAULTS = [(c, f) for c in CELLS
          for f in (AnswerAltered, HalfBatchLeftOut, StateUnchanged)]
FAULTS += [(c, f) for c in EMBEDDED for f in (ScoreAltered, BelowThreshold)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_path_is_not_correct(name, fault):
    line = harness.run(tiny_cell(name), 2 ** 31 + 21, 0.5, False,
                       time.perf_counter(), require_chip=False,
                       service_cls=fault)
    assert line["correct"] is False
    assert line["failed"] > 0
