"""Each cell of BENCHMARK.json end to end on the CPU at a tiny size, the
last-line schema, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def run_tiny(name, trace=False, **kw):
    return harness.run(tiny_cell(name), 2 ** 31 + 11, 1.0, trace,
                       time.perf_counter(), require_chip=False, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_on_cpu(name):
    line = run_tiny(name)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in BENCHMARK["end_to_end"]
             if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == names


def test_last_line_schema():
    line = run_tiny(CELLS[0])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and bench/ present: no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
