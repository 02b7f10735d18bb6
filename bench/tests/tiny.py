"""Tiny versions of the benchmark's cells, for rehearsals on the CPU."""
from __future__ import annotations

import copy

from bench import harness

TINY_CONFIG = {
    "cora": {"n_records": 200, "largest_cluster": 20},
    "walmart_amazon": {"n_a": 64, "n_b": 512, "n_matches": 24,
                       "dim": 32},
}
TINY_TRAFFIC = {"tiles_per_call": 8, "capacity": 1 << 14}


def tiny_cell(name: str, **find_kwargs) -> harness.Cell:
    """The cell ``name`` of ``BENCHMARK.json`` at a size the CPU serves in
    a second or two: fewer records and a narrower embedding."""
    cell = harness.Cell.find(name, **find_kwargs)
    cell.config = {**cell.config,
                   **TINY_CONFIG.get(cell.config["name"], {})}
    cell.traffic = copy.deepcopy(cell.traffic)
    if "machine" in cell.traffic:
        m = cell.traffic["machine"]
        for k, v in TINY_TRAFFIC.items():
            if k in m:
                m[k] = v
    return cell
