"""A configuration, a traffic mix and a metric that a later change adds as
new files only are found by name, without an edit to the harness."""
from __future__ import annotations

import json
import time

from bench import harness

TOY_CONFIG = {
    "name": "toy", "kind": "pairs", "source": "test", "reduced": [],
    "cost": {"cents_per_assignment": 2.0},
}
TOY_GENERATOR = '''
import numpy as np

def pool(spec, seed, n):
    out = []
    for k in range(n):
        rng = np.random.default_rng([seed, k])
        ent = rng.integers(0, 6, 30)
        u, v = np.triu_indices(30, 1)
        keep = rng.random(len(u)) < 0.3
        u, v = u[keep].astype(np.int32), v[keep].astype(np.int32)
        truth = ent[u] == ent[v]
        lik = np.where(truth, 0.8, 0.3) + 0.1 * rng.random(len(u))
        out.append({"u": u, "v": v, "likelihood": lik.astype(np.float32),
                    "truth": truth, "n_objects": 30,
                    "total_true_matches": int(truth.sum())})
    return out
'''
TOY_TRAFFIC = {"crowd": {"kind": "perfect"}, "submit": "pairs", "lanes": 2,
               "batch": 2, "pool": 2}
TOY_METRIC = '''
def read(rec):
    return float(len(rec.served))
'''


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "configs" / "toy.py").write_text(TOY_GENERATOR)
    (bench / "traffic" / "toy_mix.json").write_text(json.dumps(TOY_TRAFFIC))
    (bench / "metrics" / "toy_sessions.py").write_text(TOY_METRIC)
    benchmark = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.mix", "config": "toy",
                       "traffic": "toy_mix", "chips": 1}],
        "end_to_end": [{"name": "toy_sessions", "unit": "sessions"}],
        "per_layer": [],
    }
    cell = harness.Cell.find("toy.mix", bench_dir=str(bench),
                             benchmark=benchmark)
    line = harness.run(cell, 5, 0.5, False, time.perf_counter(),
                       require_chip=False)
    assert line["correct"], line["checks"]
    assert line["metrics"]["toy_sessions"]["unit"] == "sessions"
    assert line["metrics"]["toy_sessions"]["value"] == line["attempted"]
