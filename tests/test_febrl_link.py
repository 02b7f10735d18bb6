"""The person-registry linkage deployment (``bench/configs/febrl_link``) on
a 2x2 mesh: the dense machine phase's row-chunked compaction gives what one
argsort of the block gives, a tiny instance served through
``JoinService`` on four CPU devices agrees with ``bench/reference.py``,
and the cell's mesh metrics charge one device's block."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pair_scores import sharded
from repro.kernels.pair_scores.sharded import (_compact_by_rows,
                                               sharded_candidates)
from repro.launch.mesh import make_host_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("n,m,chunk_rows,capacity,i0,j0", [
    (37, 29, 5, 1000, 0, 0),   # ragged last chunk, room for everything
    (37, 29, 8, 50, 0, 0),     # overflow inside a middle chunk
    (64, 16, 16, 3, 0, 0),     # capacity below one chunk's candidates
    (40, 33, 7, 200, 0, 0),
    (9, 300, 1, 40, 0, 0),     # one row a chunk
    (37, 29, 37, 60, 0, 0),    # the whole block one chunk
    (37, 29, 5, 120, 74, 29),  # a block away from the grid's origin
])
def test_row_chunked_compaction_equals_one_argsort(n, m, chunk_rows,
                                                   capacity, i0, j0):
    rng = np.random.default_rng(n * m + chunk_rows)
    s = rng.random((n, m)).astype(np.float32)
    mask = s >= 0.8
    rows, cols, scores, total = _compact_by_rows(
        jnp.asarray(s), jnp.asarray(mask), capacity, chunk_rows, i0, j0)
    r, c = np.nonzero(mask)          # row-major, as one stable argsort
    k = min(len(r), capacity)
    want_r = np.full(capacity, -1)
    want_c = np.full(capacity, -1)
    want_s = np.zeros(capacity, np.float32)
    want_r[:k], want_c[:k] = i0 + r[:k], j0 + c[:k]
    want_s[:k] = s[r[:k], c[:k]]
    np.testing.assert_array_equal(np.asarray(rows), want_r)
    np.testing.assert_array_equal(np.asarray(cols), want_c)
    np.testing.assert_array_equal(np.asarray(scores), want_s)
    assert int(total) == len(r)


@pytest.mark.parametrize("capacity", [None, 7])
def test_sharded_candidates_chunked_block_matches_one_piece(monkeypatch,
                                                            capacity):
    """A block over ``CHUNK_CELLS`` cells serves the same candidates in the
    same order, with the same overflow count, as one argsort of it."""
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(53, 16)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(41, 16)), jnp.float32)
    mesh = make_host_mesh(1, 1)
    whole = sharded_candidates(a, b, 0.4, mesh, capacity=capacity,
                               impl="interpret")
    monkeypatch.setattr(sharded, "CHUNK_CELLS", 41 * 6)
    assert sharded._chunk_rows(53, 41) == 6
    a2 = jnp.concatenate([a, jnp.zeros((1, 16), jnp.float32)])  # new shape
    chunked = sharded_candidates(a2, b, 0.4, mesh, capacity=capacity,
                                 impl="interpret")
    assert len(whole) > 0
    np.testing.assert_array_equal(chunked.rows, whole.rows)
    np.testing.assert_array_equal(chunked.cols, whole.cols)
    np.testing.assert_array_equal(chunked.scores, whole.scores)
    assert chunked.n_dropped == whole.n_dropped
    assert (chunked.n_dropped > 0) == (capacity is not None)


FEBRL_TINY = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    from bench import harness
    from repro import obs
    from repro.kernels.pair_scores import sharded

    # a few hundred cells a chunk: every device compacts its block by rows
    sharded.CHUNK_CELLS = 1000
    blocks = []
    compact = sharded._compact_by_rows

    def spy(s, mask, capacity, chunk_rows, i0=0, j0=0):
        blocks.append((s.shape[0], chunk_rows))
        return compact(s, mask, capacity, chunk_rows, i0, j0)

    sharded._compact_by_rows = spy

    cell = harness.Cell.find("febrl.mesh4")
    cell.config = {{**cell.config, "n_a": 301, "n_b": 299,
                    "n_entities": 240, "dim": 32}}
    cell.traffic = {{**cell.traffic,
                     "machine": {{**cell.traffic["machine"],
                                  "capacity": 2048}}}}
    w0 = obs.wide_key_lanes.count
    line = harness.run(cell, 2 ** 31 + 29, 0.2, False, time.perf_counter(),
                       require_chip=False)
    line["wide_key_lanes"] = obs.wide_key_lanes.count - w0
    line["blocks"] = blocks
    sess = cell.generator.generate(cell.config, [7, 0])
    line["n_objects"] = int(sess["a"].shape[0] + sess["b"].shape[0])
    print(json.dumps(line))
""")


def test_febrl_link_tiny_on_a_2x2_cpu_mesh(tmp_path):
    """Four virtual CPU devices, the cell's own traffic (mesh [2, 2]),
    each device's block compacted by row chunks (the last one ragged):
    every served session's candidates agree with the float64 dense oracle
    and its labels, crowdsourced set, rounds and spend with the
    round-barrier reference, exactly."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    p = subprocess.run([sys.executable, "-c", FEBRL_TINY.format(root=ROOT)],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert line["device"]["count"] == 4
    # the 302 x 300 padded grid puts 151 x 150 on a device: 26 chunks of
    # 6 rows, the last sliding back over the one before it
    assert line["blocks"] and all(b == [151, 6] for b in line["blocks"])
    checks = {k: c["value"] for k, c in line["checks"].items()}
    for k in ("label_mismatches", "crowdsourced_mismatches",
              "round_mismatches", "cents_mismatch", "missed_above_tau"):
        assert checks[k] == 0, (k, checks)
    assert checks["score_gap"] <= 1e-6
    # 600 objects keep one-word keys; the generator's structure is the
    # deployment's (Zipf duplicates, households) at a tenth of a percent
    assert line["wide_key_lanes"] == 0 and line["n_objects"] == 600
    assert line["metrics"]["crowd_cents_per_pair"]["value"] < 2.0


def test_mesh_metric_readers_charge_one_device_block():
    """``mesh_kernel_roofline`` charges each of device 0's kernel calls
    with one device's (N/dd) x (M/dm) x D block of the traffic's mesh;
    ``mesh_compaction_s`` is the program's time less its kernel calls per
    run; both read nothing without a trace."""
    sys.path.insert(0, ROOT)
    from bench import harness, roofline, trace

    cell = harness.Cell.find("febrl.mesh4")
    prog = "jit__sharded_candidates_jit"
    ops = [trace.Op("pair_scores.1", prog, 0.0, 0.02, True),
           trace.Op("pair_scores.1", prog, 2.0, 0.02, True),
           trace.Op("sort.3", prog, 0.1, 1.5, False)]
    red = trace.Reduction(window_s=10.0, busy_s=5.0, ops=ops,
                          modules=[(prog, 1.8), (prog, 1.6),
                                   ("jit_engine_run_rounds_batch", 3.0)],
                          idle=[], n_devices=4)
    rec = harness.Record(cell=cell, trace=red, device_kind="TPU v5 lite")
    c = cell.config
    work = roofline.dense_work(c["n_a"] // 2, c["n_b"] // 2, c["dim"])
    want = roofline.share(work, 2, 0.04, "TPU v5 lite")
    got = cell.reader("mesh_kernel_roofline").read(rec)
    assert got == pytest.approx(want) and 0 < got < 100
    assert cell.reader("mesh_compaction_s").read(rec) == pytest.approx(
        (1.8 + 1.6 - 0.04) / 2)
    bare = harness.Record(cell=cell)
    assert cell.reader("mesh_kernel_roofline").read(bare) is None
    assert cell.reader("mesh_compaction_s").read(bare) is None


@pytest.mark.parametrize("seeds", [(5, 2 ** 31 + 11), (0, 1)])
def test_febrl_pool_serves_the_same_work_under_every_seed(seeds):
    """A pool is the fixed draws ``instance_seeds`` with the records of
    each registry reordered from ``--seed``: two seeds give each session
    the same (person, embedding) records in another order."""
    sys.path.insert(0, ROOT)
    from bench import harness

    cell = harness.Cell.find("febrl.mesh4")
    spec = {**cell.config, "n_a": 61, "n_b": 59, "n_entities": 48,
            "dim": 8}
    one, two = (cell.generator.pool(spec, s, 4) for s in seeds)

    def records(x, ent):
        x = np.asarray(x)
        order = np.lexsort(np.column_stack([ent, x]).T[::-1])
        return ent[order], x[order]

    for s1, s2 in zip(one, two):
        assert not np.array_equal(s1["ent_a"], s2["ent_a"])
        for side in ("a", "b"):
            e1, x1 = records(s1[side], s1["ent_" + side])
            e2, x2 = records(s2[side], s2["ent_" + side])
            np.testing.assert_array_equal(e1, e2)
            np.testing.assert_array_equal(x1, x2)
    # the four sessions are four different draws
    firsts = [np.sort(np.asarray(s["a"])[:, 0]) for s in one]
    assert all(not np.array_equal(firsts[i], firsts[j])
               for i in range(4) for j in range(i + 1, 4))
