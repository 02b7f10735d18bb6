"""The dense machine phase (``sharded_candidates``, DESIGN.md §7) on every
host mesh of up to four devices, against the dense oracle
``candidates_ref``: the same candidates to the score band, none dropped,
with each device's block compacted in one piece and by row chunks; and
with a per-device capacity below a block's candidates, the overflow
counted exactly.

The meshes need forced host devices, which must be set before jax starts,
so one subprocess on four virtual CPU devices serves every case."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESHES = [(1, 2), (2, 1), (2, 2), (1, 4), (4, 1)]
BAND = 1e-6

MESH_RUN = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.join({root!r}, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.pair_scores import sharded
    from repro.kernels.pair_scores.ops import l2_normalize
    from repro.kernels.pair_scores.ref import candidate_diff, candidates_ref
    from repro.launch.mesh import make_host_mesh

    blocks = []
    compact = sharded._compact_by_rows

    def spy(s, mask, capacity, chunk_rows, i0=0, j0=0):
        blocks.append([int(s.shape[0]), int(chunk_rows)])
        return compact(s, mask, capacity, chunk_rows, i0, j0)

    sharded._compact_by_rows = spy
    rng = np.random.default_rng(17)
    cents = rng.normal(size=(12, 16))
    mk = lambda n: cents[rng.integers(0, 12, n)] + 0.5 * rng.normal(
        size=(n, 16))
    a = jnp.asarray(mk(67), jnp.float32)   # no mesh extent divides
    b = jnp.asarray(mk(53), jnp.float32)   # either side
    tau = 0.6
    ref = candidates_ref(l2_normalize(a), l2_normalize(b), tau)
    out = {{}}
    pieces = {{}}
    for data, model in {meshes!r}:
        mesh = make_host_mesh(data, model)
        got = sharded.sharded_candidates(a, b, tau, mesh, capacity=5)
        n_loc, m_loc = -(-67 // data), -(-53 // model)
        per_device = np.zeros((data, model), int)
        np.add.at(per_device, (ref[0] // n_loc, ref[1] // m_loc), 1)
        pairs = set(zip(got.rows.tolist(), got.cols.tolist()))
        out[f"capacity-{{data}}x{{model}}"] = {{
            "n_got": len(got), "dropped": got.n_dropped,
            "per_device": per_device.tolist(),
            "subset": pairs <= set(zip(ref[0].tolist(), ref[1].tolist())),
            "suggested": got.suggested_capacity,
        }}
    for variant, chunk_cells in (("one_piece", sharded.CHUNK_CELLS),
                                 ("row_chunked", 90)):
        sharded.CHUNK_CELLS = chunk_cells
        jax.clear_caches()
        for data, model in {meshes!r}:
            del blocks[:]
            mesh = make_host_mesh(data, model)
            got = sharded.sharded_candidates(a, b, tau, mesh)
            dmax, extra, missing = candidate_diff(
                (got.rows, got.cols, got.scores), ref)
            key = f"{{data}}x{{model}}"
            trio = [got.rows.tolist(), got.cols.tolist(),
                    got.scores.tolist()]
            if variant == "one_piece":
                pieces[key] = trio
            out[f"{{variant}}-{{key}}"] = {{
                "devices": int(mesh.devices.size),
                "n_got": len(got), "n_ref": len(ref[0]),
                "dropped": got.n_dropped, "dmax": dmax,
                "extra": extra.tolist(), "missing": missing.tolist(),
                "same_as_one_piece": trio == pieces[key],
                "blocks": list(blocks),
            }}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cwd = tmp_path_factory.mktemp("meshes")
    p = subprocess.run(
        [sys.executable, "-c",
         MESH_RUN.format(root=ROOT, meshes=MESHES)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("variant", ["one_piece", "row_chunked"])
@pytest.mark.parametrize("mesh", MESHES, ids=[f"{d}x{m}" for d, m in MESHES])
def test_sharded_candidates_match_dense_oracle_on_host_mesh(mesh_runs,
                                                            variant, mesh):
    run = mesh_runs[f"{variant}-{mesh[0]}x{mesh[1]}"]
    assert run["devices"] == mesh[0] * mesh[1]
    assert run["n_ref"] > 50 and run["dropped"] == 0
    assert run["dmax"] <= BAND
    tau = 0.6
    assert all(s < tau + BAND for s in run["extra"] + run["missing"])
    assert run["n_got"] - len(run["extra"]) == \
        run["n_ref"] - len(run["missing"])
    n_loc = -(-67 // mesh[0])
    chunk_rows = {blk[1] for blk in run["blocks"]}
    assert {blk[0] for blk in run["blocks"]} == {n_loc}
    if variant == "one_piece":
        assert chunk_rows == {n_loc}
    else:
        # 90 cells a chunk: every device block is compacted in pieces, and
        # the result is the one-piece result, pair for pair, in order
        assert chunk_rows == {max(1, 90 // -(-53 // mesh[1]))}
        assert max(chunk_rows) < n_loc
        assert run["same_as_one_piece"]


@pytest.mark.parametrize("mesh", MESHES, ids=[f"{d}x{m}" for d, m in MESHES])
def test_sharded_candidates_count_per_device_overflow(mesh_runs, mesh):
    """Capacity 5 a device: each device keeps its first five candidates
    and ``n_dropped`` is the sum of every device's excess over five, so
    ``suggested_capacity`` fits the fullest device."""
    run = mesh_runs[f"capacity-{mesh[0]}x{mesh[1]}"]
    per_device = np.asarray(run["per_device"])
    assert per_device.max() > 5   # some device overflows
    assert run["n_got"] == np.minimum(per_device, 5).sum()
    assert run["dropped"] == np.maximum(per_device - 5, 0).sum()
    assert run["subset"]
    assert run["suggested"] >= per_device.max()
