"""Compile the two machine-phase programs of the Walmart-Amazon cells at
their real shapes for a described TPU v5e (no chip needed)."""
from __future__ import annotations

import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topo.devices[0], SingleDeviceSharding(topo.devices[0])


def test_dense_program_compiles_at_cell_shape(one_chip):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.kernels.pair_scores.sharded import _sharded_candidates_jit

    dev, sharding = one_chip
    c = _spec("configs/walmart_amazon.json")
    t = _spec("traffic/dense.json")["machine"]
    mesh = Mesh(np.array([[dev]]), ("data", "model"))
    a = jax.ShapeDtypeStruct((c["n_a"], c["dim"]), np.float32,
                             sharding=sharding)
    b = jax.ShapeDtypeStruct((c["n_b"], c["dim"]), np.float32,
                             sharding=sharding)
    lowered = _sharded_candidates_jit.lower(
        a, b, threshold=c["threshold"], capacity=t["capacity"], mesh=mesh,
        interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_compact_chunk_compiles_at_cell_shape(one_chip):
    import jax
    import numpy as np

    from repro.kernels.pair_scores.kernel import pair_scores_compact

    _, sharding = one_chip
    c = _spec("configs/walmart_amazon.json")
    m = _spec("traffic/blocked.json")["machine"]
    T = m["tiles_per_call"]
    f32 = lambda n, d: jax.ShapeDtypeStruct((n, d), np.float32,
                                            sharding=sharding)
    i32 = lambda n: jax.ShapeDtypeStruct((n, 1), np.int32,
                                         sharding=sharding)
    lowered = pair_scores_compact.lower(
        f32(T * m["bn"], c["dim"]), f32(T * m["bm"], c["dim"]),
        i32(T * m["bn"]), i32(T * m["bm"]), c["threshold"],
        min(m["capacity"], T * m["bn"] * m["bm"]), m["bn"], m["bm"],
        interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
