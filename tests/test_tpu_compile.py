"""The main path's device programs compile for a TPU v5e at real sizes.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles for a *described* v5e topology, which refuses what interpret mode
cannot see — a primitive Mosaic does not lower, a block past the tiling
rules or the fast memory, a program that does not fit the device.  The
topology is described inside a fixture (never while a module is imported),
so every worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_pair_scores_compiles_for_v5e(one_chip):
    from repro.kernels.pair_scores.kernel import pair_scores

    a = _spec((2048, 16), jnp.float32, one_chip)
    compiled = pair_scores.lower(a, a, 0.9).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compact_chunk_compiles_for_v5e(one_chip):
    """One full chunk of the blocked machine phase at the benchmark's full
    configuration: 256 tiles of 128 x 128, D = 16, capacity 1 << 22."""
    from repro.kernels.pair_scores.kernel import pair_scores_compact

    T, bn, bm, D = 256, 128, 128, 16
    a_g = _spec((T * bn, D), jnp.float32, one_chip)
    b_g = _spec((T * bm, D), jnp.float32, one_chip)
    ida = _spec((T * bn, 1), jnp.int32, one_chip)
    idb = _spec((T * bm, 1), jnp.int32, one_chip)
    compiled = pair_scores_compact.lower(
        a_g, b_g, ida, idb, 0.9, 1 << 22, bn, bm).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lsh_signatures_compiles_for_v5e(one_chip):
    """One row chunk of the blocker's signature program at the benchmark's
    configuration: D = 300, 16 tables of 6 bits."""
    from repro.kernels.pair_scores.blocking import (_SIGNATURE_ROWS,
                                                    lsh_signatures)

    x = _spec((_SIGNATURE_ROWS, 300), jnp.float32, one_chip)
    planes = _spec((300, 16 * 6), jnp.float32, one_chip)
    compiled = lsh_signatures.lower(x, planes, n_bits=6).compile()
    assert compiled.as_text().startswith("HloModule jit_lsh_signatures")


def test_round_engine_batch_compiles_for_v5e(one_chip):
    """The fused round engine as the service dispatches it: 4 lanes of
    32,768 pairs over 1,024 objects, with 32-bit pair keys."""
    from repro.core.jax_graph import (SessionState,
                                      _session_run_rounds_batch_jit)
    from repro.serve.join_service import JoinService

    B, Pn, n = 4, 32768, 1024
    i32 = lambda *s: _spec((B,) + s, jnp.int32, one_chip)
    state = SessionState(
        u=i32(Pn), v=i32(Pn), labels=i32(Pn),
        published=_spec((B, Pn), jnp.bool_, one_chip), roots=i32(n),
        neg_keys=i32(Pn), rounds=i32(), conflicts=i32(Pn),
        priority=_spec((B, Pn), jnp.float32, one_chip), n_objects=n)
    compiled = _session_run_rounds_batch_jit.lower(
        state, i32(Pn), _spec((B, Pn), jnp.float32, one_chip),
        _spec((B,), jnp.bool_, one_chip), i32(),
        max_rounds=JoinService.FUSED_ROUNDS_PER_DISPATCH).compile()
    assert compiled.memory_analysis() is not None


def test_round_engine_two_word_keys_compiles_for_v5e(one_chip):
    """The fused round engine with two-word pair keys, as a lane past
    46,340 objects runs it: 4 lanes of 4,096 pairs over 65,536 objects."""
    from repro.core.jax_graph import (SessionState,
                                      _session_run_rounds_batch_jit)
    from repro.serve.join_service import JoinService

    B, Pn, n = 4, 4096, 65536
    i32 = lambda *s: _spec((B,) + s, jnp.int32, one_chip)
    state = SessionState(
        u=i32(Pn), v=i32(Pn), labels=i32(Pn),
        published=_spec((B, Pn), jnp.bool_, one_chip), roots=i32(n),
        neg_keys=i32(2, Pn), rounds=i32(), conflicts=i32(Pn),
        priority=_spec((B, Pn), jnp.float32, one_chip), n_objects=n)
    compiled = _session_run_rounds_batch_jit.lower(
        state, i32(Pn), _spec((B, Pn), jnp.float32, one_chip),
        _spec((B,), jnp.bool_, one_chip), i32(),
        max_rounds=JoinService.FUSED_ROUNDS_PER_DISPATCH).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_candidates_compiles_for_v5e_2x2(topo):
    """The mesh-sharded dense machine phase over the full 16384 x 16384
    benchmark corpus, rows over ``data`` and columns over ``model``."""
    from repro.kernels.pair_scores.sharded import _sharded_candidates_jit

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    a = _spec((16384, 16), jnp.float32, NamedSharding(mesh, P("data", None)))
    b = _spec((16384, 16), jnp.float32,
              NamedSharding(mesh, P("model", None)))
    compiled = _sharded_candidates_jit.lower(
        a, b, threshold=0.9, capacity=1 << 22, mesh=mesh,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_candidates_row_chunks_compile_for_v5e_2x2(topo):
    """A device block past ``CHUNK_CELLS`` (8,200 x 8,200 cells a chip of
    a 16,400 x 16,400 grid at D = 300) compacts by row chunks; the 2x2
    program compiles, with the Pallas kernel in it."""
    from repro.kernels.pair_scores.sharded import (CHUNK_CELLS, _chunk_rows,
                                                   _sharded_candidates_jit)

    assert _chunk_rows(8200, 8200) < 8200 and 8200 * 8200 > CHUNK_CELLS
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    a = _spec((16400, 300), jnp.float32,
              NamedSharding(mesh, P("data", None)))
    b = _spec((16400, 300), jnp.float32,
              NamedSharding(mesh, P("model", None)))
    compiled = _sharded_candidates_jit.lower(
        a, b, threshold=0.9, capacity=1 << 16, mesh=mesh,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
