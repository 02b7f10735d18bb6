"""Persistent compilation cache placement (``repro.launch.compile_cache``)."""
import os

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, monkeypatch, from_env):
    """With JAX_COMPILATION_CACHE_DIR set the cache stays where it says and
    nothing is set in code; unset, it goes to a fixed ``.jax_cache`` in the
    checkout."""
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache(str(tmp_path / "checkout"))
        if from_env:
            assert got == str(tmp_path / "env")
            assert jax.config.jax_compilation_cache_dir == before
        else:
            want = os.path.join(str(tmp_path / "checkout"), ".jax_cache")
            assert got == want
            assert jax.config.jax_compilation_cache_dir == want
            # a second call from the same checkout picks the same path
            assert use_compile_cache(str(tmp_path / "checkout")) == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
