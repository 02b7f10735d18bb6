"""The join service's checkpoint writer (``repro.serve.checkpoint``,
DESIGN.md §16): atomic commit, round trip, GC, background writes, and the
on-disk format a checkpoint written before the writer moved still restores
from.  Trees are join-service state: a lane batch of ``SessionState``
pytrees plus a dataclass of statics."""
import json
import os
import threading
import zipfile
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import repro.serve.checkpoint as ckpt_mod
from repro.core import (CostModel, make_session_state,
                        make_session_state_batch, pack_sessions,
                        session_run_rounds_batch)
from repro.core.jax_graph import SessionState
from repro.data.entities import make_session_pairsets
from repro.serve.checkpoint import CheckpointManager

FIXTURE = Path(__file__).parent / "data" / "parent_checkpoint"


def _lane_batch(seed: int = 5) -> SessionState:
    """Three lanes of entity-clustered sessions, advanced one labelling
    round under truthful answers: labels, roots and neg keys all live."""
    sessions = make_session_pairsets(3, seed=seed)
    U, V, labels0, valid, n = pack_sessions(
        [(ps.u, ps.v, ps.n_objects) for ps in sessions])
    state = make_session_state_batch(U, V, labels0, n)
    answers = np.ones(U.shape, np.int32)
    for b, ps in enumerate(sessions):
        answers[b, :len(ps)] = np.where(ps.truth, 1, 0)
    state, *_ = session_run_rounds_batch(state, answers, 1)
    return state


def _join_tree() -> dict:
    return {"lanes": _lane_batch(),
            "cost": CostModel(cents_per_assignment=1.5, pairs_per_hit=10)}


def test_checkpoint_roundtrip_bitexact(tmp_path):
    state = _join_tree()
    cm = CheckpointManager(tmp_path, keep=2)
    cm.save(3, state, extra={"cursor": 3})
    step, restored, extra = cm.restore()
    assert step == 3 and extra["cursor"] == 3
    assert isinstance(restored["lanes"], SessionState)
    assert restored["cost"] == state["cost"]
    for a, b in zip(jax.tree.leaves(state["lanes"]),
                    jax.tree.leaves(restored["lanes"])):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_latest(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, {"x": jnp.ones(3)})
    assert cm.all_steps() == [3, 4]
    assert cm.latest_step() == 4


def test_background_save_failure_surfaces(tmp_path, monkeypatch):
    """A failed background write is never silent: the exception is captured
    in the writer thread and re-raised from wait() (and would equally
    surface from the next save/restore, which call wait() first)."""
    cm = CheckpointManager(tmp_path, keep=2)

    def boom(*a, **k):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(ckpt_mod.np, "savez", boom)
    cm.save(1, {"x": jnp.ones(3)}, background=True)
    with pytest.raises(RuntimeError, match="background checkpoint save"):
        cm.wait()
    # the error is consumed: the manager stays usable once the cause clears
    monkeypatch.undo()
    cm.save(2, {"x": jnp.ones(3)}, background=True)
    cm.wait()
    assert cm.latest_step() == 2


def test_crash_at_commit_preserves_previous_checkpoint(tmp_path, monkeypatch):
    """Regression: re-saving an existing step used to rmtree the old dir
    before renaming the new one in — a crash in that window destroyed the
    only copy.  Now the old dir is parked at ``.old`` first, so a crash at
    the commit rename still leaves a restorable checkpoint."""
    cm = CheckpointManager(tmp_path, keep=2)
    cm.save(5, {"x": jnp.full(3, 1.0)})

    real_rename = os.rename

    def crash_at_commit(src, dst):
        if str(src).endswith(".tmp"):
            raise OSError("killed at commit (injected)")
        return real_rename(src, dst)

    monkeypatch.setattr(ckpt_mod.os, "rename", crash_at_commit)
    with pytest.raises(OSError, match="killed at commit"):
        cm.save(5, {"x": jnp.full(3, 2.0)})
    monkeypatch.undo()
    # the parked copy still restores with the ORIGINAL contents
    assert cm.all_steps() == [5]
    step, state, _ = cm.restore()
    np.testing.assert_array_equal(np.asarray(state["x"]), np.full(3, 1.0))
    # and a clean re-save replaces it
    cm.save(5, {"x": jnp.full(3, 3.0)})
    _, state, _ = cm.restore()
    np.testing.assert_array_equal(np.asarray(state["x"]), np.full(3, 3.0))
    assert not (tmp_path / "step_00000005.old").exists()


def test_restore_ignores_leftover_tmp(tmp_path):
    """A crash mid-write leaves a ``.tmp`` dir: it must be invisible to
    all_steps/restore, and a later save of the same step must clobber it."""
    cm = CheckpointManager(tmp_path, keep=3)
    cm.save(1, {"x": jnp.ones(2)})
    stray = tmp_path / "step_00000002.tmp"
    stray.mkdir()
    (stray / "arrays.npz").write_bytes(b"truncated")
    assert cm.all_steps() == [1]
    assert cm.latest_step() == 1
    cm.save(2, {"x": jnp.full(2, 2.0)})
    assert cm.all_steps() == [1, 2]
    _, state, _ = cm.restore(2)
    np.testing.assert_array_equal(np.asarray(state["x"]), np.full(2, 2.0))


def test_checkpoint_bfloat16_roundtrip(tmp_path):
    """bfloat16 leaves round-trip bit-exact through the uint16 view (npz
    cannot store ml_dtypes directly)."""
    x = jnp.asarray(np.linspace(-3, 3, 16), dtype=jnp.bfloat16)
    cm = CheckpointManager(tmp_path)
    cm.save(0, {"x": x, "y": jnp.ones(4, jnp.float32)})
    _, state, _ = cm.restore()
    assert state["x"].dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(x).view(np.uint16), state["x"].view(np.uint16))
    assert state["y"].dtype == np.float32


def test_gc_spares_latest_during_background_save(tmp_path):
    """keep=1 with an in-flight background save: the previous (latest
    restorable) step survives until the new one commits — GC runs after
    the commit rename, never before."""
    cm = CheckpointManager(tmp_path, keep=1)
    cm.save(1, {"x": jnp.ones(2)})
    gate = threading.Event()
    real_savez = np.savez

    def slow_savez(path, **arrays):
        gate.wait(timeout=30)
        return real_savez(path, **arrays)

    ckpt_mod.np.savez = slow_savez
    try:
        cm.save(2, {"x": jnp.full(2, 2.0)}, background=True)
        # writer blocked pre-commit: step 1 must still be restorable
        assert cm.all_steps() == [1]
    finally:
        gate.set()
        cm.wait()
        ckpt_mod.np.savez = real_savez
    assert cm.all_steps() == [2]


def test_checkpoint_dataclass_statics_roundtrip(tmp_path):
    """Registered-dataclass subtrees (the serve layer's SessionState):
    array fields ride the npz, static scalar fields ride the manifest, and
    restore rebuilds the instance without any caller-side registration."""
    state = make_session_state(
        np.array([0, 1], np.int32), np.array([1, 2], np.int32), 3,
        pair_capacity=8, object_capacity=8)
    cm = CheckpointManager(tmp_path)
    cm.save(0, {"session": state, "extra": jnp.ones(2)})
    _, restored, _ = cm.restore()
    got = restored["session"]
    assert isinstance(got, SessionState)
    assert got.n_objects == state.n_objects
    for f in ("u", "v", "labels", "published", "roots", "neg_keys",
              "rounds", "conflicts", "priority"):
        np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                      np.asarray(getattr(got, f)))


# -- the on-disk format ------------------------------------------------------
def _fixture_tree() -> dict:
    """The tree ``tests/data/parent_checkpoint`` holds, rebuilt from its
    seed: a two-lane ``SessionState`` of plain seeded arrays (one-word
    keys), a dataclass of statics, and a bfloat16 leaf."""
    rng = np.random.default_rng(20131022)
    B, P, N = 2, 12, 9
    lanes = SessionState(
        u=rng.integers(0, N, (B, P)).astype(np.int32),
        v=rng.integers(0, N, (B, P)).astype(np.int32),
        labels=rng.integers(-1, 2, (B, P)).astype(np.int32),
        published=rng.random((B, P)) < 0.3,
        roots=rng.integers(0, N, (B, N)).astype(np.int32),
        neg_keys=np.sort(rng.integers(0, N * N, (B, P)), axis=1
                         ).astype(np.int32),
        rounds=np.array([3, 1], np.int32),
        conflicts=rng.integers(0, 3, (B, P)).astype(np.int32),
        priority=rng.random((B, P)).astype(np.float32),
        n_objects=N)
    return {"lanes": lanes,
            "cost": CostModel(cents_per_assignment=1.5, pairs_per_hit=10),
            "perm": rng.permutation(P).astype(np.int64),
            "scale": rng.normal(size=5).astype(ml_dtypes.bfloat16)}


FIXTURE_EXTRA = {"cursor": 3}
FIXTURE_SIDECAR = {"version": 1, "lanes": [{"p": 12}, {"p": 7}]}


def test_checkpoint_written_before_the_move_restores_bit_for_bit(tmp_path):
    """``tests/data/parent_checkpoint`` was written by the manager when it
    lived at ``repro/train/checkpoint.py``: it restores bit for bit, and
    today's writer lays the same tree down in the same files — the same
    npz members, manifest (but its clock) and sidecar."""
    want = _fixture_tree()
    step, got, extra = CheckpointManager(FIXTURE).restore()
    assert step == 3 and extra == FIXTURE_EXTRA
    assert CheckpointManager(FIXTURE).sidecar(3) == FIXTURE_SIDECAR
    assert isinstance(got["lanes"], SessionState)
    assert got["lanes"].n_objects == 9 and got["cost"] == want["cost"]
    leaves = [(want[k], got[k]) for k in ("perm", "scale")] + list(zip(
        jax.tree.leaves(want["lanes"]), jax.tree.leaves(got["lanes"])))
    for a, b in leaves:
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))

    CheckpointManager(tmp_path).save(3, want, extra=FIXTURE_EXTRA,
                                     sidecar=FIXTURE_SIDECAR)
    old, new = FIXTURE / "step_00000003", tmp_path / "step_00000003"
    assert sorted(p.name for p in old.iterdir()) == \
        sorted(p.name for p in new.iterdir())
    with zipfile.ZipFile(old / "arrays.npz") as zo, \
            zipfile.ZipFile(new / "arrays.npz") as zn:
        assert zo.namelist() == zn.namelist()
        for name in zo.namelist():
            assert zo.read(name) == zn.read(name), name
    mo, mn = (json.loads((d / "manifest.json").read_text())
              for d in (old, new))
    mo.pop("time"), mn.pop("time")
    assert mo == mn
    assert (old / "sidecar.json").read_bytes() == \
        (new / "sidecar.json").read_bytes()


SERVICE_FIXTURE = Path(__file__).parent / "data" / "parent_service_checkpoint"


def test_service_checkpoint_written_before_the_move_resumes(tmp_path):
    """``tests/data/parent_service_checkpoint`` is a ``JoinService`` killed
    after its second commit when the writer lived under ``train/``: three
    of the launcher's noisy join requests on two lanes.  Restored today it
    finishes with the uninterrupted run's labels, rounds and spend."""
    import shutil

    from repro.core.crowd import NoisyCrowd
    from repro.launch.serve import _join_workload
    from repro.serve.join_service import JoinService

    base = JoinService(lanes=2)
    rids = [base.submit(_join_workload(s), crowd=NoisyCrowd(seed=s))
            for s in range(3)]
    want = base.run()
    shutil.copytree(SERVICE_FIXTURE, tmp_path / "ckpt")
    restored = JoinService.restore(str(tmp_path / "ckpt"))
    assert restored.last_recovery["step"] == 1
    assert restored.last_recovery["spent_cents"] > 0
    got = restored.run()
    assert sorted(got) == sorted(rids)
    for rid in rids:
        np.testing.assert_array_equal(got[rid].labels, want[rid].labels)
        np.testing.assert_array_equal(got[rid].crowdsourced,
                                      want[rid].crowdsourced)
        assert got[rid].round_sizes == want[rid].round_sizes
        assert got[rid].n_spent_cents == want[rid].n_spent_cents
