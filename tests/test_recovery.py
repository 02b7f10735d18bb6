"""Durable serving state (DESIGN.md §16): kill-at-checkpoint / restore
parity under both serving disciplines, no re-billing of answered pairs,
admission control, and the cluster-cache auto seed/deposit wiring."""
import os

import numpy as np
import pytest

from repro.core.crowd import LatencyModel, NoisyCrowd, PerfectCrowd
from repro.core.pairs import PairSet
from repro.serve.join_service import (AdmissionError, AdmissionPolicy,
                                      JoinService, ServiceKilled)


def _pairs(seed, n=36, p=110, clusters=7):
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, clusters, n)
    u = rng.integers(0, n, p).astype(np.int32)
    v = rng.integers(0, n, p).astype(np.int32)
    keep = u != v
    u, v = u[keep], v[keep]
    truth = assign[u] == assign[v]
    lik = np.clip(rng.random(len(u)) * 0.5 + truth * 0.4, 0.0, 1.0)
    return PairSet(u=u, v=v, likelihood=lik.astype(np.float32),
                   truth=truth, n_objects=n)


def _submit_all(svc, n_reqs=3, crowd_fn=None):
    crowd_fn = crowd_fn or (lambda s: NoisyCrowd(seed=s))
    return [svc.submit(_pairs(s), crowd=crowd_fn(s)) for s in range(n_reqs)]


def _run_killed_then_restored(tmp_path, kill_after, svc_kwargs,
                              crowd_fn=None):
    """One service killed right after its ``kill_after``-th checkpoint, a
    second restored from disk; returns (restored results, cents spent by
    the killed process before dying)."""
    svc = JoinService(checkpoint_dir=str(tmp_path), **svc_kwargs)
    _submit_all(svc, crowd_fn=crowd_fn)
    svc._crash_after_checkpoints = kill_after
    with pytest.raises(ServiceKilled):
        svc.run()
    restored = JoinService.restore(str(tmp_path))
    spent_at_kill = restored.last_recovery["spent_cents"]
    return restored.run(), spent_at_kill


@pytest.mark.parametrize("async_mode", [False, True],
                         ids=["round_barrier", "async"])
def test_kill_restore_label_parity(tmp_path, async_mode):
    """Kill at checkpoint k, restore, finish: labels, crowdsourced sets,
    and per-request spend all identical to an uninterrupted run."""
    base_svc = JoinService(lanes=2, async_mode=async_mode)
    rids = _submit_all(base_svc)
    base = base_svc.run()
    rec, _ = _run_killed_then_restored(
        tmp_path, kill_after=2, svc_kwargs=dict(lanes=2,
                                                async_mode=async_mode))
    assert sorted(rec) == sorted(rids)
    for r in rids:
        np.testing.assert_array_equal(base[r].labels, rec[r].labels)
        np.testing.assert_array_equal(base[r].crowdsourced,
                                      rec[r].crowdsourced)
        assert base[r].n_spent_cents == pytest.approx(rec[r].n_spent_cents)
        assert base[r].n_conflicts == rec[r].n_conflicts


def test_kill_restore_parity_latency_em_requery(tmp_path):
    """The hard configuration: async ID/NF over a simulated worker pool,
    EM ballot aggregation, requery escalation.  Restore re-materializes
    in-flight tickets, the platform clock, and the worker-reliability
    model — the resumed event stream is bit-exact (sim_minutes included)."""
    kwargs = dict(lanes=2, async_mode=True, nf=True,
                  latency=LatencyModel(n_workers=10, seed=3),
                  aggregation="em", conflict_policy="requery")
    crowd_fn = lambda s: NoisyCrowd(error_rate=0.15, seed=s, n_workers=12)
    base_svc = JoinService(**kwargs)
    rids = _submit_all(base_svc, crowd_fn=crowd_fn)
    base = base_svc.run()
    kwargs["checkpoint_every"] = 3
    rec, _ = _run_killed_then_restored(tmp_path, kill_after=4,
                                       svc_kwargs=kwargs, crowd_fn=crowd_fn)
    for r in rids:
        np.testing.assert_array_equal(base[r].labels, rec[r].labels)
        np.testing.assert_array_equal(base[r].crowdsourced,
                                      rec[r].crowdsourced)
        assert base[r].n_spent_cents == pytest.approx(rec[r].n_spent_cents)
        assert base[r].sim_minutes == pytest.approx(rec[r].sim_minutes)
        assert base[r].n_requeried == rec[r].n_requeried


def test_restore_never_rebills_answered_pairs(tmp_path):
    """The recovered run's *additional* spend is exactly the uninterrupted
    total minus what was already committed at the kill point — answered
    (and in-flight, already-billed) pairs are never bought twice, which is
    the cents-saved claim of the recovery benchmark."""
    base_svc = JoinService(lanes=2)
    rids = _submit_all(base_svc)
    base = base_svc.run()
    total_base = sum(base[r].n_spent_cents for r in rids)
    rec, spent_at_kill = _run_killed_then_restored(
        tmp_path, kill_after=2, svc_kwargs=dict(lanes=2))
    total_rec = sum(rec[r].n_spent_cents for r in rids)
    assert total_rec == pytest.approx(total_base)
    assert spent_at_kill > 0  # the kill landed mid-run, not before work
    # restart-from-scratch would pay total_base again; restore pays only
    # the remainder
    assert total_base - spent_at_kill < total_base


def test_restore_brings_back_results_queue_and_sidecar(tmp_path):
    """A request finished before the kill comes back in ``results`` with
    identical labels/quality; one still queued behind full lanes serves
    after restore; ``last_recovery`` reports the inventory."""
    crowd_fn = lambda s: PerfectCrowd()
    svc = JoinService(lanes=1, checkpoint_dir=str(tmp_path))
    rids = _submit_all(svc, n_reqs=3, crowd_fn=crowd_fn)
    # lanes=1 + PerfectCrowd: each fused pass finishes one session, so
    # the second checkpoint already has >= 1 finished result behind it
    svc._crash_after_checkpoints = 2
    with pytest.raises(ServiceKilled):
        svc.run()
    restored = JoinService.restore(str(tmp_path))
    info = restored.last_recovery
    assert info["n_results"] >= 1
    assert info["n_results"] + info["n_lanes"] + info["n_queued"] == 3
    pre = {r: restored.results[r] for r in restored.results}
    out = restored.run()
    assert sorted(out) == sorted(rids)
    base = JoinService(lanes=1)
    _submit_all(base, n_reqs=3, crowd_fn=crowd_fn)
    expected = base.run()
    for r in rids:
        np.testing.assert_array_equal(expected[r].labels, out[r].labels)
    for r, res in pre.items():  # finished-before-kill results round-trip
        np.testing.assert_array_equal(res.labels, out[r].labels)
        assert res.quality == expected[r].quality


def test_restore_ignores_retired_timing_fields(tmp_path):
    """A checkpoint written while lanes kept ``elapsed`` and results kept
    ``wall_seconds`` in the sidecar restores as if they were absent."""
    import glob
    import json

    svc = JoinService(lanes=2, checkpoint_dir=str(tmp_path))
    rids = _submit_all(svc)
    # the 7th checkpoint holds two finished results and one open lane
    svc._crash_after_checkpoints = 7
    with pytest.raises(ServiceKilled):
        svc.run()
    for path in glob.glob(os.path.join(str(tmp_path), "*", "sidecar.json")):
        with open(path) as f:
            side = json.load(f)
        for lane in side.get("lanes", []):
            lane["elapsed"] = 1.5
        for res in side.get("results", {}).values():
            res["wall_seconds"] = 2.5
        with open(path, "w") as f:
            json.dump(side, f)
    restored = JoinService.restore(str(tmp_path))
    info = restored.last_recovery
    assert info["n_results"] >= 1 and info["n_lanes"] >= 1
    out = restored.run()
    base = JoinService(lanes=2)
    _submit_all(base)
    expected = base.run()
    for r in rids:
        np.testing.assert_array_equal(expected[r].labels, out[r].labels)
        assert expected[r].n_spent_cents == out[r].n_spent_cents
        assert not hasattr(out[r], "wall_seconds")


def test_restore_streaming_arrivals(tmp_path):
    """Pending arrival epochs (submit_stream) survive the kill: the
    restored run ingests them and matches the uninterrupted stream run."""
    def epochs(seed):
        all_pairs = _pairs(seed, p=140)
        k = len(all_pairs) // 2
        idx0, idx1 = np.arange(k), np.arange(k, len(all_pairs))
        return [all_pairs.take(idx0), all_pairs.take(idx1)]

    base_svc = JoinService(lanes=1)
    rid = base_svc.submit_stream(epochs(0), crowd=NoisyCrowd(seed=0))
    base = base_svc.run()[rid]
    svc = JoinService(lanes=1, checkpoint_dir=str(tmp_path))
    svc.submit_stream(epochs(0), crowd=NoisyCrowd(seed=0))
    svc._crash_after_checkpoints = 1
    with pytest.raises(ServiceKilled):
        svc.run()
    rec = JoinService.restore(str(tmp_path)).run()[rid]
    np.testing.assert_array_equal(base.labels, rec.labels)
    np.testing.assert_array_equal(base.crowdsourced, rec.crowdsourced)


def test_admission_max_pending_sheds(tmp_path):
    """The QPS envelope: a submit that finds the queue at ``max_pending``
    raises AdmissionError without enqueueing, and the deferred flag marks
    requests that waited behind fully-occupied lanes."""
    svc = JoinService(lanes=1, admission=AdmissionPolicy(max_pending=2))
    r0 = svc.submit(_pairs(0))
    r1 = svc.submit(_pairs(1))
    with pytest.raises(AdmissionError):
        svc.submit(_pairs(2))
    assert svc.n_shed == 1
    assert len(svc.queue) == 2
    res = svc.run()
    assert not res[r0].admission_deferred
    assert res[r1].admission_deferred


def test_admission_budget_envelope_clamps_and_frees(tmp_path):
    """The global crowd-spend envelope: an uncapped request is clamped to
    what remains (and flagged), a second submit against the fully-reserved
    envelope sheds, and finalize releases the reservation so later
    requests admit against realized spend."""
    svc = JoinService(lanes=2,
                      admission=AdmissionPolicy(global_budget_cents=50.0))
    ra = svc.submit(_pairs(0), crowd=NoisyCrowd(seed=0))
    with pytest.raises(AdmissionError):
        svc.submit(_pairs(1), crowd=NoisyCrowd(seed=1))
    res = svc.run()[ra]
    assert res.envelope_clamped
    assert res.n_spent_cents <= 50.0 + 1e-9
    # the reservation is released; whatever the first session did not
    # spend is admittable again
    assert svc._envelope_reserved == pytest.approx(0.0)
    assert svc._envelope_spent == pytest.approx(res.n_spent_cents)
    if svc._envelope_spent < 50.0:
        svc.submit(_pairs(2), crowd=NoisyCrowd(seed=2))


def test_admission_envelope_survives_restore(tmp_path):
    """Envelope ledgers are checkpointed: a restored service still refuses
    submissions the envelope cannot fund."""
    svc = JoinService(lanes=1, checkpoint_dir=str(tmp_path),
                      admission=AdmissionPolicy(global_budget_cents=40.0))
    svc.submit(_pairs(0), crowd=NoisyCrowd(seed=0))
    svc._crash_after_checkpoints = 1
    with pytest.raises(ServiceKilled):
        svc.run()
    restored = JoinService.restore(str(tmp_path))
    assert restored._envelope_reserved == pytest.approx(40.0)
    with pytest.raises(AdmissionError):
        restored.submit(_pairs(1), crowd=NoisyCrowd(seed=1))
    restored.run()


def test_checkpoint_every_validates():
    with pytest.raises(ValueError, match="checkpoint_every"):
        JoinService(checkpoint_every=0)


def test_restore_without_sidecar_rejected(tmp_path):
    """A checkpoint written by the train path (no serving sidecar) is not
    silently misinterpreted as serving state."""
    from repro.train.checkpoint import CheckpointManager
    CheckpointManager(tmp_path).save(0, {"x": np.ones(3)})
    with pytest.raises(FileNotFoundError, match="sidecar"):
        JoinService.restore(str(tmp_path))


def test_perfect_crowd_fused_path_parity(tmp_path):
    """PerfectCrowd sessions ride the fused §13 megabatch path; a kill
    between fused waves restores and still matches the uninterrupted run
    (the fused path re-engages on the restored lanes)."""
    base_svc = JoinService(lanes=2)
    rids = [base_svc.submit(_pairs(s), crowd=PerfectCrowd())
            for s in range(3)]
    base = base_svc.run()
    svc = JoinService(lanes=2, checkpoint_dir=str(tmp_path))
    [svc.submit(_pairs(s), crowd=PerfectCrowd()) for s in range(3)]
    svc._crash_after_checkpoints = 2
    with pytest.raises(ServiceKilled):
        svc.run()
    rec = JoinService.restore(str(tmp_path)).run()
    for r in rids:
        np.testing.assert_array_equal(base[r].labels, rec[r].labels)
        np.testing.assert_array_equal(base[r].crowdsourced,
                                      rec[r].crowdsourced)
