"""Scale-out join pipeline (DESIGN.md §7, §8): embeddings in, labels out.

Machine phase on the mesh (sharded candidate generation), human phase over
persistent device-resident session states (JoinService), crowd I/O through
the batched CrowdGateway — including the asynchronous instant-decision /
non-matching-first discipline against a latency-modeled crowd platform.
Runs on CPU; on a multi-device host set
XLA_FLAGS=--xla_force_host_platform_device_count=8 before running to see the
same code drive a real 4x2 mesh.

    PYTHONPATH=src python examples/sharded_join.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LatencyModel, NoisyCrowd, PerfectCrowd
from repro.launch.mesh import make_host_mesh
from repro.serve.join_service import JoinService

rng = np.random.default_rng(0)

# two record sets sharing 64 ground-truth entities, embedded with noise
n_ent, D = 64, 32
cents = rng.normal(size=(n_ent, D))
a_ids = rng.integers(0, n_ent, 300)
b_ids = rng.integers(0, n_ent, 280)
emb_a = jnp.asarray(cents[a_ids] + 0.6 * rng.normal(size=(300, D)), jnp.float32)
emb_b = jnp.asarray(cents[b_ids] + 0.6 * rng.normal(size=(280, D)), jnp.float32)

# mesh over whatever devices exist (1x1 on a plain CPU host)
n_dev = len(jax.devices())
mesh = make_host_mesh(max(n_dev // 2, 1), 2 if n_dev >= 2 else 1)
print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

# -- round-barrier serving: lanes advance in lockstep engine rounds ---------
svc = JoinService(lanes=2)
truth_fn = lambda r, c: a_ids[r] == b_ids[c]
r1 = svc.submit_embeddings(emb_a, emb_b, 0.55, mesh,
                           crowd=PerfectCrowd(), truth_fn=truth_fn)
r2 = svc.submit_embeddings(emb_a, emb_b, 0.7, mesh,
                           crowd=NoisyCrowd(error_rate=0.08),
                           truth_fn=truth_fn)
results = svc.run()
for rid, tag in ((r1, "tau=0.55 perfect"), (r2, "tau=0.70 noisy  ")):
    r = results[rid]
    print(f"{tag}: {len(r.labels)} candidates, "
          f"{r.n_crowdsourced} crowdsourced + {r.n_deduced} deduced "
          f"in {r.n_rounds} rounds — {r.quality.row()}")

# -- blocked machine phase (DESIGN.md §12) ----------------------------------
# LSH buckets in front of the scorer: only colliding buckets reach the
# fused similarity/threshold kernel, so the dense 300x280 grid is never
# scored.  The config is sized for a recall floor at the threshold
# boundary; surviving pairs score as the dense path scores them (to a few
# ulps: the tile shape sets the summation order), so the join result is
# the same minus blocker misses.
from repro.kernels.pair_scores.blocking import BlockingConfig

cfg = BlockingConfig.for_recall(0.95, threshold=0.7, n_bits=5)
svc_b = JoinService(lanes=1)
rb = svc_b.submit_embeddings(emb_a, emb_b, 0.7, mesh, crowd=PerfectCrowd(),
                             truth_fn=truth_fn, blocking=cfg)
r = svc_b.run()[rb]
print(f"blocked tau=0.70 ({cfg.n_tables} tables): {len(r.labels)} "
      f"candidates, {r.n_crowdsourced} crowdsourced + {r.n_deduced} deduced "
      f"— {r.quality.row()}")

# -- async ID/NF vs round barrier on a simulated crowd platform -------------
# Same workload, same latency model; the event-driven gateway discipline
# (fold answers as they land, re-select on non-matching returns, steer
# workers to probable-non-matching pairs first) finishes in fewer simulated
# minutes than waiting out every round (DESIGN.md §8).
latency = lambda: LatencyModel(n_workers=8, mean_minutes=30.0, seed=7)
sim_minutes = {}
for name, kwargs in (("round barrier", dict(async_mode=False)),
                     ("async id+nf ", dict(async_mode=True, nf=True))):
    sim = JoinService(lanes=2, latency=latency(), **kwargs)
    rids = [sim.submit_embeddings(emb_a, emb_b, 0.55, mesh,
                                  crowd=PerfectCrowd(), truth_fn=truth_fn)]
    res = sim.run()
    sim_minutes[name] = max(res[r].sim_minutes for r in rids)
    print(f"{name}: workload done in {sim_minutes[name]:.0f} simulated min")
speedup = sim_minutes["round barrier"] / sim_minutes["async id+nf "]
print(f"async gateway speedup: {speedup:.2f}x")
