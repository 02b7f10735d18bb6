"""End-to-end hybrid human-machine join — the paper's full pipeline with the
service's own machine phase: the records' seeded embedding vectors go
through the Pallas pair-scores kernel (``sharded_candidates`` on a 1x1
mesh, the program ``JoinService.submit_embeddings`` runs), the thresholded
cosine scores become the likelihoods, and the transitive labeling framework
drives a simulated AMT deployment.

    PYTHONPATH=src python examples/crowdsourced_join.py [--records 256]
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import (CostModel, LatencyModel, NoisyCrowd, PairSet,
                        PerfectCrowd, crowdsourced_join, get_order,
                        simulate_wallclock_parallel_id,
                        simulate_wallclock_sequential)
from repro.data.entities import make_product_dataset
from repro.kernels.pair_scores.sharded import sharded_candidates
from repro.launch.mesh import make_host_mesh


def record_vectors(entity_of: np.ndarray, dim: int = 64, seed: int = 0
                   ) -> np.ndarray:
    """One embedding per record: its entity's centroid plus record noise.
    Entities come in product families of eight that share most of their
    direction, so a family's other products are the near-duplicates a
    real embedding model confuses."""
    rng = np.random.default_rng(seed)
    n_ent = int(entity_of.max()) + 1
    family = rng.normal(size=(n_ent // 8 + 1, dim))
    centroid = (1.2 * family[np.arange(n_ent) // 8]
                + rng.normal(size=(n_ent, dim)))
    noise = rng.uniform(0.4, 1.1, size=(len(entity_of), 1))
    return (centroid[entity_of] + noise
            * rng.normal(size=(len(entity_of), dim))).astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=256)
    ap.add_argument("--threshold", type=float, default=0.45)
    args = ap.parse_args()

    # ---- records: bipartite product catalogs -------------------------------
    ds = make_product_dataset()
    n_a = min(args.records, 1081)
    n_b = min(args.records, 1092)
    ents_a = ds.entity_of[:n_a]
    ents_b = ds.entity_of[1081:1081 + n_b]
    vecs = record_vectors(ds.entity_of)
    emb_a = jnp.asarray(vecs[:n_a])
    emb_b = jnp.asarray(vecs[1081:1081 + n_b])

    # ---- machine phase: pair_scores kernel + on-device compaction ----------
    t0 = time.time()
    cand = sharded_candidates(emb_a, emb_b, args.threshold,
                              make_host_mesh(1, 1))
    truth = ents_a[cand.rows] == ents_b[cand.cols]
    n_true = int((ents_a[:, None] == ents_b[None, :]).sum())
    pairs = PairSet(cand.rows, (cand.cols + n_a).astype(np.int32),
                    cand.scores, truth, n_objects=n_a + n_b)
    print(f"[machine] scored {n_a}x{n_b} pairs with the pair_scores kernel "
          f"in {time.time()-t0:.1f}s: {len(pairs)} candidates above "
          f"{args.threshold} ({int(truth.sum())} of {n_true} true matches)")

    # ---- human phase: transitive parallel labeling on simulated AMT --------
    res = crowdsourced_join(pairs, NoisyCrowd(error_rate=0.08),
                            order="expected", labeler="parallel",
                            total_true_matches=n_true)
    print(f"[human]   crowdsourced {res.n_crowdsourced}/{len(pairs)} pairs "
          f"in {res.n_iterations} rounds -> {res.n_hits} HITs, "
          f"{res.cost_cents/100:.2f}$")
    if res.quality:
        print(f"[quality] {res.quality.row()}")

    # ---- §15 serving: per-worker reliability + cluster tasks ---------------
    # the same candidates through the serving layer, over a heterogeneous
    # worker pool: EM aggregation learns who to trust, and mixed scheduling
    # posts multi-pair cluster tasks whenever they beat the pair rate
    from repro.serve.join_service import JoinService

    def pool():
        return NoisyCrowd(error_rate=0.1, n_assignments=3, seed=1,
                          n_workers=25, worker_concentration=3.0,
                          qualification=False)

    for tag, kw in (("majority pairs", {}),
                    ("em + clusters", {"aggregation": "em",
                                       "cluster_tasks": True})):
        svc = JoinService(lanes=1, **kw)
        rid = svc.submit(pairs, pool(), total_true_matches=n_true)
        r = svc.run()[rid]
        print(f"[serve]   {tag:14s} F={r.quality.f_measure:.3f} "
              f"spent={r.n_spent_cents:.0f}c "
              f"cluster_tasks={r.n_cluster_tasks} "
              f"cluster_pairs={r.n_cluster_pairs}")

    # ---- wall-clock: Parallel(ID) vs Non-Parallel on the AMT simulator -----
    order = get_order(pairs, "expected")
    cost, lat = CostModel(), LatencyModel(n_workers=20)
    par = simulate_wallclock_parallel_id(pairs, order, PerfectCrowd(), cost,
                                         lat)
    seq_h = simulate_wallclock_sequential(par.hits, cost, lat)
    print(f"[latency] Non-Parallel {seq_h:.1f}h vs Parallel(ID) "
          f"{par.hours:.1f}h ({seq_h/max(par.hours, 1e-9):.1f}x)")


if __name__ == "__main__":
    main()
