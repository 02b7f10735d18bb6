"""Quickstart: crowdsourced join with transitive relations in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import NoisyCrowd, PerfectCrowd, crowdsourced_join
from repro.data.entities import make_paper_dataset

# 1) machine phase: candidate pairs + matching likelihoods (synthetic
#    Cora-like dataset; see examples/crowdsourced_join.py for the kernel)
ds = make_paper_dataset()
candidates = ds.pairs.above(0.3)
print(f"dataset: {ds.n_objects} records, {len(candidates)} candidate pairs")

# 2) human phase WITHOUT transitive relations: crowdsource everything
baseline = crowdsourced_join(candidates, PerfectCrowd(), labeler="all")
print(f"non-transitive: {baseline.n_crowdsourced} pairs, "
      f"{baseline.n_hits} HITs, {baseline.cost_cents/100:.2f}$")

# 3) human phase WITH transitive relations (the paper): sort by likelihood,
#    label in parallel, deduce the rest
ours = crowdsourced_join(candidates, PerfectCrowd(), order="expected",
                         labeler="parallel")
print(f"transitive:     {ours.n_crowdsourced} pairs, {ours.n_hits} HITs, "
      f"{ours.cost_cents/100:.2f}$ in {ours.n_iterations} parallel rounds "
      f"({1 - ours.n_crowdsourced/baseline.n_crowdsourced:.0%} saved)")

# 4) with a noisy crowd (majority vote of 3), quality loss stays small
noisy = crowdsourced_join(candidates, NoisyCrowd(error_rate=0.08),
                          order="expected", labeler="parallel",
                          total_true_matches=ds.total_true_matches)
print(f"noisy crowd:    {noisy.quality.row()}")
