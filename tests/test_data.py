"""Synthetic datasets: calibration and determinism."""
import numpy as np

from repro.data.entities import make_paper_dataset, make_product_dataset


def test_paper_dataset_calibration(paper_ds):
    sizes = paper_ds.cluster_sizes()
    assert sizes[0] == 102                       # Figure 11: one 102-cluster
    assert paper_ds.n_objects == 997
    c3 = paper_ds.pairs.above(0.3)
    assert 15_000 < len(c3) < 60_000             # paper: 29,281
    assert 10_000 < paper_ds.total_true_matches < 30_000


def test_product_dataset_calibration(product_ds):
    assert product_ds.n_objects == 1081 + 1092
    sizes = product_ds.cluster_sizes()
    assert sizes[0] <= 6                         # tiny clusters only
    c2 = product_ds.pairs.above(0.2)
    assert 3_000 < len(c2) < 12_000              # paper: 8,315
    # bipartite: candidates never join two same-source records
    assert ((product_ds.pairs.u < 1081) & (product_ds.pairs.v >= 1081)).all()


def test_dataset_determinism():
    a = make_paper_dataset(seed=0)
    b = make_paper_dataset(seed=0)
    np.testing.assert_array_equal(a.pairs.likelihood, b.pairs.likelihood)
    c = make_paper_dataset(seed=1)
    assert len(c.pairs) != len(a.pairs) or \
        not np.array_equal(a.pairs.likelihood, c.pairs.likelihood)
