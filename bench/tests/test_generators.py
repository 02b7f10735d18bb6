"""The benchmark's generators: the Cora copy against the program's, and the
Walmart-Amazon shape against its source."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness


@pytest.fixture(scope="module")
def cora():
    return harness.Cell.find("cora.perfect")


@pytest.mark.parametrize("seed", [0, 1, 7, 2 ** 31 + 77])
def test_cora_copy_is_bit_identical(cora, seed):
    from repro.data.entities import make_paper_dataset

    d = make_paper_dataset(seed=seed)
    ps = d.pairs.above(cora.config["likelihood_threshold"])
    g = cora.generator.generate(cora.config, seed)
    for k in ("u", "v", "likelihood", "truth"):
        assert np.array_equal(getattr(ps, k), g[k]), k
    assert g["n_objects"] == ps.n_objects == 997
    assert g["total_true_matches"] == d.total_true_matches


def test_cora_pool_relabels_the_same_work(cora):
    p1 = cora.generator.pool(cora.config, 5, 4)
    p2 = cora.generator.pool(cora.config, 6, 4)
    for a, b in zip(p1, p2):
        assert len(a["u"]) == len(b["u"])
        assert a["truth"].sum() == b["truth"].sum()
        assert not np.array_equal(a["u"], b["u"])


def test_walmart_amazon_matches_the_source_shape():
    wa = harness.Cell.find("wa.dense")
    c = wa.config
    st = wa.generator.structure(c, [2 ** 31 + 3, 0])
    assert len(st["ent_a"]) == c["n_a"] == 2554
    assert len(st["ent_b"]) == c["n_b"] == 22074
    shared = np.intersect1d(st["ent_a"], st["ent_b"])
    assert len(shared) == c["n_matches"] == 962
    # one-to-one: each matched entity has one record on each side
    assert len(np.unique(st["ent_b"])) == c["n_b"]


def test_walmart_amazon_candidates_near_the_source_count():
    wa = harness.Cell.find("wa.dense")
    g = wa.generator.generate(wa.config, [4, 0])
    a, b = np.asarray(g["a"]), np.asarray(g["b"])
    s = a @ b.T
    r, c = np.nonzero(s >= g["threshold"])
    matches = int((g["ent_a"][r] == g["ent_b"][c]).sum())
    assert abs(len(r) - wa.config["source_candidates"]) < 0.03 * 10242
    assert matches == 962
