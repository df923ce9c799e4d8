"""The seeded graph generator."""
import numpy as np
import pytest

from bench import graphgen

CFG = {"nodes": 3000, "parts": 6, "mean_degree": 13.77, "intra_share": 0.7,
       "graph_seed": 11, "label_noise": 0.4, "train_share": 0.537}


def test_structure_is_fixed_by_the_configuration():
    a = graphgen.structure(CFG)
    b = graphgen.structure(dict(CFG))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = graphgen.structure({**CFG, "graph_seed": 12})
    assert not np.array_equal(a[1], c[1])


def test_structure_matches_its_statistics():
    indptr, indices, part = graphgen.structure(CFG)
    n = CFG["nodes"]
    assert len(indices) == 2 * round(n * CFG["mean_degree"] / 2)
    dst = np.repeat(np.arange(n), np.diff(indptr))
    # undirected, no self-loops, no duplicates
    pairs = set(zip(dst.tolist(), indices.tolist()))
    assert len(pairs) == len(indices)
    assert all((s, d) in pairs for d, s in pairs)
    assert not np.any(dst == indices)
    assert np.bincount(part).max() - np.bincount(part).min() <= 1
    # drawn inside the part, or anywhere (the part itself included)
    p = CFG["parts"]
    want = CFG["intra_share"] + (1 - CFG["intra_share"]) / p
    assert graphgen.intra_share(indptr, indices, part) == pytest.approx(
        want, abs=0.03)


def test_node_data_by_seed():
    _, _, part = graphgen.structure(CFG)
    a = graphgen.node_data(part, CFG, 16, 5, 2**31 + 3)
    b = graphgen.node_data(part, CFG, 16, 5, 2**31 + 3)
    c = graphgen.node_data(part, CFG, 16, 5, 7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    x, y, train = a
    assert x.shape == (CFG["nodes"], 16) and x.dtype == np.float32
    assert y.min() >= 0 and y.max() < 5
    assert train.mean() == pytest.approx(CFG["train_share"], abs=0.03)
