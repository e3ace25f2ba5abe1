"""``bench/graph.py``: the vectorised SBM keeps its parameters, and the
cache hands back what it stored."""
import numpy as np
import pytest

from bench import graph

SPLIT = dict(train=10, val=2, test=3, of=100)


def build(**kw):
    args = dict(n=20000, n_classes=4, avg_degree=12.0, homophily=0.7,
                feat_dim=8, power_law=True, seed=3, split=SPLIT)
    args.update(kw)
    return args, graph.make_sbm(**args)


@pytest.mark.parametrize("power_law", [True, False])
def test_degree_and_homophily_follow_the_parameters(power_law):
    args, g = build(power_law=power_law)
    n = args["n"]
    deg = np.diff(g["indptr"])
    # each node picks floor(budget / 2) targets and is picked about as
    # often: the mean degree sits just under the budget's mean
    assert 0.9 * 12.0 < deg.mean() <= 12.0
    if power_law:
        assert deg.max() > 8 * deg.mean()        # a Pareto tail
    else:
        assert deg.max() < 3 * deg.mean()
    rows = np.repeat(np.arange(n), deg)
    same = (g["labels"][rows] == g["labels"][g["indices"]]).mean()
    # a random pick lands in the own class one time in n_classes
    assert same == pytest.approx(0.7 + 0.3 / 4, abs=0.02)
    # undirected, no self-loops, rows sorted
    assert not (rows == g["indices"]).any()
    e = rows.astype(np.int64) * n + g["indices"]
    assert np.all(np.diff(e) > 0)
    assert np.array_equal(np.sort(e), np.sort(g["indices"].astype(np.int64)
                                              * n + rows))


def test_features_are_class_conditioned_and_splits_published_shares():
    args, g = build()
    means = np.stack([g["feats"][g["labels"] == c].mean(0) for c in range(4)])
    within = np.linalg.norm(g["feats"] - means[g["labels"]], axis=1).mean()
    assert within == pytest.approx(np.sqrt(8), rel=0.05)   # unit noise
    for name, cnt in (("train", 10), ("val", 2), ("test", 3)):
        assert g[name + "_mask"].sum() == round(cnt / 100 * args["n"])
    assert not (g["train_mask"] & g["test_mask"]).any()


def test_cache_returns_what_it_stored(tmp_path):
    args = dict(build(n=500)[0])
    a, built = graph.load("c", args, str(tmp_path))
    b, again = graph.load("c", args, str(tmp_path))
    assert built is not None and again is None
    for f in graph.FIELDS:
        assert np.array_equal(a[f], b[f])
    assert graph.cache_key(args) != graph.cache_key(dict(args, seed=4))
    calls = []
    x = graph.derived("c", args, str(tmp_path), "t", ("y",),
                      lambda: calls.append(1) or (np.arange(3),))
    y = graph.derived("c", args, str(tmp_path), "t", ("y",),
                      lambda: calls.append(1) or (np.arange(3),))
    assert calls == [1] and np.array_equal(x[0], y[0])
