"""``bench/reference.py`` against the program's float32 einsum path at a
tiny size: the same weights, the same three Adam steps, the same capped
adjacency and mini-batch tensors.  The reference imports nothing of the
program; these tests are where the two meet."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import graph, reference as R
from bench.tests.conftest import PLAN, tiny_conf

N = 1500


@pytest.fixture(scope="module")
def g():
    return graph.make_sbm(n=N, n_classes=5, avg_degree=12.0, homophily=0.6,
                          feat_dim=16, power_law=True, seed=3,
                          split=dict(train=30, val=10, test=10, of=100))


def program(g, conf, source, seed):
    from repro.configs.base import GNNConfig
    from repro.core.engine import Callback, Trainer, TrainPlan
    from repro.core.graph import Graph
    gnn = dict(conf["gnn"], fanout=tuple(conf["gnn"]["fanout"]))
    cfg = GNNConfig(**gnn, n_nodes=N)
    kept = []

    class Keep(Callback):
        def on_step(self, state):
            kept.append(jax.device_get(state.params))

    plan = TrainPlan(n_iters=3, eval_every=100, seed=seed, optimizer="adamw",
                     lr=PLAN["lr"], deferred_sync=False)
    res = Trainer(Graph(n=N, **{f: g[f] for f in graph.FIELDS}), cfg, plan,
                  source=source, extra_callbacks=[Keep()]).run()
    return [float(x) for x in res.history.losses], kept[-1], cfg


@pytest.mark.parametrize("model,layers", [("graphsage", 2), ("gcn", 3)])
def test_fullgraph_reference_follows_the_program(g, model, layers):
    from repro.core.engine import FullGraphSource
    conf = tiny_conf("t", model, layers)
    with jax.default_matmul_precision("highest"):
        losses, p3, _ = program(g, conf, FullGraphSource(), 7)
    idx, kept = R.capped_ell(g["indptr"], g["indices"], 8)
    data = R.fullgraph_plan(g, idx, kept, layers)
    init, step = R.fullgraph_step(conf["gnn"], PLAN)
    ref_l, _, ref_p3 = R.three_steps(
        init, step, R.init_params(conf["gnn"], 7),
        [(jnp.asarray(g["feats"]), data)] * 3)
    np.testing.assert_allclose(losses, ref_l, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(ref_p3)):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("model,layers", [("graphsage", 2), ("gcn", 3)])
def test_sampled_reference_follows_the_program(g, model, layers):
    from repro.core.engine import SampledSource
    conf = tiny_conf("t", model, layers)
    drawn = []
    src = SampledSource(prefetch=False)
    sample = src._sample
    src._sample = lambda *a: drawn.append(sample(*a)) or drawn[-1]
    with jax.default_matmul_precision("highest"):
        losses, p3, cfg = program(g, conf, src, 11)
    batches = []
    for fb in drawn[:3]:
        t, faults = R.sampled_tensors(g, fb.nodes, cfg.fanout)
        assert faults == 0
        for d in range(layers):       # the reference's own arithmetic
            np.testing.assert_array_equal(t["masks"][d], fb.masks[d])
            np.testing.assert_allclose(t["weights"][d], fb.weights[d],
                                       rtol=1e-6)
        batches.append((t,))
    init, step = R.sampled_step(conf["gnn"], PLAN)
    ref_l, _, ref_p3 = R.three_steps(init, step, R.init_params(
        conf["gnn"], 11), batches)
    np.testing.assert_allclose(losses, ref_l, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(ref_p3)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_capped_ell_matches_the_program(g):
    from repro.core.graph import Graph, to_ell
    idx, kept = R.capped_ell(g["indptr"], g["indices"], 8)
    p_idx, p_w, _ = to_ell(Graph(n=N, **{f: g[f] for f in graph.FIELDS}),
                           max_deg=8)
    real = p_w > 0
    np.testing.assert_array_equal(real.sum(1), kept)
    np.testing.assert_array_equal(np.where(real, p_idx, -1),
                                  np.where(real, idx, -1))


def test_sampler_faults_are_counted(g):
    from repro.core.graph import Graph
    from repro.core.sampler import sample_batch
    fb = sample_batch(np.random.default_rng(0),
                      Graph(n=N, **{f: g[f] for f in graph.FIELDS}), 16,
                      (5, 3))
    assert R.sampled_tensors(g, fb.nodes, (5, 3))[1] == 0
    bad = [x.copy() for x in fb.nodes]
    deg = np.diff(g["indptr"])
    row = int(np.nonzero(deg[bad[0]] >= 5)[0][0])
    nb = set(g["indices"][g["indptr"][bad[0][row]]:
                          g["indptr"][bad[0][row] + 1]].tolist())
    bad[1][row, 0] = next(v for v in range(N) if v not in nb)   # a non-edge
    bad[0][1] = bad[0][0]                                       # a repeat
    assert R.sampled_tensors(g, bad, (5, 3))[1] >= 2
