"""The GAT module (``bench/models/gat.py``) and the two cells that came
with it: the reference against the program's first three steps on the
full graph and on sampled batches, the counts by hand and at the cells'
sizes, the configuration, the padding reader, and a tiny GAT cell run
end to end through ``bench/run.py``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, graph, models, reference as R
from bench.tests.conftest import PLAN, ROOT, tiny_conf
from bench.tests.test_bench_reference import program

N = 1500
SEED = 2 ** 31 + 77


def gat_conf(layers, **gnn):
    # hidden 30 over 3 heads: d_head 10; the last layer averages 3 heads
    # of the 5 classes
    return tiny_conf("t", "gat", layers, **dict(dict(hidden=30, gat_heads=3),
                                                 **gnn))


@pytest.fixture(scope="module")
def g():
    return graph.make_sbm(n=N, n_classes=5, avg_degree=12.0, homophily=0.6,
                          feat_dim=16, power_law=True, seed=3,
                          split=dict(train=30, val=10, test=10, of=100))


def assert_follows(losses, p3, ref_l, ref_p3):
    # float32 at highest precision on both sides, the sums in another
    # order: the tolerances of the GCN and GraphSAGE cases
    np.testing.assert_allclose(losses, ref_l, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(ref_p3)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_fullgraph_reference_follows_the_program(g):
    from repro.core.engine import FullGraphSource
    conf = gat_conf(3)
    with jax.default_matmul_precision("highest"):
        losses, p3, _ = program(g, conf, FullGraphSource(), 7)
    idx, kept = R.capped_ell(g["indptr"], g["indices"], 8)
    init, step = R.fullgraph_step(conf["gnn"], PLAN)
    ref_l, _, ref_p3 = R.three_steps(
        init, step, R.init_params(conf["gnn"], 7),
        [(jnp.asarray(g["feats"]), R.fullgraph_plan(g, idx, kept, 3))] * 3)
    assert_follows(losses, p3, ref_l, ref_p3)


def test_sampled_reference_follows_the_program(g):
    from repro.core.engine import SampledSource
    conf = gat_conf(2)
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
        batches.append((t,))
    init, step = R.sampled_step(conf["gnn"], PLAN)
    ref_l, _, ref_p3 = R.three_steps(init, step, R.init_params(
        conf["gnn"], 11), batches)
    assert_follows(losses, p3, ref_l, ref_p3)


@pytest.mark.parametrize("kernel", [False, True], ids=["einsum", "kernel"])
def test_program_forward_and_grads_match_the_module(kernel):
    """The program's full-graph GAT forward, on the kernel path (the
    Pallas interpreter here) and on the einsum path, against the module's
    layer on gathered raw rows: the output and the gradients of every
    layer's ``w``, ``a_src`` and ``a_dst``.  3 heads of 10 columns (no
    multiple of a lane tile), the last layer averaging 3 heads of 5
    classes; rows with masked slots and one whose only edge is its self
    edge.  float32 at highest on both sides, the sums in another order:
    1e-5 of each leaf's largest entry."""
    from repro.configs.base import GNNConfig
    from repro.core import gnn as G
    small = graph.make_sbm(n=96, n_classes=5, avg_degree=6.0, homophily=0.6,
                           feat_dim=16, power_law=True, seed=4,
                           split=dict(train=30, val=10, test=10, of=100))
    idx, kept = R.capped_ell(small["indptr"], small["indices"], 6)
    kept[7] = 0                                     # only the self edge
    mask = (np.arange(6)[None, :] < kept[:, None]).astype(np.float32)
    assert ((mask.sum(1) > 0) & (mask.sum(1) < 6)).any()
    gnn = gat_conf(2)["gnn"]
    cfg = GNNConfig(**dict(gnn, fanout=tuple(gnn["fanout"]),
                           use_agg_kernel=kernel, agg_d_tile=8),
                    n_nodes=small["labels"].size)
    params = G.init_gnn(jax.random.key(SEED), cfg, gnn["feat_dim"])
    feats, idx = jnp.asarray(small["feats"]), jnp.asarray(idx)
    m, maskj = models.load("gat"), jnp.asarray(mask)

    def program_out(p):
        return G.full_graph_forward(p, cfg, feats, idx, maskj,
                                    jnp.ones(mask.shape[0], jnp.float32))

    def module_out(p):
        h = feats
        for li, pl in enumerate(p):
            last = li == len(p) - 1
            h = R._layer(m, pl, last, h, h[idx], maskj, maskj, None, False)
        return h

    with jax.default_matmul_precision("highest"):
        got, want = program_out(params), module_out(params)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        g_got, g_want = (jax.grad(lambda p: jnp.sum(jnp.sin(f(p))))(params)
                         for f in (program_out, module_out))
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max())
        assert scale > 0
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, rtol=0)


def test_weights_are_the_programs():
    from repro.configs.base import GNNConfig
    from repro.core import gnn as G
    gnn = gat_conf(3)["gnn"]
    cfg = GNNConfig(**dict(gnn, fanout=tuple(gnn["fanout"])), n_nodes=N)
    want = G.init_gnn(jax.random.key(SEED), cfg, gnn["feat_dim"])
    got = R.init_params(gnn, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and np.array_equal(a, b)


def test_counts_by_hand():
    """3 heads over hidden 6 (d_head 2), 2 classes a head on the last
    layer; bf16 tables: per head, per edge a 2-byte row of d_head, an
    int32 id and a 2-byte weight, a written row, and the fused self row
    and weight."""
    gnn = dict(model="gat", feat_dim=4, hidden=6, gat_heads=3, n_classes=2,
               n_layers=2, dtype="bfloat16")
    c = counts.fullgraph(gnn, n=5, edges=8)

    def call(dh):
        return {"flops": 2 * 8 * dh + 2 * 5 * dh,
                "bytes": 8 * (dh * 2 + 4 + 2) + 5 * dh * 2 + 5 * (dh * 2 + 2)}
    assert c["agg_calls"] == [call(2)] * 3 + [call(2)] * 3
    # W once per node and two scores per node and head
    dense = (2 * 5 * 4 * 6 + 4 * 5 * 6) + (2 * 5 * 6 * 6 + 4 * 5 * 6)
    assert c["model_flops"] == 3 * (dense + 6 * call(2)["flops"])


@pytest.mark.parametrize("gnn", [dict(hidden=31), dict(gat_heads=None)])
def test_a_gat_configuration_states_heads_that_split_hidden(gnn):
    conf = gat_conf(2, **gnn)["gnn"]
    if conf["gat_heads"] is None:
        del conf["gat_heads"]
    with pytest.raises(ValueError, match="gat_heads|split"):
        counts.fullgraph(conf, n=5, edges=8)


#: counts at real sizes: arxiv-gat-full on the built graph's nodes and
#: kept edges (as arxiv-full); papers-sampled-b1024 is the sampled-b1024
#: traffic on papers-sampled's graph, its real slots per hop scaled by
#: 1024 / 8192
CELL_COUNTS = {
    "arxiv-gat-full": ("gat-arxiv", "fullgraph",
                       dict(n=169343, edges=2082800),
                       (9, 785702270880.0, 7296943320.0, 7961134014.0)),
    "papers-sampled-b1024": ("sage-papers100m", "sampled",
                             dict(batch=1024, fanouts=[15, 10],
                                  edges=[15342.420673076924, 153599.90625]),
                             (3, 7136828928.0, 51104256.0, 113119968.0)),
}


@pytest.mark.parametrize("name", sorted(CELL_COUNTS))
def test_cell_counts_are_pinned(name):
    config, kind, args, want = CELL_COUNTS[name]
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        gnn = json.load(f)["gnn"]
    c = getattr(counts, kind)(gnn, **args)
    calls = c["agg_calls"]
    assert (len(calls), c["model_flops"], sum(x["flops"] for x in calls),
            sum(x["bytes"] for x in calls)) == want


def test_gat_arxiv_configuration_is_complete():
    from repro.configs.base import GNNConfig
    path = os.path.join(ROOT, "bench", "configs", "{}.json")
    with open(path.format("gat-arxiv")) as f:
        conf = json.load(f)
    with open(path.format("gcn-arxiv")) as f:
        gcn = json.load(f)
    gnn = conf["gnn"]
    GNNConfig(**dict(gnn, fanout=tuple(gnn["fanout"])),
              n_nodes=conf["data"]["n"]).validate()
    pub = conf["published"]
    assert (gnn["n_layers"], gnn["gat_heads"]) == (pub["n_layers"],
                                                   pub["n_heads"])
    assert gnn["hidden"] == pub["n_heads"] * pub["n_hidden_per_head"]
    assert conf["data"] == gcn["data"] and conf["reduced"] == []
    assert {"lr", "departures", "max_degree", "dtype"} <= set(conf["assumed"])
    assert models.load("gat").transforms_first(128, 750) is False


def test_attn_col_pad_share_reads_the_counters():
    from bench.registry import Registry
    from repro.core import tracing
    read = Registry(ROOT).reader("attn_col_pad_share.full")
    tracing.clear()
    assert read({}) is None
    # the cell's layers: 3 heads of 250 columns padded to 256 twice, and
    # 3 of 40 padded to 128
    tracing.count("attn_cols", 3 * 250 * 2 + 3 * 40)
    tracing.count("attn_kernel_cols", 3 * 256 * 2 + 3 * 128)
    assert read({}) == pytest.approx(15.625)
    tracing.clear()


def test_tiny_gat_cell_runs_end_to_end(cpu_cell, capsys, tmp_path):
    """A tiny GAT configuration in the full-graph mix, traced: correct,
    with the padding readers on the result line (the einsum path pads no
    column)."""
    from bench.tests.conftest import make_root
    root = make_root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "tgat.json"), "w") as f:
        json.dump(dict(gat_conf(3), gnn=dict(gat_conf(3)["gnn"],
                                             name="tgat")), f)
    with open(os.path.join(bench, "limits", "sage-full.json")) as f:
        lim = json.load(f)
    with open(os.path.join(bench, "limits", "gat-full.json"), "w") as f:
        json.dump(lim, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name="tgat",
                                file="bench/configs/tgat.json"))
    spec["workloads"].append(dict(name="gat-full", config="tgat",
                                  traffic="full", chips=1))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sage-full" in m.get("workloads", []) or m["name"].startswith(
                "attn_col_pad_share"):
            m["workloads"].append("gat-full")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    assert cpu_cell.main(["--workload", "gat-full", "--seed", str(SEED),
                          "--seconds", "0.5", "--trace", "1"],
                         root=root) == 0
    result = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["attn_col_pad_share.full"]["value"] == 0.0
    assert "agg_pad_share.full" in result["metrics"]
