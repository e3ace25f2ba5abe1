"""Scenario-diverse BatchSources (cluster / importance / sharded
mini-batch) + the hardened sampling/boundary layer: fixed-seed
determinism per source, 1-device bit-equality for the sharded
mini-batch, boundary paths (b == n_train, b > n_train, single-node
clusters, beta > d_max, unnormalized importance scores), and the
regression tests for the max_deg-truthiness, empty-train-split and
stuck-Prefetcher satellites."""
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core.engine import (ClusterSource, FullGraphSource,
                               ImportanceSampledSource, SampledSource,
                               ShardedFullGraphSource,
                               ShardedSampledSource, Trainer, TrainPlan,
                               _device_ell)
from repro.core.experiment import make_source, run_experiment, sweep
from repro.core.gnn import gnn_loss
from repro.core.graph import to_ell
from repro.core.prefetch import Prefetcher
from repro.core.sampler import expand_batch, sample_batch
from repro.data import make_sbm_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(g, **kw):
    base = dict(name="src", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=32,
                n_classes=g.n_classes, n_layers=2, fanout=(5, 3),
                batch_size=64, loss="ce")
    base.update(kw)
    return GNNConfig(**base)


@pytest.fixture(scope="module")
def graph():
    return make_sbm_graph(n=240, n_classes=4, avg_degree=8, feat_dim=16,
                          seed=31)


def _no_train(g):
    empty = np.zeros(g.n, bool)
    return dataclasses.replace(g, train_mask=empty)


# ---------------------------------------------------------------------------
# ClusterSource
# ---------------------------------------------------------------------------

def test_cluster_source_trains_and_is_deterministic(graph):
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=6, eval_every=3, seed=0)
    r1 = Trainer(graph, cfg, plan, source=ClusterSource()).run()
    r2 = Trainer(graph, cfg, plan, source=ClusterSource()).run()
    assert r1.history.losses == r2.history.losses
    assert r1.history.val_accs == r2.history.val_accs
    assert r1.final_test_acc == r2.final_test_acc
    assert all(np.isfinite(r1.history.losses))
    assert all(n >= 1 for n in r1.history.nodes_processed)


def test_cluster_source_compiles_one_fixed_shape(graph):
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=5, seed=0)
    t = Trainer(graph, cfg, plan, source=ClusterSource())
    t.run()
    assert t._step._cache_size() == 1          # padded (m_max, K) shape


def test_cluster_source_single_node_clusters(graph):
    """n_parts = n degenerates to single-node clusters: every batch is k
    isolated nodes with w_self = 1 — the boundary the induced-degree
    weights must survive."""
    src = ClusterSource(clusters_per_batch=4, n_parts=graph.n)
    plan = TrainPlan(lr=0.3, n_iters=4, seed=0)
    res = Trainer(graph, _cfg(graph), plan, source=src).run()
    assert all(len(c) == 1 for c in src.blocks.clusters)
    assert src.m_max == 4 and src.K == 1
    assert all(np.isfinite(res.history.losses))


def test_cluster_source_through_run_experiment(graph):
    row = run_experiment(graph, _cfg(graph), TrainPlan(lr=0.3, n_iters=3),
                         paradigm="cluster", b=48)
    assert row["paradigm"] == "cluster"
    assert row["fanouts"].startswith("clusters(k=")
    assert row["iters"] == 3


def test_cluster_source_requires_a_train_cluster(graph):
    with pytest.raises(ValueError, match="no cluster contains"):
        ClusterSource().bind(_no_train(graph), _cfg(graph),
                             TrainPlan(n_iters=1))


def test_cluster_source_rejects_bad_params():
    with pytest.raises(ValueError, match="clusters_per_batch"):
        ClusterSource(clusters_per_batch=0)
    with pytest.raises(ValueError, match="n_parts"):
        ClusterSource(n_parts=0)


# ---------------------------------------------------------------------------
# ImportanceSampledSource
# ---------------------------------------------------------------------------

def test_importance_weights_are_unbiased_by_construction(graph):
    src = ImportanceSampledSource().bind(graph, _cfg(graph),
                                         TrainPlan(n_iters=1))
    # E_p[w] = sum_j p_j * 1/(n p_j) = 1 regardless of the score scale
    assert np.isclose(float((src._p * src._w).sum()), 1.0)
    assert (src._w > 0).all()


def test_importance_deterministic_and_converges(graph):
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=8, eval_every=4, seed=0)
    r1 = Trainer(graph, cfg, plan, source=ImportanceSampledSource()).run()
    r2 = Trainer(graph, cfg, plan, source=ImportanceSampledSource()).run()
    assert r1.history.losses == r2.history.losses
    assert r1.final_test_acc == r2.final_test_acc
    assert all(np.isfinite(r1.history.losses))


def test_importance_scores_need_not_sum_to_one(graph):
    """Scores are a PROPOSAL, not a distribution: scaling them by any
    constant (their sum is far from 1 either way) must not change the
    run — normalization and the 1/(n p) reweighting absorb it."""
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=5, seed=0)
    s = (graph.degrees + 1).astype(np.float64)          # sums to ~2000
    r1 = Trainer(graph, cfg, plan,
                 source=ImportanceSampledSource(scores=s)).run()
    r2 = Trainer(graph, cfg, plan,
                 source=ImportanceSampledSource(scores=17.0 * s)).run()
    np.testing.assert_allclose(r1.history.losses, r2.history.losses,
                               rtol=1e-6, atol=1e-6)


def test_importance_batch_larger_than_train_split(graph):
    """Sampling WITH replacement makes b > n_train legal without
    padding: the batch just revisits nodes, weights keep the estimator
    unbiased."""
    n_train = len(graph.train_nodes)
    b = n_train + 16
    cfg = _cfg(graph, batch_size=b)
    src = ImportanceSampledSource(batch_size=b)
    res = Trainer(graph, cfg, TrainPlan(lr=0.3, n_iters=3, seed=0),
                  source=src).run()
    assert src.pad == 0
    assert res.history.nodes_processed[0] == b
    assert all(np.isfinite(res.history.losses))


def test_importance_grad_norm_scores_mode(graph):
    src = ImportanceSampledSource(scores="grad")
    res = Trainer(graph, _cfg(graph), TrainPlan(lr=0.3, n_iters=3, seed=0),
                  source=src).run()
    assert (src._p > 0).all()
    assert all(np.isfinite(res.history.losses))


def test_importance_rejects_bad_scores(graph):
    cfg, plan = _cfg(graph), TrainPlan(n_iters=1)
    with pytest.raises(ValueError, match="non-negative"):
        ImportanceSampledSource(
            scores=-np.ones(graph.n)).bind(graph, cfg, plan)
    with pytest.raises(ValueError, match="length"):
        ImportanceSampledSource(scores=np.ones(7)).bind(graph, cfg, plan)
    with pytest.raises(ValueError, match="unknown scores"):
        ImportanceSampledSource(scores="nope").bind(graph, cfg, plan)


def test_gnn_loss_weight_oracle():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(8, 3)).astype(np.float32)
    labels = rng.integers(0, 3, 8).astype(np.int32)
    w = rng.uniform(0.5, 2.0, 8).astype(np.float32)
    valid = np.ones(8, np.float32)
    got = float(gnn_loss(logits, labels, "ce", 3, valid=valid, weight=w))
    z = logits.astype(np.float64)
    rows = (np.log(np.exp(z).sum(-1))
            - z[np.arange(8), labels])
    assert np.isclose(got, float((rows * w).mean()), atol=1e-5)
    # weight of exactly 1.0 leaves the loss untouched
    plain = float(gnn_loss(logits, labels, "ce", 3))
    ones = float(gnn_loss(logits, labels, "ce", 3,
                          weight=np.ones(8, np.float32)))
    assert plain == ones


# ---------------------------------------------------------------------------
# ShardedSampledSource
# ---------------------------------------------------------------------------

def test_sharded_minibatch_bit_equals_plain_on_one_device(graph):
    """The mini-batch twin of PR 3's sharded full-graph equality: on a
    1-device mesh the host batches, compiled step and loss sequence are
    identical bit-for-bit."""
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=6, eval_every=2, seed=0,
                     track_full_loss_every=3)
    r_plain = Trainer(graph, cfg, plan, source=SampledSource()).run()
    t = Trainer(graph, cfg, plan, source=ShardedSampledSource())
    r_shard = t.run()
    assert r_plain.history.losses == r_shard.history.losses
    assert r_plain.history.val_accs == r_shard.history.val_accs
    assert r_plain.history.full_losses == r_shard.history.full_losses
    assert r_plain.final_test_acc == r_shard.final_test_acc
    # stable input shardings from iteration 0: exactly one compile
    assert t._step._cache_size() == 1


def test_sharded_minibatch_batch_is_row_sharded(graph):
    from jax.sharding import NamedSharding
    cfg = _cfg(graph)
    src = ShardedSampledSource().bind(graph, cfg, TrainPlan(n_iters=2))
    stream = src.batches()
    batch, n = next(stream)
    import jax
    for leaf in jax.tree.leaves(batch):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec[0] == "data"
    src.close()


_MULTIDEV_SCRIPT = r"""
import jax, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.data import make_sbm_graph
from repro.configs.base import GNNConfig
from repro.core.engine import (SampledSource, ShardedSampledSource,
                               Trainer, TrainPlan)
g = make_sbm_graph(n=240, n_classes=4, avg_degree=8, feat_dim=16, seed=5)
cfg = GNNConfig(name="md", model="graphsage", n_nodes=g.n, feat_dim=16,
                hidden=32, n_classes=g.n_classes, n_layers=2,
                fanout=(5, 3), batch_size=30, loss="ce")
plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0)
r1 = Trainer(g, cfg, plan, source=SampledSource(batch_size=30)).run()
src = ShardedSampledSource(batch_size=30)   # 30 % 4 != 0 -> pads to 32
r2 = Trainer(g, cfg, plan, source=src).run()
assert src.b == 32 and src.pad == 2, (src.b, src.pad)
np.testing.assert_allclose(r1.history.losses, r2.history.losses,
                           atol=1e-5, rtol=1e-5)
print("MULTIDEV_MB_OK", r2.history.losses)
"""


def test_sharded_minibatch_runs_on_multidevice_cpu_mesh():
    """4 virtual CPU devices (own process — the flag must be set before
    jax initializes): data-parallel mini-batches with a non-divisible b
    (masked-row padding) match the single-device losses to float
    tolerance."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_MB_OK" in out.stdout


# ---------------------------------------------------------------------------
# Boundary paths shared by the sources
# ---------------------------------------------------------------------------

def test_batch_size_equals_train_split_exact_fit(graph):
    n_train = len(graph.train_nodes)
    cfg = _cfg(graph, batch_size=n_train)
    src = SampledSource(batch_size=n_train)
    res = Trainer(graph, cfg, TrainPlan(lr=0.3, n_iters=3, seed=0),
                  source=src).run()
    assert src.pad == 0                        # no masked rows needed
    assert res.history.nodes_processed[0] == n_train


def test_fanout_beyond_max_degree_keeps_all_neighbors(graph):
    beta = graph.d_max + 3
    rng = np.random.default_rng(0)
    targets = graph.train_nodes[:32]
    fb = expand_batch(rng, graph, targets, (beta,))
    # every row keeps exactly its true degree: no truncation, rest padded
    np.testing.assert_array_equal(fb.masks[0].sum(-1),
                                  graph.degrees[targets])
    cfg = _cfg(graph, n_layers=1, fanout=(beta,), batch_size=32)
    res = Trainer(graph, cfg, TrainPlan(lr=0.3, n_iters=2, seed=0),
                  source=SampledSource(batch_size=32, fanouts=(beta,))
                  ).run()
    assert all(np.isfinite(res.history.losses))


def test_sweep_runs_the_sampler_cube(graph):
    cfg = _cfg(graph, n_layers=1, fanout=(3,), batch_size=32)
    rows = sweep(graph, cfg, TrainPlan(lr=0.3, n_iters=2),
                 batch_sizes=[32], fanout_grid=[(3,)],
                 sources=("minibatch", "cluster", "importance"))
    assert [r["paradigm"] for r in rows] == ["minibatch", "cluster",
                                             "importance"]


def test_sweep_does_not_duplicate_cluster_points_across_fanouts(graph):
    """Fan-out does not apply to cluster batches: a fanout grid must not
    rerun identical, identically-labelled cluster points."""
    cfg = _cfg(graph, n_layers=1, fanout=(3,), batch_size=32)
    rows = sweep(graph, cfg, TrainPlan(lr=0.3, n_iters=2),
                 batch_sizes=[32], fanout_grid=[(2,), (3,)],
                 sources=("minibatch", "cluster"))
    assert [r["paradigm"] for r in rows].count("cluster") == 1
    assert [r["paradigm"] for r in rows].count("minibatch") == 2


def test_make_source_dispatches_all_paradigms():
    assert isinstance(make_source("minibatch_sharded", b=8, fanouts=(2,)),
                      ShardedSampledSource)
    assert isinstance(make_source("cluster", b=8), ClusterSource)
    assert isinstance(make_source("importance", b=8, fanouts=(2,)),
                      ImportanceSampledSource)
    with pytest.raises(ValueError, match="paradigm"):
        make_source("nope")


# ---------------------------------------------------------------------------
# Satellite: max_deg truthiness (explicit 0 must error, not fall back)
# ---------------------------------------------------------------------------

def test_max_deg_zero_is_rejected_not_silently_uncapped(graph):
    with pytest.raises(ValueError, match="max_deg"):
        to_ell(graph, max_deg=0)
    with pytest.raises(ValueError, match="max_deg"):
        _device_ell(graph, 0)
    with pytest.raises(ValueError, match="max_deg"):
        FullGraphSource(max_deg=0).bind(graph, _cfg(graph),
                                        TrainPlan(n_iters=1))
    with pytest.raises(ValueError, match="max_deg"):
        ShardedFullGraphSource(max_deg=-2).bind(graph, _cfg(graph),
                                                TrainPlan(n_iters=1))
    # None still means "uncapped d_max"
    idx, w, ws = to_ell(graph, max_deg=None)
    assert idx.shape[1] == graph.d_max


# ---------------------------------------------------------------------------
# Satellite: empty/overflowed train split fails with a clear message
# ---------------------------------------------------------------------------

def test_sample_batch_empty_train_split_clear_error(graph):
    g0 = _no_train(graph)
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="n_train=0"):
        sample_batch(rng, g0, 16, (3, 2))


def test_sample_batch_strict_names_b_and_n_train(graph):
    rng = np.random.default_rng(0)
    n_train = len(graph.train_nodes)
    with pytest.raises(ValueError,
                       match=rf"b={n_train + 5} > n_train={n_train}"):
        sample_batch(rng, graph, n_train + 5, (3, 2), strict=True)
    with pytest.raises(ValueError, match="batch_size"):
        sample_batch(rng, graph, 0, (3, 2))
    # non-strict keeps the engine's clamp-then-pad contract
    fb = sample_batch(rng, graph, n_train + 5, (3, 2))
    assert fb.batch_size == n_train


def test_sampled_source_checks_train_split_up_front(graph):
    g0 = _no_train(graph)
    with pytest.raises(ValueError, match="no training nodes"):
        SampledSource().bind(g0, _cfg(g0), TrainPlan(n_iters=1))


def test_gnnconfig_rejects_batch_beyond_graph(graph):
    cfg = _cfg(graph, batch_size=graph.n + 1)
    with pytest.raises(ValueError, match="n_nodes"):
        cfg.validate()
    _cfg(graph, batch_size=graph.n).validate()   # boundary is legal


# ---------------------------------------------------------------------------
# Satellite: Prefetcher close() diagnoses a stuck worker; a worker dying
# mid-batch releases its staging slot
# ---------------------------------------------------------------------------

def test_prefetcher_close_warns_on_stuck_worker(graph):
    release = threading.Event()

    def stuck_payload(g, fb):
        release.wait(timeout=30)
        return []

    pf = Prefetcher(graph, 8, (2,), payload_fn=stuck_payload)
    time.sleep(0.2)                    # let the worker enter the payload
    try:
        with pytest.warns(RuntimeWarning, match="did not exit"):
            pf.close(timeout=0.3)
    finally:
        release.set()                  # let the daemon thread finish
    pf._thread.join(timeout=5)


def test_prefetcher_surfaces_worker_errors(graph):
    def boom(rng, g, b, fanouts):
        raise RuntimeError("sampler exploded")

    pf = Prefetcher(graph, 8, (2,), sample_fn=boom)
    with pytest.raises(RuntimeError, match="sampler exploded"):
        pf.next()
    pf.close()


def test_host_batch_error_releases_staging_slot(graph):
    cfg = _cfg(graph)
    src = SampledSource(prefetch=False).bind(graph, cfg,
                                             TrainPlan(n_iters=2))
    free0 = src._ring._free.qsize()
    rng = np.random.default_rng(0)
    fb = sample_batch(rng, graph, src.b, src.fanouts)
    fb.nodes[1][:] = graph.n + 99      # out-of-range gather -> IndexError
    with pytest.raises(IndexError):
        src._host_batch(graph, fb)
    assert src._ring._free.qsize() == free0    # slot was NOT leaked
    src.close()


# ---------------------------------------------------------------------------
# The feature table stays on the device: sampled batches ship node ids
# and the step gathers their rows
# ---------------------------------------------------------------------------

def _hop_sizes(src):
    """(rows, slots) of one batch: node ids over all hops, and sampled
    neighbour slots over all fan-out levels."""
    level, rows, slots = src.b, src.b, 0
    for f in src.fanouts:
        level *= f
        rows += level
        slots += level
    return rows, slots


@pytest.mark.parametrize("reuse_buffers", [True, False],
                         ids=["ring", "plain"])
def test_sampled_batch_ships_ids_not_rows(graph, reuse_buffers):
    import jax
    src = SampledSource(prefetch=False, reuse_buffers=reuse_buffers).bind(
        graph, _cfg(graph), TrainPlan(n_iters=1))
    batch, _ = next(src.batches())
    leaves = jax.tree.leaves(batch)
    # the widths differ (16 against b=64 and fan-outs 5, 3), so a staged
    # row table would show as a last axis of 16
    assert all(x.shape[-1] != graph.feats.shape[1] for x in leaves)
    assert [x.dtype for x in batch[0]] == [np.int32] * 3
    rows, slots = _hop_sizes(src)
    # int32 ids, f32 masks, weights and self weights, int32 labels
    assert sum(x.nbytes for x in leaves) == 4 * (rows + 2 * slots + rows
                                                 + src.b)
    src.close()


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "inline"])
def test_device_gather_rows_counts_every_staged_batch(graph, prefetch):
    from repro.core import tracing
    src = SampledSource(prefetch=prefetch)
    Trainer(graph, _cfg(graph), TrainPlan(lr=0.3, n_iters=4, seed=0),
            source=src).run()
    rows, _ = _hop_sizes(src)
    assert tracing.snapshot()["counters"]["device_gather_rows"] == 4 * rows


@pytest.mark.parametrize("reuse_buffers", [True, False],
                         ids=["ring", "plain"])
@pytest.mark.parametrize("bad", [-1, 240], ids=["negative", "past_n"])
def test_out_of_range_ids_raise_before_staging(graph, reuse_buffers, bad):
    """The step's gather clips, so staging has to refuse the ids that a
    host gather of the rows refused (or, negative, wrapped)."""
    src = SampledSource(prefetch=False, reuse_buffers=reuse_buffers).bind(
        graph, _cfg(graph), TrainPlan(n_iters=1))
    fb = sample_batch(np.random.default_rng(0), graph, src.b, src.fanouts)
    fb.nodes[2][3, 1, 0] = bad
    with pytest.raises(IndexError, match="outside"):
        src._host_batch(graph, fb)
    src.close()


def _host_gather_losses(graph, cfg, plan, src, n):
    """The first ``n`` losses from rows gathered on the host: the same
    draws as ``src`` (its own ``_sample`` from the plan's seed), each
    hop's rows by ``gather_features``, the same forward and the plan's
    optimizer update, with no device-side gather."""
    import jax
    from repro.core import gnn as G
    from repro.core.sampler import gather_features

    opt = plan.make_optimizer()

    @jax.jit
    def step(params, opt_state, feats, masks, weights, self_w, labels,
             extra):
        def loss(p):
            logits = G.minibatch_forward(p, cfg, feats, masks, weights,
                                         self_w)
            return gnn_loss(logits, labels, cfg.loss, cfg.n_classes,
                            valid=extra[0] if extra else None,
                            weight=extra[1] if len(extra) > 1 else None)

        val, grads = jax.value_and_grad(loss)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, val

    params = G.init_gnn(jax.random.key(plan.seed), cfg, graph.feats.shape[1])
    opt_state = opt.init(params)
    rng = np.random.default_rng(plan.seed)
    out = []
    for _ in range(n):
        fb = src._sample(rng, graph, src.b_request, src.fanouts)
        valid_n = fb.batch_size
        fb = src._pad_batch(fb)
        params, opt_state, val = step(
            params, opt_state, gather_features(graph, fb),
            [m.astype(np.float32) for m in fb.masks], fb.weights,
            fb.self_w, fb.labels, tuple(src._extra_cols(fb, valid_n)))
        out.append(float(val))
    return out


@pytest.mark.parametrize("kind", ["sampled", "padded", "importance"])
def test_device_gather_losses_equal_host_gather(graph, kind):
    """Gathering the rows in the step changes no value: the first three
    losses equal, bit for bit, a reference that gathers them on the
    host from the same draws."""
    b = {"padded": len(graph.train_nodes) + 16}.get(kind, 64)
    cfg = _cfg(graph, batch_size=b)
    plan = TrainPlan(lr=0.3, n_iters=3, seed=7)
    src = (ImportanceSampledSource(batch_size=b) if kind == "importance"
           else SampledSource(batch_size=b))
    res = Trainer(graph, cfg, plan, source=src).run()
    assert (src.pad > 0) == (kind == "padded")
    assert res.history.losses == _host_gather_losses(graph, cfg, plan,
                                                      src, 3)


# ---------------------------------------------------------------------------
# Satellite: bench gate tolerates variants the baseline predates
# ---------------------------------------------------------------------------

def test_bench_gate_skips_variants_missing_from_baseline(tmp_path):
    sys.path.insert(0, REPO)
    try:
        from benchmarks import bench_engine
    finally:
        sys.path.pop(0)
    base = {"smoke": True, "rows": [
        {"variant": "minibatch+fast", "kernel": 0,
         "steady_steps_per_s": 100.0}]}
    path = tmp_path / "BENCH_engine.json"
    path.write_text(json.dumps(base))
    rows = [
        {"variant": "minibatch+fast", "kernel": 0,
         "steady_steps_per_s": 99.0, "time_to_first_step_s": 0.1},
        # sources this PR introduced: absent from the baseline -> the
        # gate reports them but must NOT fail
        {"variant": "cluster+fast", "kernel": 0,
         "steady_steps_per_s": 1.0, "time_to_first_step_s": 0.1},
        {"variant": "importance+fast", "kernel": 0,
         "steady_steps_per_s": 1.0, "time_to_first_step_s": 0.1},
    ]
    failures = bench_engine.check_regression(rows, str(path), smoke=True)
    assert failures == []
