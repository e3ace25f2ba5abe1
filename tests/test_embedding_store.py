"""EmbeddingStore (PR 7): cached per-layer tables + dirty-frontier
incremental re-embedding, validated against full recompute.

Contract (ISSUE 7): after random feature updates and random edge
additions, ``refresh()`` re-embeds ONLY the forward-influence frontier
and the resulting tables equal a from-scratch store on the updated
graph (allclose — edge rebuilds may reorder CSR neighbor lists, which
permutes float summation order).  Boundaries: an empty update is a
0-row no-op; marking the whole graph dirty re-embeds every row and
still matches."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core.embedding_store import EmbeddingStore


def _cfg(g, **kw):
    base = dict(name="es", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=8,
                n_classes=g.n_classes, n_layers=2, fanout=(4, 3),
                batch_size=32, loss="ce", use_agg_kernel=False,
                agg_b_tile=4, agg_d_tile=8,
                agg_k_slab=2)
    base.update(kw)
    return GNNConfig(**base)


def _store(g, cfg, params, **kw):
    s = EmbeddingStore(params, cfg, g, chunk_size=48, **kw)
    s.build()
    return s


def _copy_graph(g):
    return dataclasses.replace(g, feats=g.feats.copy(),
                               indptr=g.indptr.copy(),
                               indices=g.indices.copy())


def _assert_matches_fresh(store, params, cfg, **tol):
    tol = tol or dict(rtol=1e-4, atol=1e-5)
    fresh = _store(store.graph, cfg, params)
    for li, (a, b) in enumerate(zip(store.layers, fresh.layers)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"layer {li}", **tol)


@pytest.mark.parametrize("model,kernel", [("graphsage", False),
                                          ("gcn", False), ("gcn", True)])
def test_feature_update_incremental_equals_full(small_graph, model,
                                                kernel):
    g = _copy_graph(small_graph)
    cfg = _cfg(g, model=model, use_agg_kernel=kernel)
    params = G.init_gnn(jax.random.key(0), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    rng = np.random.default_rng(1)
    nodes = rng.choice(g.n, size=6, replace=False)
    store.update_features(
        nodes, rng.normal(size=(6, g.feats.shape[1])).astype(np.float32))
    assert store.dirty
    info = store.refresh()
    assert not store.dirty
    # genuinely incremental: strictly fewer rows than a full rebuild,
    # and the frontier grows monotonically layer to layer
    assert info["rows_per_layer"][0] >= len(nodes)
    assert all(a <= b for a, b in zip(info["rows_per_layer"],
                                      info["rows_per_layer"][1:]))
    assert info["total_rows"] < g.n * cfg.n_layers
    _assert_matches_fresh(store, params, cfg)


def test_edge_update_incremental_equals_full(small_graph):
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(1), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    rng = np.random.default_rng(2)
    src = rng.choice(g.n, size=5, replace=False)
    dst = rng.choice(g.n, size=5, replace=False)
    old_nnz = len(store.graph.indices)
    store.add_edges(src, dst)
    assert len(store.graph.indices) >= old_nnz   # self-loops dropped
    info = store.refresh()
    assert 0 < info["total_rows"] < g.n * cfg.n_layers
    _assert_matches_fresh(store, params, cfg)


def test_edge_update_affects_neighbor_weights(small_graph):
    """ã depends on BOTH endpoint degrees: adding one edge (u, v) must
    re-derive the ELL rows of u, v AND their existing neighbors."""
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(2), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    u = int(np.argmax(g.degrees))                # has neighbors for sure
    v = int((u + g.n // 2) % g.n)
    if v in set(g.neighbors(u)) or v == u:
        v = (v + 1) % g.n
    nb = set(store.graph.neighbors(u))
    store.add_edges([u], [v])
    dirty = set(np.nonzero(store._dirty_row)[0])
    assert {u, v} <= dirty and nb <= dirty
    store.refresh()
    _assert_matches_fresh(store, params, cfg)


def test_empty_update_is_noop(small_graph):
    cfg = _cfg(small_graph)
    params = G.init_gnn(jax.random.key(3), cfg,
                        small_graph.feats.shape[1])
    store = _store(small_graph, cfg, params)
    before = [np.asarray(t) for t in store.layers]
    info = store.refresh()
    assert info["total_rows"] == 0
    assert info["rows_per_layer"] == [0] * cfg.n_layers
    for a, b in zip(store.layers, before):
        assert np.array_equal(np.asarray(a), b)
    # add_edges with only self-loops is also a no-op
    store.add_edges([1, 2], [1, 2])
    assert not store.dirty


def test_whole_graph_dirty_equals_rebuild(small_graph):
    g = small_graph
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(4), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    store.mark_dirty(np.arange(g.n))
    info = store.refresh()
    assert info["rows_per_layer"] == [g.n] * cfg.n_layers
    _assert_matches_fresh(store, params, cfg)


def test_frontier_preview_matches_refresh(small_graph):
    cfg = _cfg(small_graph)
    params = G.init_gnn(jax.random.key(5), cfg,
                        small_graph.feats.shape[1])
    store = _store(small_graph, cfg, params)
    store.mark_dirty([0, 7])
    fronts = store.frontier()
    info = store.refresh()
    assert [int(f.sum()) for f in fronts] == info["rows_per_layer"]


def test_query_autorefresh_and_predict(small_graph):
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(6), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    rng = np.random.default_rng(7)
    store.update_features([3], rng.normal(size=(1, g.feats.shape[1]))
                          .astype(np.float32))
    assert store.dirty
    preds = store.predict([0, 3, 11])            # triggers refresh
    assert not store.dirty
    fresh = _store(store.graph, cfg, params)
    want = np.argmax(np.asarray(fresh.layers[-1])[[0, 3, 11]], -1)
    assert np.array_equal(preds, want)
    logits = store.query_logits([5, 3])
    np.testing.assert_allclose(
        logits, np.asarray(store.layers[-1])[[5, 3]], rtol=1e-6)


def test_wal_pending_updates_and_staleness(small_graph):
    """PR 10: writers append to the WAL; ``pending_updates`` /
    ``staleness_s`` track what the serving snapshot does not reflect
    yet, and a successful refresh zeroes both."""
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(10), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    assert store.version == 1
    assert store.pending_updates() == 0
    assert store.staleness_s() == 0.0
    rng = np.random.default_rng(10)
    store.update_features([1], rng.normal(size=(1, g.feats.shape[1]))
                          .astype(np.float32))
    store.mark_dirty([2])
    assert store.pending_updates() == 2
    assert store.staleness_s() > 0.0
    store.refresh()
    assert store.version == 2
    assert store.pending_updates() == 0
    assert store.staleness_s() == 0.0
    _assert_matches_fresh(store, params, cfg)


def test_predict_meta_serves_stale_without_refresh(small_graph):
    """``predict_meta`` answers from the current snapshot and reports
    its version + staleness; only ``predict``/``query_logits`` keep the
    PR-7 auto-refresh behavior."""
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(11), cfg, g.feats.shape[1])
    store = _store(g, cfg, params)
    before = np.argmax(store.snapshot().final_np, -1)
    rng = np.random.default_rng(11)
    store.update_features(np.arange(8),
                          rng.normal(size=(8, g.feats.shape[1]))
                          .astype(np.float32))
    preds, ver, stale = store.predict_meta(np.arange(g.n))
    assert ver == 1 and stale > 0.0
    assert np.array_equal(preds, before)     # old version, NOT refreshed
    assert store.dirty
    store.predict([0])                       # auto-refreshes
    assert not store.dirty
    assert store.predict_meta([0])[1] == 2


def test_capped_max_deg_store(small_graph):
    """A degree-capped store stays consistent with a capped fresh
    rebuild through updates (truncated ELL is the documented layout)."""
    g = _copy_graph(small_graph)
    cfg = _cfg(g)
    params = G.init_gnn(jax.random.key(8), cfg, g.feats.shape[1])
    store = EmbeddingStore(params, cfg, g, chunk_size=48, max_deg=6)
    store.build()
    assert store.K == 6
    rng = np.random.default_rng(9)
    store.update_features([2, 4], rng.normal(size=(2, g.feats.shape[1]))
                          .astype(np.float32))
    store.refresh()
    fresh = EmbeddingStore(params, cfg, store.graph, chunk_size=48,
                           max_deg=6)
    fresh.build()
    for a, b in zip(store.layers, fresh.layers):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
