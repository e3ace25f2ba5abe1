"""The program's span and counter log (``repro.core.tracing``): spans from
several threads land whole, the log is bounded and cleared per Trainer,
the stop iteration is the one asked for, span names stay clear of the
benchmark's own, the ELL padding counter matches a hand count, and a
sampled run records its spans for every batch it consumes."""
import dataclasses
import os
import sys
import threading

import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import tracing
from repro.core.engine import (Callback, ClusterSource, FullGraphSource,
                               SampledSource, ShardedFullGraphSource,
                               Trainer, TrainPlan)
from repro.core.graph import Graph
from repro.data import make_sbm_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLED_SPANS = {"sample", "stage", "ring_wait", "queue_wait", "device_put"}


def _cfg(g, **kw):
    base = dict(name="trace", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=16,
                n_classes=g.n_classes, n_layers=2, fanout=(4, 3),
                batch_size=32, loss="ce")
    base.update(kw)
    return GNNConfig(**base)


@pytest.fixture(scope="module")
def graph():
    return make_sbm_graph(n=200, n_classes=4, avg_degree=8, feat_dim=16,
                          seed=7)


def _by_batch(snap):
    out = {}
    for s in snap["spans"]:
        out.setdefault(s.batch, []).append(s)
    return out


# ---------------------------------------------------------------------------
# The log
# ---------------------------------------------------------------------------

def test_spans_from_two_threads_land_whole():
    log = tracing.Log()
    n = 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)        # interleave the threads finely

    def work(first):
        for b in range(first, first + n):
            with log.span("stage", b):
                with log.span("ring_wait"):
                    pass
            log.count("done", 1)

    try:
        ts = [threading.Thread(target=work, args=(k * n,), name=f"w{k}")
              for k in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    snap = log.snapshot()
    assert snap["counters"] == {"done": 2 * n}
    by = _by_batch(snap)
    assert sorted(by) == list(range(2 * n))
    for b, spans in by.items():
        stage, = [s for s in spans if s.name == "stage"]
        child, = [s for s in spans if s.name == "ring_wait"]
        assert stage.thread == child.thread == f"w{b // n}"
        assert (stage.parent, child.parent) == (None, "stage")
        assert stage.start_ns <= child.start_ns <= child.end_ns \
            <= stage.end_ns


def test_span_that_raises_is_kept_and_orphan_child_is_not():
    log = tracing.Log()
    with pytest.raises(ValueError):
        with log.span("sample", 3):
            raise ValueError("transient")
    with log.span("ring_wait"):        # no batch, no parent
        pass
    assert [(s.name, s.batch) for s in log.snapshot()["spans"]] \
        == [("sample", 3)]


def test_log_keeps_the_last_batches(monkeypatch):
    assert tracing.MAX_BATCHES == 1024
    monkeypatch.setattr(tracing, "MAX_BATCHES", 4)
    log = tracing.Log()
    for b in range(10):
        with log.span("sample", b):
            pass
    assert [s.batch for s in log.snapshot()["spans"]] == [6, 7, 8, 9]


def test_trainer_init_clears_the_log(graph):
    with tracing.span("sample", 12345):
        pass
    tracing.count("ell_slots", 7)
    tracing.note_stop(99)
    Trainer(graph, _cfg(graph), TrainPlan(lr=0.1, n_iters=1),
            source=SampledSource(prefetch=False))
    snap = tracing.snapshot()
    assert snap == {"spans": [], "counters": {}, "stop_batch": None}


class _StopAt(Callback):
    def __init__(self, it):
        self.it = it

    def on_step(self, state):
        if state.it >= self.it:
            state.request_stop("test")


@pytest.mark.parametrize("deferred", [True, False])
def test_stop_notes_the_last_batch_drawn(graph, deferred):
    """A stop asked for at iteration 5 comes while batch 5 is the last
    drawn, or batch 6 under deferred sync (step 6 is dispatched before
    record 5 is read)."""
    plan = TrainPlan(lr=0.1, n_iters=20, seed=0, eval_every=100,
                     deferred_sync=deferred)
    drawn = []
    src = SampledSource()
    batches = src.batches

    def counted():
        for item in batches():
            drawn.append(src._consumed - 1)
            yield item
    src.batches = counted
    res = Trainer(graph, _cfg(graph), plan, extra_callbacks=[_StopAt(5)],
                  source=src).run()
    assert res.stop_reason == "test"
    assert tracing.snapshot()["stop_batch"] == drawn[-1] \
        == (6 if deferred else 5)


def test_stop_in_the_drained_record_is_noted(graph):
    plan = TrainPlan(lr=0.1, n_iters=4, seed=0, eval_every=100)
    Trainer(graph, _cfg(graph), plan, extra_callbacks=[_StopAt(3)],
            source=SampledSource()).run()
    assert tracing.snapshot()["stop_batch"] == 3


# ---------------------------------------------------------------------------
# Spans of the sampled pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "inline"])
def test_sampled_run_records_every_batch(graph, prefetch):
    n = 6
    Trainer(graph, _cfg(graph), TrainPlan(lr=0.1, n_iters=n, seed=0),
            source=SampledSource(prefetch=prefetch)).run()
    by = _by_batch(tracing.snapshot())
    assert sorted(by) == list(range(n))
    want = {"sample", "stage", "ring_wait", "device_put"}
    if prefetch:
        want.add("queue_wait")
    for b, spans in by.items():
        names = [s.name for s in spans]
        assert set(names) == want and len(names) == len(want), (b, names)
        th = {s.name: s.thread for s in spans}
        worker = "prefetch" if prefetch else "MainThread"
        assert th["sample"] == th["stage"] == th["ring_wait"] == worker
        assert th["device_put"] == "MainThread"
        assert {s.name: s.parent for s in spans}["ring_wait"] == "stage"


def test_spans_sit_on_the_profiler_trace(graph, tmp_path):
    import jax
    from jax.profiler import ProfileData
    n = 3
    with jax.profiler.trace(str(tmp_path)):
        Trainer(graph, _cfg(graph), TrainPlan(lr=0.1, n_iters=n, seed=0),
                source=SampledSource()).run()
    path, = tmp_path.glob("**/*.xplane.pb")
    lines = {}                 # (name, batch) -> host lines it sits on
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in SAMPLED_SPANS:
                    key = (e.name, dict(e.stats)["batch"])
                    lines.setdefault(key, set()).add(li)
    assert set(lines) == {(name, b) for name in SAMPLED_SPANS
                          for b in range(n)}
    for b in range(n):
        # the worker's spans on one line, the loop's on another
        assert lines[("sample", b)] == lines[("ring_wait", b)]
        assert lines[("sample", b)] != lines[("device_put", b)]
        assert lines[("queue_wait", b)] == lines[("device_put", b)]


def test_span_names_stay_clear_of_the_benchmarks(graph):
    sys.path.insert(0, ROOT)
    try:
        from bench.trace import HOST_SPANS
    finally:
        sys.path.remove(ROOT)
    names = set()
    for src in (SampledSource(), SampledSource(prefetch=False),
                ClusterSource()):
        Trainer(graph, _cfg(graph), TrainPlan(lr=0.1, n_iters=2, seed=0),
                source=src).run()
        names |= {s.name for s in tracing.snapshot()["spans"]}
    assert names == SAMPLED_SPANS
    assert not names & set(HOST_SPANS)


def test_cluster_run_records_every_batch(graph):
    n = 4
    Trainer(graph, _cfg(graph), TrainPlan(lr=0.1, n_iters=n, seed=0),
            source=ClusterSource()).run()
    by = _by_batch(tracing.snapshot())
    assert sorted(by) == list(range(n))
    for spans in by.values():
        assert {s.name for s in spans} == {"sample", "stage", "queue_wait",
                                           "device_put"}


def test_resumed_stream_keeps_its_batch_ids(graph, tmp_path):
    d = str(tmp_path / "ck")
    plan = TrainPlan(lr=0.1, n_iters=4, seed=0, eval_every=100,
                     ckpt_every=3, ckpt_dir=d)
    Trainer(graph, _cfg(graph), plan, source=SampledSource()).run()
    Trainer(graph, _cfg(graph), dataclasses.replace(plan, n_iters=7),
            source=SampledSource()).run(resume_from=d)
    by = _by_batch(tracing.snapshot())
    assert sorted(by) == [4, 5, 6]
    assert all(len({s.name for s in v}) == 5 for v in by.values())


# ---------------------------------------------------------------------------
# The ELL padding counter
# ---------------------------------------------------------------------------

def _tiny_csr():
    """A symmetric random adjacency of 13 nodes, as CSR."""
    rng = np.random.default_rng(3)
    n = 13
    adj = np.zeros((n, n), bool)
    for u in range(n):
        for v in rng.choice(n, size=u % 4, replace=False):
            if v != u:
                adj[u, v] = adj[v, u] = True
    indptr = np.concatenate([[0], np.cumsum(adj.sum(1))]).astype(np.int64)
    indices = np.nonzero(adj)[1].astype(np.int32)
    labels = (np.arange(n) % 3).astype(np.int32)
    mask = np.ones(n, bool)
    return Graph(n=n, indptr=indptr, indices=indices,
                 feats=rng.normal(size=(n, 8)).astype(np.float32),
                 labels=labels, train_mask=mask, val_mask=mask,
                 test_mask=mask)


def _hand_share(g, k, rows_p, k_p):
    kept = sum(min(int(d), k) for d in np.diff(g.indptr))
    return 1.0 - kept / (rows_p * k_p)


@pytest.mark.parametrize("src_cls", [FullGraphSource,
                                     ShardedFullGraphSource],
                         ids=["plain", "sharded"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "einsum"])
def test_pad_counter_matches_hand_count(src_cls, kernel):
    g = _tiny_csr()
    k = 3
    assert g.d_max > k                  # the cap truncates some rows
    cfg = _cfg(g, max_degree=k, use_agg_kernel=kernel, agg_b_tile=8,
               agg_k_slab=4)
    # the kernel pads 13 rows to 16 (b_tile 8) and K = 3 to 4 (k_slab 4)
    want = (_hand_share(g, k, 16, 4) if kernel
            else _hand_share(g, k, 13, 3))
    plan = TrainPlan(lr=0.1, n_iters=1)
    for _ in ("fresh", "cached"):
        Trainer(g, cfg, plan, source=src_cls())
        c = tracing.snapshot()["counters"]
        assert 1.0 - c["ell_edges"] / c["ell_slots"] == pytest.approx(
            want, rel=1e-12)
    cache = g._sharded_ell_cache if src_cls is ShardedFullGraphSource \
        else g._ell_cache
    assert len([key for key in cache if key != "base"]) == 1


@pytest.mark.parametrize("src_cls", [FullGraphSource,
                                     ShardedFullGraphSource],
                         ids=["plain", "sharded"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "einsum"])
def test_gat_counters_match_hand_count(src_cls, kernel):
    """GAT's ELL slots pad as GCN's do, and its attention columns: 3
    heads of 6 on the hidden layer, of 3 classes on the last; with f32
    tiles of 8 lanes the kernel reads 8 columns a head on the hidden
    layer (a whole tile) and 4 on the last (half a tile, paired)."""
    g = _tiny_csr()
    cfg = _cfg(g, model="gat", hidden=18, gat_heads=3, max_degree=3,
               use_agg_kernel=kernel, agg_b_tile=8, agg_d_tile=8,
               agg_k_slab=4)
    Trainer(g, cfg, TrainPlan(lr=0.1, n_iters=1), source=src_cls())
    c = tracing.snapshot()["counters"]
    assert c["attn_cols"] == 3 * 6 + 3 * 3
    assert c["attn_kernel_cols"] == (3 * 8 + 3 * 4 if kernel else 27)
    want = (_hand_share(g, 3, 16, 4) if kernel
            else _hand_share(g, 3, 13, 3))
    assert 1.0 - c["ell_edges"] / c["ell_slots"] == pytest.approx(
        want, rel=1e-12)


def test_other_models_record_no_attention_counters(graph):
    Trainer(graph, _cfg(graph, model="gcn", max_degree=4),
            TrainPlan(lr=0.1, n_iters=1), source=FullGraphSource())
    c = tracing.snapshot()["counters"]
    assert "ell_slots" in c and "attn_cols" not in c
