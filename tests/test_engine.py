"""Unified training engine: legacy-wrapper equivalence against recorded
pre-refactor goldens, callback ordering, early-stop semantics, the
(b, β) sweep runner, staging-ring reuse, and config validation."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core.engine import (Callback, EarlyStop, FullGraphSource,
                               HistoryCallback, SampledSource, Trainer,
                               TrainPlan)
from repro.core.experiment import run_experiment, save_rows, sweep
from repro.core.metrics import (History, iteration_to_accuracy,
                                time_to_accuracy)
from repro.core.prefetch import HostStagingRing
from repro.core.trainer import train_full_graph, train_minibatch

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens",
                       "trainer_seed.json")


def _cfg(g, **kw):
    base = dict(name="t", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=32,
                n_classes=g.n_classes, n_layers=2, fanout=(5, 3),
                batch_size=64, loss="ce")
    base.update(kw)
    return GNNConfig(**base)


# ---------------------------------------------------------------------------
# Legacy-wrapper equivalence: bit-for-bit vs the pre-engine loops
# ---------------------------------------------------------------------------

def _assert_matches(gold, res, name):
    """Exact equality on every field.  The goldens were recorded on the
    CPU backend with the installed jax (their "_rule" entry names the
    version): a jax upgrade that changes the random bits is a reason to
    re-record them, never to loosen this comparison."""
    h = res.history
    assert h.losses == gold["losses"], name
    assert h.val_accs == gold["val_accs"], name
    assert h.full_losses == gold["full_losses"], name
    assert h.full_loss_iters == gold["full_loss_iters"], name
    assert h.nodes_processed == gold["nodes_processed"], name
    assert res.final_test_acc == gold["final_test_acc"], name


@pytest.fixture(scope="module")
def goldens():
    with open(GOLDENS) as f:
        return json.load(f)


def test_fullgraph_wrapper_matches_seed_golden(small_graph, goldens):
    """train_full_graph == the pre-engine loop, bit-for-bit at fixed seed
    (goldens recorded from the PR-1 code before the Trainer refactor)."""
    g = small_graph
    cfg = _cfg(g, name="golden")
    res = train_full_graph(g, cfg, lr=0.3, n_iters=12, eval_every=5,
                           seed=0)
    _assert_matches(goldens["full_graph"], res, "full_graph")


def test_fullgraph_wrapper_target_loss_golden(small_graph, goldens):
    g = small_graph
    cfg = _cfg(g, name="golden")
    res = train_full_graph(g, cfg, lr=0.3, n_iters=50, eval_every=10,
                           seed=0, target_loss=1.2)
    _assert_matches(goldens["full_graph_target"], res, "full_graph_target")
    assert res.stop_reason == "target_loss<=1.2"


@pytest.mark.parametrize("prefetch,key", [(False, "minibatch_sync"),
                                          (True, "minibatch_prefetch")])
def test_minibatch_wrapper_matches_seed_golden(small_graph, goldens,
                                               prefetch, key):
    g = small_graph
    cfg = _cfg(g, name="golden")
    res = train_minibatch(g, cfg, lr=0.3, n_iters=12, eval_every=5,
                          seed=0, track_full_loss_every=4,
                          prefetch=prefetch)
    _assert_matches(goldens[key], res, key)


def test_minibatch_wrapper_explicit_b_fanout_golden(small_graph, goldens):
    g = small_graph
    cfg = _cfg(g, name="golden")
    res = train_minibatch(g, cfg, lr=0.3, n_iters=8, batch_size=32,
                          fanouts=(4, 2), eval_every=3, seed=7,
                          prefetch=True)
    _assert_matches(goldens["minibatch_b32"], res, "minibatch_b32")


def test_fullgraph_max_deg_uses_capped_ell_everywhere():
    """With max_deg set, training AND evaluation run on the capped ELL
    (legacy-loop semantics) — the full-width ELL is never built."""
    from repro.data import make_sbm_graph
    g = make_sbm_graph(n=200, n_classes=4, avg_degree=10, feat_dim=16,
                       seed=3)
    res = train_full_graph(g, _cfg(g), lr=0.3, n_iters=3, max_deg=4)
    assert len(res.history.losses) == 3
    cache = g._ell_cache
    assert 4 in cache and g.d_max not in cache


def test_run_experiment_custom_source_labels_row(small_graph):
    """A custom source overrides `paradigm`; the row must describe the
    source that actually ran, not the default paradigm string."""
    g = small_graph
    row = run_experiment(g, _cfg(g), TrainPlan(lr=0.3, n_iters=2),
                         source=FullGraphSource())
    assert row["paradigm"] == "fullgraph"
    assert row["b"] == len(g.train_nodes)
    row = run_experiment(g, _cfg(g), TrainPlan(lr=0.3, n_iters=2),
                         source=SampledSource(batch_size=16,
                                              fanouts=(2, 2)))
    assert row["paradigm"] == "minibatch"
    assert row["b"] == 16 and row["fanouts"] == "2x2"


def test_staging_ring_off_is_identical(small_graph):
    """Buffer reuse is a pure transport optimization: the loss sequence
    with the staging ring disabled is bit-identical."""
    g = small_graph
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=6, seed=0)
    r_ring = Trainer(g, cfg, plan, source=SampledSource()).run()
    r_flat = Trainer(g, cfg, plan,
                     source=SampledSource(reuse_buffers=False)).run()
    assert r_ring.history.losses == r_flat.history.losses


# ---------------------------------------------------------------------------
# Callbacks
# ---------------------------------------------------------------------------

class Recorder(Callback):
    def __init__(self):
        self.events = []

    def on_train_start(self, state):
        self.events.append(("train_start", state.it))

    def on_step(self, state):
        self.events.append(("step", state.it))

    def on_eval(self, state):
        self.events.append(("eval", state.it, state.val_acc))

    def on_stop(self, state):
        self.events.append(("stop", state.it, state.stop_reason))

    def on_train_end(self, state):
        self.events.append(("train_end", state.it))


def test_callback_ordering(small_graph):
    g = small_graph
    rec = Recorder()
    plan = TrainPlan(lr=0.3, n_iters=5, eval_every=2, seed=0)
    Trainer(g, _cfg(g), plan, source=SampledSource(),
            extra_callbacks=[rec]).run()
    kinds = [e[0] for e in rec.events]
    assert kinds[0] == "train_start" and kinds[-1] == "train_end"
    # every iteration fires on_step; eval iterations (0, 2, 4) fire
    # on_eval immediately after their on_step
    assert kinds[1:-1] == ["step", "eval", "step", "step", "eval",
                           "step", "step", "eval"]
    assert [e[1] for e in rec.events if e[0] == "eval"] == [0, 2, 4]
    assert all(e[2] is not None for e in rec.events if e[0] == "eval")


def test_callbacks_fire_in_list_order(small_graph):
    g = small_graph
    order = []

    class A(Callback):
        def on_step(self, state):
            order.append("a")

    class B(Callback):
        def on_step(self, state):
            order.append("b")

    plan = TrainPlan(lr=0.3, n_iters=2, seed=0)
    Trainer(g, _cfg(g), plan, source=FullGraphSource(),
            extra_callbacks=[A(), B()]).run()
    assert order == ["a", "b", "a", "b"]


def test_early_stop_target_acc(small_graph):
    """target_acc stops on the first eval that crosses it; on_stop fires
    exactly once, on the stopping iteration."""
    g = small_graph
    rec = Recorder()
    plan = TrainPlan(lr=0.3, n_iters=50, eval_every=1, target_acc=0.0,
                     seed=0)
    res = Trainer(g, _cfg(g), plan, source=FullGraphSource(),
                  extra_callbacks=[rec]).run()
    assert len(res.history.losses) == 1        # stopped after iter 0
    assert res.stop_reason == "target_acc>=0.0"
    stops = [e for e in rec.events if e[0] == "stop"]
    assert stops == [("stop", 0, "target_acc>=0.0")]


def test_early_stop_records_crossing_iteration(small_graph):
    """Stop fires AFTER History records the crossing loss (legacy loop
    semantics): the last recorded loss is the one <= target."""
    g = small_graph
    plan = TrainPlan(lr=0.3, n_iters=100, target_loss=1.0, seed=0)
    res = Trainer(g, _cfg(g), plan, source=FullGraphSource()).run()
    assert res.history.losses[-1] <= 1.0
    assert all(l > 1.0 for l in res.history.losses[:-1])


def test_checkpoint_callback(small_graph, tmp_path):
    from repro.checkpoint import (latest_step, load_metadata,
                                  restore_checkpoint)
    g = small_graph
    plan = TrainPlan(lr=0.3, n_iters=7, ckpt_every=3, seed=0,
                     ckpt_dir=str(tmp_path))
    tr = Trainer(g, _cfg(g), plan, source=FullGraphSource())
    res = tr.run()
    # periodic saves at 3, 6 + final save at last iter
    assert latest_step(str(tmp_path)) == 6
    # checkpoints are full TrainerState snapshots: params AND opt_state
    # in the npz, the resume engine_state in the metadata
    like = {"params": res.params, "opt_state": tr.opt.init(res.params)}
    restored = restore_checkpoint(str(tmp_path), like)
    np.testing.assert_array_equal(np.asarray(res.params[0]["w_self"]),
                                  restored["params"][0]["w_self"])
    es = load_metadata(str(tmp_path))["engine_state"]
    assert es["it"] == 6 and es["seed"] == 0
    assert len(es["history"]["losses"]) == 7


# ---------------------------------------------------------------------------
# TrainPlan: optimizer/schedule resolution from repro.optim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_kw", [dict(optimizer="sgd", momentum=0.9),
                                    dict(optimizer="adamw", lr=1e-2),
                                    dict(schedule="cosine", warmup=2)])
def test_plan_optimizers_train(small_graph, opt_kw):
    g = small_graph
    plan = TrainPlan(lr=opt_kw.pop("lr", 0.3), n_iters=15, seed=0,
                     **opt_kw)
    res = Trainer(g, _cfg(g), plan, source=FullGraphSource()).run()
    assert res.history.losses[-1] < res.history.losses[0]


def test_plan_rejects_unknown_optimizer():
    with pytest.raises(ValueError, match="unknown optimizer"):
        TrainPlan(optimizer="lion").make_optimizer()
    with pytest.raises(ValueError, match="unknown schedule"):
        TrainPlan(schedule="linear").make_schedule()


# ---------------------------------------------------------------------------
# Sweep runner
# ---------------------------------------------------------------------------

def test_sweep_2x2_smoke(small_graph, tmp_path):
    g = small_graph
    cfg = _cfg(g, n_layers=1, fanout=(5,))
    plan = TrainPlan(lr=0.3, n_iters=3, eval_every=2)
    rows = sweep(g, cfg, plan, batch_sizes=[16, 32],
                 fanout_grid=[(2,), 4], include_fullgraph=True)
    assert len(rows) == 1 + 2 * 2
    assert rows[0]["paradigm"] == "fullgraph"
    assert rows[0]["b"] == len(g.train_nodes)
    assert {(r["b"], r["fanouts"]) for r in rows[1:]} == {
        (16, "2"), (16, "4"), (32, "2"), (32, "4")}
    assert all(r["iters"] == 3 for r in rows)
    paths = save_rows("engine_sweep_smoke", rows, out_dir=str(tmp_path))
    assert os.path.exists(paths["json"]) and os.path.exists(paths["csv"])
    loaded = json.load(open(paths["json"]))
    assert len(loaded) == len(rows) and loaded[0]["paradigm"] == "fullgraph"


def test_sweep_namespaces_checkpoints_per_grid_point(small_graph,
                                                     tmp_path):
    """Grid points must not overwrite each other's ckpt_{step}.npz."""
    g = small_graph
    cfg = _cfg(g, n_layers=1, fanout=(5,))
    plan = TrainPlan(lr=0.3, n_iters=3, ckpt_every=2,
                     ckpt_dir=str(tmp_path))
    sweep(g, cfg, plan, batch_sizes=[16, 32], fanout_grid=[(2,)])
    subdirs = sorted(p.name for p in tmp_path.iterdir() if p.is_dir())
    assert subdirs == ["b16_f2_s0", "b32_f2_s0"]
    for d in subdirs:
        assert any(f.name.startswith("ckpt_")
                   for f in (tmp_path / d).iterdir())


def test_sweep_rejects_bad_grid(small_graph):
    g = small_graph
    cfg = _cfg(g, n_layers=1, fanout=(5,))
    plan = TrainPlan(n_iters=2)
    with pytest.raises(ValueError, match="fan-outs must be positive"):
        sweep(g, cfg, plan, batch_sizes=[16], fanout_grid=[(0,)])
    with pytest.raises(ValueError, match="batch_size"):
        sweep(g, cfg, plan, batch_sizes=[-4], fanout_grid=[(2,)])


def test_run_experiment_validates_override_kwargs(small_graph):
    """b/fanouts overrides must hit the fail-fast validation, not crash
    deep inside the sampler."""
    g = small_graph
    plan = TrainPlan(n_iters=1)
    with pytest.raises(ValueError, match="batch_size"):
        run_experiment(g, _cfg(g), plan, b=-5)
    with pytest.raises(ValueError, match="fan-outs must be positive"):
        run_experiment(g, _cfg(g), plan, fanouts=(0, 3))
    with pytest.raises(ValueError, match="one β per layer"):
        run_experiment(g, _cfg(g), plan, fanouts=(3,))


def test_run_experiment_fullgraph_row(small_graph):
    g = small_graph
    row = run_experiment(g, _cfg(g), TrainPlan(lr=0.3, n_iters=3),
                         paradigm="fullgraph", report_loss=0.1)
    assert row["paradigm"] == "fullgraph"
    assert row["iters"] == 3 and "iter_to_loss" in row
    with pytest.raises(ValueError, match="paradigm"):
        run_experiment(g, _cfg(g), TrainPlan(n_iters=1), paradigm="nope")


# ---------------------------------------------------------------------------
# Config validation (fail fast before the Pallas kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [dict(agg_b_tile=0), dict(agg_d_tile=-1),
                                 dict(agg_k_slab=0), dict(batch_size=0),
                                 dict(fanout=(5, 0)), dict(fanout=(5,)),
                                 dict(max_degree=0), dict(hidden=0)])
def test_gnnconfig_validate_rejects(small_graph, bad):
    cfg = _cfg(small_graph, **bad)
    with pytest.raises(ValueError):
        cfg.validate()


def test_gnnconfig_validate_accepts_good(small_graph):
    _cfg(small_graph).validate()


# ---------------------------------------------------------------------------
# Metrics: eval-iteration bookkeeping (the satellite fix)
# ---------------------------------------------------------------------------

def test_iteration_to_accuracy_uses_eval_iters():
    """val_accs recorded every 5 iters: crossing on the 3rd eval means
    iteration 11, not list index 3."""
    h = History()
    h.start()
    for it in range(20):
        val = [0.1, 0.3, 0.9, 0.95][it // 5] if it % 5 == 0 else None
        h.record(2.0 - it * 0.1, val, nodes=1)
    assert h.val_acc_iters == [1, 6, 11, 16]
    assert iteration_to_accuracy(h, 0.85) == 11
    t = time_to_accuracy(h, 0.85)
    assert t == h.times[10]
    assert iteration_to_accuracy(h, 0.99) is None
    assert time_to_accuracy(h, 0.99) is None


def test_engine_history_records_eval_iters(small_graph):
    g = small_graph
    plan = TrainPlan(lr=0.3, n_iters=7, eval_every=3, seed=0)
    res = Trainer(g, _cfg(g), plan, source=SampledSource()).run()
    assert res.history.val_acc_iters == [1, 4, 7]


# ---------------------------------------------------------------------------
# HostStagingRing
# ---------------------------------------------------------------------------

def test_staging_ring_reuses_buffers():
    specs = [((2, 3), np.float32), ((2,), np.int32)]
    ring = HostStagingRing(2)
    s0 = ring.acquire()
    bufs0 = ring.buffers(s0, specs)
    assert [(b.shape, b.dtype) for b in bufs0] == [
        ((2, 3), np.dtype(np.float32)), ((2,), np.dtype(np.int32))]
    bufs0[0][:] = 7.0
    ring.release(s0)
    s1 = ring.acquire()
    s2 = ring.acquire()                      # both slots handed out
    assert {s1, s2} == {0, 1}
    # the recycled slot returns the SAME buffer objects (no realloc)
    assert ring.buffers(s0, specs)[0] is bufs0[0]
    # changed specs reallocate that slot's buffers
    bigger = [((4, 3), np.float32), ((2,), np.int32)]
    assert ring.buffers(s0, bigger)[0].shape == (4, 3)


def test_staging_ring_close_unblocks_acquire():
    ring = HostStagingRing(1)
    ring.acquire()                           # exhaust the ring
    ring.close()
    with pytest.raises(RuntimeError, match="closed"):
        ring.acquire()                       # would otherwise block
