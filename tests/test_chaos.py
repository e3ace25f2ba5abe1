"""Chaos suite: deterministic fault injection against every recovery
path — supervised prefetch restarts, the engine's non-finite
BadStepPolicy (skip / raise / rollback, sync AND deferred), kill-mid-
checkpoint + exact resume, and crash-safe sweep journaling."""
import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import faults
from repro.core.engine import (BadStepPolicy, Callback, FullGraphSource,
                               NonFiniteStepError, SampledSource, Trainer,
                               TrainPlan)
from repro.core.experiment import sweep
from repro.core.prefetch import Prefetcher


def _cfg(g, **kw):
    base = dict(name="chaos", model="graphsage", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=16, n_classes=g.n_classes,
                n_layers=2, fanout=(4, 3), batch_size=32, loss="ce")
    base.update(kw)
    return GNNConfig(**base)


@pytest.fixture(autouse=True)
def _no_armed_failpoints():
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# Supervised Prefetcher
# ---------------------------------------------------------------------------

def _targets(graph, n=6, seed=0, **kw):
    """The target-node sequence a Prefetcher run delivers."""
    out = []
    pf = Prefetcher(graph, 16, (3,), seed=seed, n_batches=n, **kw)
    try:
        for fb, _ in pf:
            out.append(np.asarray(fb.nodes[0]))   # hop 0 = target nodes
    finally:
        pf.close()
    return out, pf


def test_transient_worker_fault_restart_preserves_sequence(small_graph):
    clean, _ = _targets(small_graph, n=6)
    from repro.core.sampler import sample_batch
    flaky_sample = faults.flaky(sample_batch, fail_at={2})
    with pytest.warns(RuntimeWarning, match="transient"):
        faulty, pf = _targets(small_graph, n=6, sample_fn=flaky_sample,
                              backoff=0.001)
    assert pf.restarts == 1
    assert len(faulty) == len(clean) == 6
    for a, b in zip(clean, faulty):     # batch 2 replayed, not skipped
        np.testing.assert_array_equal(a, b)


def test_restart_budget_exhaustion_escalates_to_fatal(small_graph):
    from repro.core.sampler import sample_batch
    flaky_sample = faults.flaky(sample_batch, fail_at=range(10))
    pf = None
    try:
        # the worker starts (and may warn) inside the constructor, so the
        # constructor runs under the warning capture too
        with pytest.warns(RuntimeWarning, match="transient"):
            pf = Prefetcher(small_graph, 16, (3,), n_batches=4,
                            sample_fn=flaky_sample, max_restarts=2,
                            backoff=0.001)
            with pytest.raises(faults.TransientSamplerFault):
                for _ in range(4):
                    pf.next()
    finally:
        if pf is not None:
            pf.close()


def test_fatal_worker_fault_surfaces_immediately(small_graph):
    from repro.core.sampler import sample_batch
    flaky_sample = faults.flaky(sample_batch, fail_at={1},
                                exc=faults.FatalSamplerFault)
    pf = Prefetcher(small_graph, 16, (3,), n_batches=4,
                    sample_fn=flaky_sample)
    try:
        pf.next()                        # batch 0 fine
        with pytest.raises(faults.FatalSamplerFault):
            for _ in range(3):
                pf.next()
        assert pf.restarts == 0          # fatal != transient
    finally:
        pf.close()


def test_next_after_sentinel_raises_immediately(small_graph):
    """Post-exhaustion next() must re-raise instantly, not deadlock on
    the drained queue (the pre-fault-tolerance bug)."""
    pf = Prefetcher(small_graph, 16, (3,), n_batches=2)
    try:
        pf.next(), pf.next()
        with pytest.raises(StopIteration):
            pf.next()
        outcome = {}

        def call_again():
            try:
                pf.next()
            except BaseException as e:
                outcome["exc"] = e

        t = threading.Thread(target=call_again, daemon=True)
        t0 = time.perf_counter()
        t.start()
        t.join(timeout=2.0)
        assert not t.is_alive(), "next() after sentinel deadlocked"
        assert isinstance(outcome["exc"], StopIteration)
        assert time.perf_counter() - t0 < 2.0
    finally:
        pf.close()


def test_fatal_error_rereaised_after_sentinel(small_graph):
    from repro.core.sampler import sample_batch
    flaky_sample = faults.flaky(sample_batch, fail_at={0},
                                exc=faults.FatalSamplerFault)
    pf = Prefetcher(small_graph, 16, (3,), n_batches=2,
                    sample_fn=flaky_sample)
    try:
        for _ in range(3):               # every call: same stored error
            with pytest.raises(faults.FatalSamplerFault):
                pf.next()
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# Non-finite step guard + BadStepPolicy
# ---------------------------------------------------------------------------

class _ParamTrace(Callback):
    """Copies params every step (donation-safe) keyed by iteration."""

    def __init__(self):
        self.at = {}

    def on_step(self, state):
        self.at[state.it] = jax.tree.map(jnp.copy, state.params)


def _params_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("deferred", [False, True],
                         ids=["sync", "deferred"])
def test_nan_step_skip_policy(small_graph, deferred):
    """NaN batch at step k: loss recorded as nan, bad step logged,
    params UNCHANGED across the bad step, training continues — under
    both sync and one-step-lagged deferred readback."""
    g = small_graph
    k = 3
    plan = TrainPlan(lr=0.3, n_iters=8, seed=0, eval_every=100,
                     deferred_sync=deferred,
                     bad_steps=BadStepPolicy(on_bad="skip",
                                             max_consecutive=4))
    src = faults.poison_batches(SampledSource(), at_iters=[k])
    trace = _ParamTrace()
    res = Trainer(g, _cfg(g), plan, source=src,
                  extra_callbacks=[trace]).run()
    assert len(res.history.losses) == 8
    assert np.isnan(res.history.losses[k])
    assert all(np.isfinite(l) for i, l in enumerate(res.history.losses)
               if i != k)
    assert res.history.bad_steps == [k + 1]          # 1-based
    # the guard made step k an identity update.  The trace records
    # state.params at record-consumption time, which under deferred
    # readback is already one step ahead of the record — shift by one.
    off = 1 if deferred else 0
    assert _params_equal(trace.at[k - off], trace.at[k - 1 - off])
    # and step k+1 moved again (resampled batch, finite grads)
    assert not _params_equal(trace.at[k + 1 - off], trace.at[k - off])


def test_nan_step_raise_policy_default(small_graph):
    g = small_graph
    plan = TrainPlan(lr=0.3, n_iters=6, seed=0, eval_every=100,
                     deferred_sync=False)       # default on_bad="raise"
    src = faults.poison_batches(SampledSource(), at_iters=[2])
    with pytest.raises(NonFiniteStepError, match="iteration 2"):
        Trainer(g, _cfg(g), plan, source=src).run()


def test_nan_streak_escalates_after_max_consecutive(small_graph):
    g = small_graph
    plan = TrainPlan(lr=0.3, n_iters=10, seed=0, eval_every=100,
                     deferred_sync=False,
                     bad_steps=BadStepPolicy(on_bad="skip",
                                             max_consecutive=2))
    src = faults.poison_batches(SampledSource(), at_iters=[3, 4, 5])
    with pytest.raises(NonFiniteStepError) as ei:
        Trainer(g, _cfg(g), plan, source=src).run()
    assert ei.value.consecutive == 2


def test_nan_streak_rollback_policy(small_graph, tmp_path):
    """k consecutive NaN steps with checkpointing on: the engine
    restores the last checkpoint and finishes with finite params."""
    g = small_graph
    # deterministic 2-step NaN streak somewhere in iters 4..9 (after the
    # first it=3 checkpoint exists) — same fault seed, same streak
    bad = {4 + i for i in faults.FaultSchedule(7).consecutive(n=6, k=2)}
    plan = TrainPlan(lr=0.3, n_iters=12, seed=0, eval_every=100,
                     ckpt_every=3, ckpt_dir=str(tmp_path),
                     bad_steps=BadStepPolicy(on_bad="rollback",
                                             max_consecutive=2))
    src = faults.poison_batches(SampledSource(), at_iters=sorted(bad))
    with pytest.warns(RuntimeWarning, match="rolling back"):
        res = Trainer(g, _cfg(g), plan, source=src).run()
    assert len(res.history.bad_steps) == 2
    assert len(res.history.losses) == 12
    assert all(np.isfinite(x) for x in
               jax.tree.leaves(jax.tree.map(jnp.sum, res.params)))


def test_rollback_policy_requires_checkpoints(small_graph):
    with pytest.raises(ValueError, match="ckpt_every"):
        Trainer(small_graph, _cfg(small_graph),
                TrainPlan(n_iters=4,
                          bad_steps=BadStepPolicy(on_bad="rollback")),
                source=FullGraphSource())


def test_bad_step_policy_validation():
    with pytest.raises(ValueError):
        BadStepPolicy(on_bad="explode")
    with pytest.raises(ValueError):
        BadStepPolicy(escalate="shrug")


# ---------------------------------------------------------------------------
# Kill mid-checkpoint during training -> exact resume
# ---------------------------------------------------------------------------

def test_kill_mid_checkpoint_then_resume_equals_uninterrupted(
        small_graph, tmp_path):
    g, cfg = small_graph, _cfg(small_graph)
    golden_dir = str(tmp_path / "golden")
    plan = TrainPlan(lr=0.3, n_iters=9, seed=0, eval_every=4,
                     ckpt_every=3, ckpt_dir=golden_dir)
    golden = Trainer(g, cfg, plan, source=SampledSource()).run()

    # run 2: SIGKILL stand-in mid-save of the it=6 checkpoint
    crash_dir = str(tmp_path / "crash")
    plan2 = dataclasses.replace(plan, ckpt_dir=crash_dir)
    with faults.armed("ckpt.before_npz_rename", at_hits=(1,)):
        with pytest.raises(faults.SimulatedCrash):
            Trainer(g, cfg, plan2, source=SampledSource()).run()
    from repro.checkpoint import latest_step
    assert latest_step(crash_dir) == 3      # it=6 save never completed

    res = Trainer(g, cfg, plan2, source=SampledSource()).run(
        resume_from=crash_dir)
    assert res.history.losses == golden.history.losses
    assert res.history.val_accs == golden.history.val_accs
    assert res.history.bad_steps == golden.history.bad_steps
    assert _params_equal(res.params, golden.params)
    assert res.final_test_acc == golden.final_test_acc


# ---------------------------------------------------------------------------
# Crash-safe sweeps
# ---------------------------------------------------------------------------

def _sweep_args(g):
    cfg = _cfg(g, n_layers=1, fanout=(3,))
    plan = TrainPlan(lr=0.3, n_iters=2, eval_every=100)
    return cfg, plan, dict(batch_sizes=[16, 32], fanout_grid=[(3,)])


def test_sweep_journal_resume_skips_completed(small_graph, tmp_path):
    g = small_graph
    cfg, plan, kw = _sweep_args(g)
    journal = str(tmp_path / "sweep.jsonl")
    with faults.armed("sweep.after_point", at_hits=(0,)):
        with pytest.raises(faults.SimulatedCrash):
            sweep(g, cfg, plan, journal=journal, **kw)
    lines = [json.loads(l) for l in open(journal)]
    assert [l["status"] for l in lines] == ["ok"]

    rows = sweep(g, cfg, plan, journal=journal, **kw)
    lines = [json.loads(l) for l in open(journal)]
    assert len(rows) == 2
    assert len(lines) == 2                  # point 1 NOT rerun
    assert rows[0] == lines[0]["row"]       # journaled row returned as-is


def test_sweep_isolates_point_failure_into_error_row(
        small_graph, tmp_path, monkeypatch):
    g = small_graph
    cfg, plan, kw = _sweep_args(g)
    journal = str(tmp_path / "sweep.jsonl")
    import repro.core.experiment as X
    real = X.run_experiment

    def exploding(graph, cfg_, plan_, **kwargs):
        if kwargs.get("b") == 16:
            raise RuntimeError("boom at b=16")
        return real(graph, cfg_, plan_, **kwargs)

    monkeypatch.setattr(X, "run_experiment", exploding)
    rows = sweep(g, cfg, plan, journal=journal, **kw)
    assert len(rows) == 2
    assert rows[0]["status"] == "error" and "boom" in rows[0]["error"]
    assert rows[1].get("status") != "error"
    # error points are RETRIED on resume (only ok rows are skipped)
    monkeypatch.setattr(X, "run_experiment", real)
    rows2 = sweep(g, cfg, plan, journal=journal, **kw)
    assert all(r.get("status") != "error" for r in rows2)


def test_sweep_without_journal_fails_fast(small_graph, monkeypatch):
    g = small_graph
    cfg, plan, kw = _sweep_args(g)
    import repro.core.experiment as X

    def exploding(*a, **k):
        raise RuntimeError("boom")

    monkeypatch.setattr(X, "run_experiment", exploding)
    with pytest.raises(RuntimeError, match="boom"):
        sweep(g, cfg, plan, **kw)


def test_sweep_degrades_pallas_kernel_failure(small_graph, monkeypatch):
    """A kernel lowering failure is NOT degraded to the einsum path: it
    propagates out of the sweep after exactly one (kernel) attempt."""
    g = small_graph
    cfg, plan, kw = _sweep_args(g)
    cfg = dataclasses.replace(cfg, use_agg_kernel=True)
    import repro.core.experiment as X
    real, seen = X.run_experiment, []

    def mosaic_fails(graph, cfg_, plan_, **kwargs):
        seen.append(cfg_.use_agg_kernel)
        if cfg_.use_agg_kernel:
            raise RuntimeError("Mosaic lowering failed: unsupported op")
        return real(graph, cfg_, plan_, **kwargs)

    monkeypatch.setattr(X, "run_experiment", mosaic_fails)
    with pytest.raises(RuntimeError, match="Mosaic lowering failed"):
        sweep(g, cfg, plan, batch_sizes=[16], fanout_grid=[(3,)])
    assert seen == [True]                  # no einsum retry


# ---------------------------------------------------------------------------
# Determinism of the injection layer itself
# ---------------------------------------------------------------------------

def test_fault_schedule_deterministic():
    a, b = faults.FaultSchedule(11), faults.FaultSchedule(11)
    assert a.pick(100, 5) == b.pick(100, 5)
    assert a.consecutive(50, 4) == b.consecutive(50, 4)
    run = sorted(faults.FaultSchedule(3).consecutive(50, 4))
    assert len(run) == 4
    assert run == list(range(run[0], run[0] + 4))
