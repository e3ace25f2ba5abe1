"""``bench/run.py`` end to end on the CPU, with the look for a chip
switched off: sound runs come out correct, and runs with the timed path
broken underneath come out not correct."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench.tests.conftest import ROOT

SEED = 2 ** 31 + 77        # above 32 signed bits, as a run's seed may be


def run_cell(run, capsys, root, workload, trace=0, seconds="0.5"):
    assert run.main(["--workload", workload, "--seed", str(SEED),
                     "--seconds", seconds, "--trace", str(trace)],
                    root=root) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    return result, err


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload",
         "papers-full", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("workload", ["sage-full", "sage-s", "gcn-full"])
def test_sound_run_is_correct(cpu_cell, capsys, tiny_root, workload):
    result, err = run_cell(cpu_cell, capsys, tiny_root, workload)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    from bench.registry import Registry
    kind = "sampled" if workload == "sage-s" else "full"
    want = {m["name"] for m in
            Registry(tiny_root).metrics("end_to_end", workload)}
    assert {"train_targets_per_s." + kind, "setup_s"} <= want
    if workload == "sage-s":
        assert result["checks"]["sampler_faults"]["value"] == 0
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    # the numbers compared, each beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[1] for line in tail] == list(result["checks"])


def test_traced_run_reports_per_layer_metrics(cpu_cell, capsys, tiny_root):
    result, _ = run_cell(cpu_cell, capsys, tiny_root, "sage-s", trace=1)
    assert result["correct"] is True
    # no device plane on the CPU: the readers of the trace find nothing
    # to read and stay out of the line; the host's readings remain
    assert set(result["metrics"]) == {"batch_wait_ms", "h2d_mb_per_step"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _unchanged_state(engine, monkeypatch):
    monkeypatch.setattr(
        engine, "_guarded_update",
        lambda opt, params, opt_state, loss, grads:
        (params, opt_state, jnp.isfinite(loss)))


def _half_batch(engine, monkeypatch):
    from repro.core import gnn
    loss = gnn.gnn_loss

    def half(logits, labels, kind, n_classes, valid=None, weight=None):
        keep = (jnp.arange(labels.shape[0]) % 2 == 0).astype(jnp.float32)
        return loss(logits, labels, kind, n_classes,
                    valid=keep if valid is None else keep * valid,
                    weight=weight)
    monkeypatch.setattr(gnn, "gnn_loss", half)


@pytest.mark.parametrize("workload", ["sage-full", "sage-s"])
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch],
                         ids=["unchanged_state", "half_batch"])
def test_planted_fault_is_not_correct(cpu_cell, capsys, tiny_root,
                                      monkeypatch, workload, fault):
    from repro.core import engine
    fault(engine, monkeypatch)
    result, _ = run_cell(cpu_cell, capsys, tiny_root, workload)
    assert result["correct"] is False
    over = [k for k, c in result["checks"].items()
            if c["value"] > c["limit"]]
    assert over
