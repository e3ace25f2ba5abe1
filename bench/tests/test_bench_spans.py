"""The readers of the program's spans and counters (``bench/metrics``
through ``bench/program_spans.py``) on a traced CPU run: with device time
in the record they read the program's log over the window's batches; as a
CPU run's record comes (no device time) they stay out of the line."""
import copy
import os

import numpy as np
import pytest

from bench import program_spans
from bench.registry import Registry
from bench.tests.test_bench_run import run_cell

SPAN_METRICS = ("sample_ms", "stage_ms", "queue_wait_ms", "device_put_ms")


@pytest.fixture
def captured(monkeypatch, cpu_cell):
    """The record the run hands its readers, the iterations of the
    benchmark's window (the ``on_step`` calls made while it was open) and
    the batches drawn inside it (the ``next()`` calls its ``batch_wait``
    counts)."""
    got = {"records": [], "window_its": [], "window_batches": []}
    reader = Registry.reader

    def spy_reader(self, metric):
        read = reader(self, metric)

        def wrapped(record):
            got["records"].append(record)
            return read(record)
        return wrapped

    on_step = cpu_cell.Probe.on_step

    def spy_on_step(self, state):
        if self.open:
            got["window_its"].append(state.it)
        on_step(self, state)

    attach = cpu_cell.Probe.attach

    def spy_attach(self, source, *a, **kw):
        attach(self, source, *a, **kw)
        inner = source.batches

        def batches():
            stream = inner()
            try:
                for i, item in enumerate(stream):
                    if self.open:
                        got["window_batches"].append(i)
                    yield item
            finally:
                stream.close()
        source.batches = batches

    monkeypatch.setattr(Registry, "reader", spy_reader)
    monkeypatch.setattr(cpu_cell.Probe, "on_step", spy_on_step)
    monkeypatch.setattr(cpu_cell.Probe, "attach", spy_attach)
    return got


def test_span_readers_read_the_window(cpu_cell, capsys, tiny_root,
                                      captured):
    result, _ = run_cell(cpu_cell, capsys, tiny_root, "sage-s", trace=1)
    assert result["correct"] is True
    record = captured["records"][0]
    assert record["trace"]["busy_s"] == 0
    assert not set(SPAN_METRICS) & set(result["metrics"])
    reg = Registry(tiny_root)
    for name in SPAN_METRICS:
        assert reg.reader(name)(record) is None

    rec = copy.deepcopy(record)
    rec["trace"]["busy_s"] = 1.0
    got = {name: reg.reader(name)(rec) for name in SPAN_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    ids = program_spans.window_batches(rec, program_spans.snapshot())
    # the batches whose wait the benchmark counted: under deferred sync
    # each is drawn one iteration ahead of the window's step
    assert ids == captured["window_batches"]
    assert ids == [it + 1 for it in captured["window_its"]]
    assert len(ids) == rec["window"]["steps"] == 5
    batch_wait = reg.reader("batch_wait_ms")(rec)
    assert got["queue_wait_ms"] + got["device_put_ms"] <= batch_wait


def test_span_reader_needs_every_window_batch(cpu_cell, capsys, tiny_root,
                                              captured, monkeypatch):
    run_cell(cpu_cell, capsys, tiny_root, "sage-s", trace=1)
    rec = copy.deepcopy(captured["records"][0])
    rec["trace"]["busy_s"] = 1.0
    snap = program_spans.snapshot()
    first = program_spans.window_batches(rec, snap)[0]
    snap["spans"] = [s for s in snap["spans"]
                     if not (s.batch == first and s.name == "device_put")]
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    assert program_spans.mean_span_ms(rec, "device_put") is None
    assert program_spans.mean_span_ms(rec, "sample") > 0


@pytest.mark.parametrize("workload,conf", [("sage-full", "tsage"),
                                           ("gcn-full", "tgcn")])
def test_pad_share_matches_the_hand_count(cpu_cell, capsys, tiny_root,
                                          workload, conf):
    from bench import graph as bgraph
    result, _ = run_cell(cpu_cell, capsys, tiny_root, workload, trace=1)
    assert result["correct"] is True
    got = result["metrics"]["agg_pad_share.full"]
    assert got["unit"] == "%"
    # the tiny configurations run the einsum path: every row reads K slots
    c = Registry(tiny_root).config(conf)
    k = c["gnn"]["max_degree"]
    arrays, _ = bgraph.load(conf, c["data"], os.path.join(
        tiny_root, "bench", ".cache", "graphs"))
    deg = np.diff(arrays["indptr"])
    want = 100.0 * (1.0 - np.minimum(deg, k).sum() / (deg.size * k))
    assert got["value"] == pytest.approx(want, rel=1e-9)
    assert 0 < got["value"] < 100
