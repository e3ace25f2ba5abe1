"""``bench/registry.py`` finds every entry of ``BENCHMARK.json`` by name,
and a cell, a traffic mix or a metric added as new files alone."""
import json
import os
import re
import shutil

import pytest

from bench.registry import Registry
from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_entry_resolves():
    reg = Registry(ROOT)
    for c in reg.spec["configs"]:
        conf = reg.config(c["name"])
        assert conf["gnn"]["name"] == c["name"]
        assert set(c["reduced"]) == set(conf["reduced"])
        assert callable(reg.model(conf["gnn"]["model"]).layer)
    compared = {"loss0", "loss1", "loss2", "grad0", "grad0_dist", "change3"}
    for w in reg.spec["workloads"]:
        traffic = reg.traffic(w["traffic"])
        lim = reg.limits(w["name"])
        assert lim and set(lim) <= compared | {"sampler_faults"}
        if traffic["source"] == "SampledSource":
            assert lim["sampler_faults"] == 0
    for m in reg.spec["per_layer"]:
        assert callable(reg.reader(m["name"]))


def test_benchmark_json_keeps_to_its_shape():
    spec = Registry(ROOT).spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for e in spec["configs"] + spec["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["layer"] for m in spec["per_layer"]}) <= len(
        spec["per_layer"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in spec["workloads"]}
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in spec["end_to_end"]}
    for w in cells:
        assert {n for n, ws in reported.items() if w in ws} - {"setup_s"}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        # every cell of a per-layer metric reports the metric it moves
        assert set(m.get("workloads", cells)) <= reported[m["moves"]]
    assert 1 <= spec["run_seconds"] <= 51


def test_a_new_cell_is_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = os.path.join(root, "bench")
    conf = Registry(root).config("gcn-arxiv")
    conf["gnn"]["name"] = "gcn-arxiv-2"
    with open(os.path.join(bench, "configs", "gcn-arxiv-2.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(bench, "traffic", "full-w5.json"), "w") as f:
        json.dump({"source": "FullGraphSource", "args": {},
                   "warmup_steps": 5, "trace_steps": 3}, f)
    with open(os.path.join(bench, "metrics", "new_metric.py"), "w") as f:
        f.write("def read(record):\n    return record['x']\n")
    with open(os.path.join(bench, "limits", "arxiv-w5.json"), "w") as f:
        json.dump({"limits": {"loss0": 1.0}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "gcn-arxiv-2", "source": "x",
                            "file": "bench/configs/gcn-arxiv-2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "arxiv-w5", "config": "gcn-arxiv-2",
                              "traffic": "full-w5", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "setup_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    assert reg.config(reg.workload("arxiv-w5")["config"])["gnn"]["name"] \
        == "gcn-arxiv-2"
    assert reg.traffic("full-w5")["warmup_steps"] == 5
    assert reg.reader("new_metric")({"x": 3.0}) == 3.0
    assert reg.limits("arxiv-w5") == {"loss0": 1.0}
    # a metric without a workloads key belongs to every cell
    assert "new_metric" in [m["name"] for m in
                            reg.metrics("per_layer", "arxiv-w5")]
    with pytest.raises(KeyError):
        reg.workload("nope")
