"""Finds what ``BENCHMARK.json`` names: configurations by their ``file``,
the module of a configuration's model in ``bench/models/<model>.py``,
traffic mixes in ``bench/traffic/<traffic>.json``, per-layer metric
readers in ``bench/metrics/<metric>.py`` and a cell's output limits in
``bench/limits/<workload>.json``.  A new one is a new file and an entry
in ``BENCHMARK.json``; no existing file changes."""
from __future__ import annotations

import importlib.util
import json
import os

from bench import models


class Registry:
    def __init__(self, root: str):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")

    def _json(self, *parts) -> dict:
        with open(os.path.join(self.bench_dir, *parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def model(self, name: str):
        """The module of model ``name`` (``bench/models``), which the
        reference and the counts ask for what is the model's own."""
        return models.load(name, self.root)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name + ".json")

    def limits(self, workload: str) -> dict:
        """The cell's limit on each number its output check compares; a
        cell without a file has none, and every number then fails."""
        path = os.path.join(self.bench_dir, "limits", workload + ".json")
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return json.load(f)["limits"]

    def metrics(self, kind: str, workload: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str):
        """The ``read(record)`` function of a per-layer metric."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
