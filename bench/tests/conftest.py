"""CPU-only tests of the benchmark's own code (``bench/``)."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

PLAN = dict(optimizer="adamw", lr=0.01, b1=0.9, b2=0.95, eps=1e-8,
            weight_decay=0.0, clip_norm=1.0)
SPLIT = dict(train=30, val=10, test=10, of=100)


def tiny_conf(name, model, n_layers, **gnn):
    """A configuration of the benchmark's shape at a size a CPU test
    holds: float32, the einsum aggregation (the kernel would run in the
    Pallas interpreter here)."""
    g = dict(name=name, model=model, feat_dim=16, hidden=32, n_classes=5,
             n_layers=n_layers, fanout=[5, 3, 2][:n_layers], batch_size=64,
             max_degree=8, dtype="float32", loss="ce", use_agg_kernel=False,
             agg_b_tile=8, agg_d_tile=128, agg_k_slab=4)
    g.update(gnn)
    return dict(source="test", gnn=g, plan=PLAN, reduced=[],
                data=dict(n=3000, n_classes=5, avg_degree=12.0,
                          homophily=0.6, feat_dim=16, power_law=True, seed=5,
                          split=SPLIT))


def make_root(path, limits=None):
    """A checkout-shaped directory: ``BENCHMARK.json`` with tiny cells on
    the repository's model modules, per-layer readers and peaks (plus a ``cpu`` row so a
    traced CPU run can reduce), and limits for every cell."""
    bench = os.path.join(path, "bench")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    for d in ("metrics", "models"):
        shutil.copytree(os.path.join(ROOT, "bench", d), os.path.join(bench, d),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = dict(next(iter(peaks.values())), source="test")
    with open(os.path.join(bench, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    confs = {"tsage": tiny_conf("tsage", "graphsage", 2),
             "tgcn": tiny_conf("tgcn", "gcn", 3)}
    for k, v in confs.items():
        with open(os.path.join(bench, "configs", k + ".json"), "w") as f:
            json.dump(v, f)
    traffic = {
        "full": dict(source="FullGraphSource", args={}, warmup_steps=3,
                     trace_steps=3),
        "s64": dict(source="SampledSource",
                    args=dict(batch_size=64, fanouts=[5, 3]),
                    warmup_steps=8, trace_steps=5)}
    for k, v in traffic.items():
        with open(os.path.join(bench, "traffic", k + ".json"), "w") as f:
            json.dump(v, f)
    cells = [dict(name="sage-full", config="tsage", traffic="full", chips=1),
             dict(name="sage-s", config="tsage", traffic="s64", chips=1),
             dict(name="gcn-full", config="tgcn", traffic="full", chips=1)]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # a metric of the repository's sampled (full-graph) cells only goes
    # to the tiny sampled (full-graph) cells
    kind = {w["name"]: source_of(w["traffic"]) for w in spec["workloads"]}
    tiny = {"SampledSource": [c["name"] for c in cells
                              if c["traffic"] != "full"],
            "FullGraphSource": [c["name"] for c in cells
                                if c["traffic"] == "full"]}
    spec["configs"] = [dict(name=k, source="test", why="test", reduced=[],
                            file=f"bench/configs/{k}.json") for k in confs]
    spec["workloads"] = cells
    sampled = tiny["SampledSource"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {kind[w] for w in m["workloads"]}
            m["workloads"] = (tiny[kinds.pop()] if len(kinds) == 1
                              else [c["name"] for c in cells])
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    lim = limits or dict(loss0=1e-4, loss1=1e-4, loss2=1e-4, grad0=1e-3,
                         grad0_dist=1e-3, change3=1e-3)
    for c in cells:
        extra = {"sampler_faults": 0} if c["name"] in sampled else {}
        with open(os.path.join(bench, "limits", c["name"] + ".json"),
                  "w") as f:
            json.dump({"limits": dict(lim, **extra)}, f)
    return str(path)


def source_of(traffic: str) -> str:
    """The batch source of one of the repository's traffic mixes."""
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        return json.load(f)["source"]


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture
def cpu_cell(monkeypatch):
    """``bench.run`` with the look for a chip and the compile cache
    switched off, so a CPU test drives the rest of a run."""
    import jax
    from bench import run
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setattr(run, "use_compile_cache", lambda path: None)
    return run
