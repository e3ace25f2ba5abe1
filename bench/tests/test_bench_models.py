"""The benchmark's model modules (``bench/models``).

The reference's three steps and the step counts are pinned bit for bit
to files recorded before the model-specific mathematics moved out of the
shared drivers (``bench/tests/data/reference_pins.npz``,
``bench/tests/data/counts_pins.json``), and a model added as files alone
is taken by the reference and the counts.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m bench.tests.test_bench_models --record

writes the pins anew from the checkout it runs in.  XLA's CPU backend
splits its work by the number of CPUs the process may use, and the bits
follow the split, so the pinned outputs are computed in a process held
to one CPU.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, counts, graph, reference as R
from bench.tests.conftest import ROOT, tiny_conf

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REF_PINS = os.path.join(DATA, "reference_pins.npz")
COUNT_PINS = os.path.join(DATA, "counts_pins.json")

N = 1500
SEED = 2 ** 31 + 77
MODELS = [("graphsage", 2), ("gcn", 3)]
#: the reference as the benchmark runs it, and its float8 control
VARIANTS = {"plain": dict(lowp=None, precision="highest"),
            "control": dict(lowp=jnp.float8_e4m3fn, precision="default")}


def tiny_graph():
    return graph.make_sbm(n=N, n_classes=5, avg_degree=12.0, homophily=0.6,
                          feat_dim=16, power_law=True, seed=3,
                          split=dict(train=30, val=10, test=10, of=100))


def traffic(path, layers):
    if path == "full":
        return {"source": "FullGraphSource", "args": {}}
    return {"source": "SampledSource",
            "args": {"batch_size": 64, "fanouts": [5, 3, 2][:layers]}}


def reference_outputs(g, conf, path, nodes, variant, **kw):
    """``check.reference_run`` at the tiny size, flattened to named
    arrays: the three losses, the sampler faults, and every leaf of the
    initial params, the first gradient and the params after three
    steps."""
    layers = conf["gnn"]["n_layers"]
    ell = (R.capped_ell(g["indptr"], g["indices"], conf["gnn"]["max_degree"])
           if path == "full" else None)
    out = check.reference_run(conf, traffic(path, layers), g, SEED,
                              nodes=nodes, ell=ell, **VARIANTS[variant], **kw)
    flat = {"losses": np.asarray(out["losses"], np.float64),
            "sampler_faults": np.asarray(out.get("sampler_faults", -1))}
    for name in ("p0", "g0", "p3"):
        for path_, leaf in jax.tree_util.tree_flatten_with_path(out[name])[0]:
            flat[name + jax.tree_util.keystr(path_)] = np.asarray(leaf)
    return flat


def drawn_nodes(g, layers):
    """Three batches' node ids from the program's sampler: inputs of the
    sampled pins, stored with them."""
    from repro.core.graph import Graph
    from repro.core.sampler import sample_batch
    gr = Graph(n=N, **{f: g[f] for f in graph.FIELDS})
    rng = np.random.default_rng(11)
    args = traffic("sampled", layers)["args"]
    return [sample_batch(rng, gr, args["batch_size"], args["fanouts"]).nodes
            for _ in range(3)]


def stored_nodes(pins, model):
    out = []
    for b in range(3):
        keys = sorted((k for k in pins if k.startswith(f"{model}.nodes.{b}.")),
                      key=lambda k: int(k.rsplit(".", 1)[1]))
        out.append([pins[k] for k in keys])
    return out


#: the counts of the benchmark's cells at their real sizes (nodes and
#: kept edges of the built graphs; real slots per hop averaged over a
#: traced papers-sampled window on a v5e), and of GCN's sampled branch at
#: the gcn-arxiv configuration, a case no cell runs
COUNT_CASES = {
    "papers-full": ("sage-papers100m", "fullgraph",
                    dict(n=2097152, edges=52442842)),
    "papers-sampled": ("sage-papers100m", "sampled",
                       dict(batch=8192, fanouts=[15, 10],
                            edges=[122739.36538461539, 1228799.25])),
    "arxiv-full": ("gcn-arxiv", "fullgraph", dict(n=169343, edges=2082800)),
    "arxiv-sampled": ("gcn-arxiv", "sampled",
                      dict(batch=1024, fanouts=[15, 10, 5],
                           edges=[13611.5, 122002.25, 588121.75])),
}


def count_case(name):
    config, kind, args = COUNT_CASES[name]
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        gnn = json.load(f)["gnn"]
    return gnn, kind, args


def one_cpu():
    """Hold this process to one CPU, before XLA's backend starts."""
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})


def reference_pins(out, nodes=None):
    """Every pinned reference output, and the sampled cases' node ids,
    into the ``.npz`` file ``out``.  ``nodes`` holds each model's three
    batches of node ids; by default the program's sampler draws them."""
    g = tiny_graph()
    pins = {}
    for model, layers in MODELS:
        conf = tiny_conf("t", model, layers)
        ids_of = nodes[model] if nodes else drawn_nodes(g, layers)
        for b, hops in enumerate(ids_of):
            for h, ids in enumerate(hops):
                pins[f"{model}.nodes.{b}.{h}"] = np.asarray(ids)
        for path in ("full", "sampled"):
            for variant in VARIANTS:
                got = reference_outputs(g, conf, path,
                                        ids_of if path == "sampled" else None,
                                        variant)
                for k, v in got.items():
                    pins[f"{model}.{path}.{variant}.{k}"] = v
    np.savez_compressed(out, **pins)


def record():
    os.makedirs(DATA, exist_ok=True)
    reference_pins(REF_PINS)
    cpins = {}
    for name in COUNT_CASES:
        gnn, kind, args = count_case(name)
        cpins[name] = getattr(counts, kind)(gnn, **args)
    with open(COUNT_PINS, "w") as f:
        json.dump(cpins, f, indent=1)


@pytest.fixture(scope="module")
def g():
    return tiny_graph()


def load(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


@pytest.fixture(scope="module")
def pins():
    return load(REF_PINS)


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """The pinned outputs computed now, by this checkout's code, on the
    pinned node ids, in a child process held to one CPU."""
    out = str(tmp_path_factory.mktemp("pins") / "fresh.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-m", "bench.tests.test_bench_models",
                    "--compute", out], cwd=ROOT, env=env, check=True,
                   timeout=600)
    return load(out)


def assert_bits(got, pins, prefix):
    want = {k[len(prefix):]: v for k, v in pins.items()
            if k.startswith(prefix)}
    assert set(got) == set(want)
    for k, v in got.items():
        assert (v.dtype, v.shape) == (want[k].dtype, want[k].shape), k
        assert v.tobytes() == want[k].tobytes(), k


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("path", ["full", "sampled"])
@pytest.mark.parametrize("model,layers", MODELS)
def test_reference_is_pinned(pins, fresh, model, layers, path, variant):
    key = f"{model}.{path}.{variant}."
    assert_bits({k[len(key):]: v for k, v in fresh.items()
                 if k.startswith(key)}, pins, key)


@pytest.mark.parametrize("name", sorted(COUNT_CASES))
def test_counts_are_pinned(name):
    gnn, kind, args = count_case(name)
    with open(COUNT_PINS) as f:
        want = json.load(f)[name]
    assert getattr(counts, kind)(gnn, **args) == want


def toy_root(path):
    """A checkout-shaped directory whose one configuration names a model
    ``toy``, with ``bench/models/toy.py`` a copy of the GCN module."""
    bench = os.path.join(path, "bench")
    for d in ("configs", "models", "traffic"):
        os.makedirs(os.path.join(bench, d))
    shutil.copy(os.path.join(ROOT, "bench", "models", "gcn.py"),
                os.path.join(bench, "models", "toy.py"))
    with open(os.path.join(bench, "configs", "toy-tiny.json"), "w") as f:
        json.dump(tiny_conf("toy-tiny", "toy", 3), f)
    spec = {"configs": [{"name": "toy-tiny", "source": "test",
                         "file": "bench/configs/toy-tiny.json",
                         "reduced": [], "why": "test"}],
            "workloads": [], "end_to_end": [], "per_layer": []}
    for p in ("full", "sampled"):
        with open(os.path.join(bench, "traffic", p + ".json"), "w") as f:
            json.dump(traffic(p, 3), f)
        spec["workloads"].append({"name": "toy-" + p, "config": "toy-tiny",
                                  "traffic": p, "chips": 1, "why": "test"})
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return str(path)


@pytest.mark.parametrize("path", ["full", "sampled"])
def test_a_new_model_is_new_files_only(tmp_path, g, pins, path):
    from bench.registry import Registry
    reg = Registry(toy_root(tmp_path))
    cell = reg.workload("toy-" + path)
    conf = reg.config(cell["config"])
    model = reg.model(conf["gnn"]["model"])
    assert reg.traffic(cell["traffic"]) == traffic(path, 3)
    nodes = stored_nodes(pins, "gcn") if path == "sampled" else None
    got = reference_outputs(g, conf, path, nodes, "plain", model=model)
    assert_bits(got, reference_outputs(g, tiny_conf("t", "gcn", 3), path,
                                       nodes, "plain"), "")
    gcn = dict(conf["gnn"], model="gcn")
    if path == "full":
        args = dict(n=N, edges=4321)
    else:
        args = dict(batch=64, fanouts=[5, 3, 2], edges=[300.5, 800.25, 1500.0])
    kind = "fullgraph" if path == "full" else "sampled"
    assert (getattr(counts, kind)(conf["gnn"], **args, model=model)
            == getattr(counts, kind)(gcn, **args))
    # the repository itself has no such model
    with pytest.raises(ValueError, match="toy"):
        getattr(counts, kind)(conf["gnn"], **args)


if __name__ == "__main__":
    one_cpu()
    if sys.argv[1:] == ["--record"]:
        record()
    elif sys.argv[1:2] == ["--compute"] and len(sys.argv) == 3:
        stored = load(REF_PINS)
        reference_pins(sys.argv[2], {m: stored_nodes(stored, m)
                                     for m, _ in MODELS})
    else:
        sys.exit(__doc__)
