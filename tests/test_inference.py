"""Layer-wise full-graph inference (PR 7): per-layer equivalence with
the naive ``full_graph_forward`` oracle.

Contract (ISSUE 7 tentpole):
- per-layer allclose for GCN + SAGE (and GAT), kernel AND einsum paths,
  at chunk sizes that do and do not divide n;
- prefetch on/off is BIT-identical (same chunks, same compiled ops);
- on a 1-device NODES mesh the kernel path is BIT-identical to the
  unsharded kernel path (inherited from ``neighbor_agg_sharded``);
- on a 4-device CPU mesh (own subprocess, mirroring
  tests/test_sharded_kernel.py) the sharded layer-wise pass matches the
  naive einsum forward.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding as sh
from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core.graph import to_ell
from repro.core.inference import layerwise_embeddings, layerwise_logits

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(g, **kw):
    base = dict(name="inf", model="gcn", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=8,
                n_classes=g.n_classes, n_layers=2, fanout=(4, 3),
                batch_size=32, loss="ce", use_agg_kernel=False,
                agg_b_tile=4, agg_d_tile=8,
                agg_k_slab=2)
    base.update(kw)
    return GNNConfig(**base)


def _naive_layers(params, cfg, g):
    idx, w, ws = to_ell(g)
    _, layers = G.full_graph_forward(
        params, cfg, jnp.asarray(g.feats), jnp.asarray(idx),
        jnp.asarray(w), jnp.asarray(ws), return_layers=True)
    return layers


def _assert_layers_close(got, want, **tol):
    tol = tol or dict(rtol=1e-5, atol=1e-5)
    assert len(got) == len(want)
    for li, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"layer {li}", **tol)


@pytest.mark.parametrize("model,kernel", [
    ("gcn", False), ("gcn", True),
    ("graphsage", False), ("graphsage", True),
    ("gat", False), ("gat", True),
])
# 37 does not divide n=300, 150 does, 999 > n collapses to one chunk
@pytest.mark.parametrize("chunk", [37, 150, 999])
def test_layerwise_matches_naive(small_graph, model, kernel, chunk):
    cfg = _cfg(small_graph, model=model, use_agg_kernel=kernel)
    params = G.init_gnn(jax.random.key(0), cfg,
                        small_graph.feats.shape[1])
    run = layerwise_embeddings(params, cfg, small_graph, chunk_size=chunk)
    _assert_layers_close(run.layers, _naive_layers(params, cfg,
                                                   small_graph))
    # stats populated and consistent
    assert run.stats["n_chunks"] == -(-small_graph.n
                                      // min(chunk, small_graph.n))
    assert run.stats["chunk_steps"] == cfg.n_layers * run.stats["n_chunks"]
    assert run.stats["total_s"] > 0 and run.stats["ms_per_node"] > 0


def test_layerwise_three_layers_width_shrink(small_graph):
    """3 layers with hidden < feat_dim exercises the pre-aggregation
    width-shrinking transform on every layer."""
    for model in ("gcn", "graphsage"):
        cfg = _cfg(small_graph, model=model, n_layers=3, fanout=(4, 3, 3),
                   hidden=8)
        params = G.init_gnn(jax.random.key(1), cfg,
                            small_graph.feats.shape[1])
        run = layerwise_embeddings(params, cfg, small_graph,
                                   chunk_size=64)
        _assert_layers_close(run.layers,
                             _naive_layers(params, cfg, small_graph))


def test_layerwise_logits_matches_forward(small_graph):
    cfg = _cfg(small_graph, model="graphsage")
    params = G.init_gnn(jax.random.key(2), cfg,
                        small_graph.feats.shape[1])
    idx, w, ws = to_ell(small_graph)
    want = G.full_graph_forward(params, cfg,
                                jnp.asarray(small_graph.feats),
                                jnp.asarray(idx), jnp.asarray(w),
                                jnp.asarray(ws))
    got = layerwise_logits(params, cfg, small_graph, chunk_size=50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_prefetch_off_bit_identical(small_graph):
    cfg = _cfg(small_graph, model="graphsage", use_agg_kernel=True)
    params = G.init_gnn(jax.random.key(3), cfg,
                        small_graph.feats.shape[1])
    r1 = layerwise_embeddings(params, cfg, small_graph, chunk_size=40,
                              prefetch=True)
    r2 = layerwise_embeddings(params, cfg, small_graph, chunk_size=40,
                              prefetch=False)
    for a, b in zip(r1.layers, r2.layers):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("model", ["gcn", "graphsage", "gat"])
def test_one_device_mesh_bit_equal(small_graph, model):
    """Sharded kernel path on a 1-device mesh == unsharded kernel path,
    bit for bit, per layer (the sharded kernel's contract carried into
    inference); for GAT, through each head's kernel call."""
    cfg = _cfg(small_graph, model=model, use_agg_kernel=True)
    params = G.init_gnn(jax.random.key(4), cfg,
                        small_graph.feats.shape[1])
    base = layerwise_embeddings(params, cfg, small_graph, chunk_size=64)
    shrd = layerwise_embeddings(params, cfg, small_graph, chunk_size=64,
                                mesh=sh.node_mesh(1))
    for a, b in zip(base.layers, shrd.layers):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("model", ["gcn", "graphsage", "gat"])
@pytest.mark.parametrize("kernel", [False, True])
def test_layerwise_bf16_bit_equal_to_forward(small_graph, model, kernel):
    """Under dtype="bfloat16" every chunk runs the compiled forward's own
    layer on its rows, so each layer table equals the forward's bit for
    bit.  A chunk path with its own maths (say, a GCN self term summed in
    the table's dtype, not the aggregation dtype) drifts by bf16 ulps."""
    cfg = _cfg(small_graph, model=model, use_agg_kernel=kernel,
               dtype="bfloat16")
    params = G.init_gnn(jax.random.key(5), cfg,
                        small_graph.feats.shape[1])
    idx, w, ws = to_ell(small_graph)
    forward = jax.jit(G.full_graph_forward, static_argnums=1,
                      static_argnames="return_layers")
    _, want = forward(params, cfg, jnp.asarray(small_graph.feats),
                      jnp.asarray(idx), jnp.asarray(w), jnp.asarray(ws),
                      return_layers=True)
    run = layerwise_embeddings(params, cfg, small_graph, chunk_size=37)
    for li, (a, b) in enumerate(zip(run.layers, want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"layer {li}"


def test_empty_graph_rejected(small_graph):
    from repro.core.inference import layerwise_layers
    cfg = _cfg(small_graph)
    params = G.init_gnn(jax.random.key(0), cfg,
                        small_graph.feats.shape[1])
    idx, w, ws = to_ell(small_graph)
    with pytest.raises(ValueError, match="n=0"):
        layerwise_layers(params, cfg, np.zeros((0, 16), np.float32),
                         (idx, w, ws))


# ---------------------------------------------------------------------------
# 4-device CPU mesh (subprocess): sharded layer-wise == naive einsum
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro import sharding as sh
from repro.data import make_sbm_graph
from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core.graph import to_ell
from repro.core.inference import layerwise_embeddings

mesh = sh.node_mesh()
g = make_sbm_graph(n=202, n_classes=4, avg_degree=8, feat_dim=16, seed=5)
idx, w, ws = to_ell(g)
for model in ("gcn", "graphsage"):
    base = GNNConfig(name="md", model=model, n_nodes=g.n, feat_dim=16,
                     hidden=8, n_classes=g.n_classes, n_layers=2,
                     fanout=(4, 3), batch_size=30, loss="ce")
    kcfg = dataclasses.replace(base, use_agg_kernel=True,
                               agg_b_tile=4,
                               agg_d_tile=8, agg_k_slab=2)
    params = G.init_gnn(jax.random.key(0), kcfg, 16)
    _, want = G.full_graph_forward(params, base, jnp.asarray(g.feats),
                                   jnp.asarray(idx), jnp.asarray(w),
                                   jnp.asarray(ws), return_layers=True)
    # chunk size 60 does not divide n=202; shard padding is internal
    run = layerwise_embeddings(params, kcfg, g, chunk_size=60, mesh=mesh)
    for li, (a, b) in enumerate(zip(run.layers, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{model} layer {li}")
print("MULTIDEV_INFERENCE_OK")
"""


def test_layerwise_on_multidevice_cpu_mesh():
    """4 virtual CPU devices (own process: the XLA device-count flag
    must be set before jax initializes): the NODES-sharded layer-wise
    pass matches the naive einsum forward per layer, GCN + SAGE."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_INFERENCE_OK" in out.stdout
