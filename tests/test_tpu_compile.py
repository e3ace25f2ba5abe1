"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler installed with jax compiles for a chip that is only
described (``jax.experimental.topologies``), so what Mosaic or XLA would
refuse on the chip is refused here: blocks not aligned to the (8, 128)
tiling, DMA slices not aligned to the tiling of a bf16 table, scalar
memory (SMEM) that grows with B·K, programs that do not fit 16 GB of HBM.
Interpret-mode tests cannot see any of these.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.  The code under test asks ``jax.default_backend``
(the CPU here) whether to interpret its Pallas kernels, so the
``compiled_kernels`` fixture steers that answer to the chip's for the
tests that compile whole programs; the kernel tests pass
``interpret=False`` themselves.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3          # TPU v5e: 16 GB of HBM per chip

# the mini-batch outer hop at papers100M widths: b=8192 targets, fan-out
# 15 then 10 (K=10 pads to 12 for k_slab=4) -> 8192·15 rows x 12 ids
OUTER_ROWS, OUTER_K = 8192 * 15, 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The program picks interpret mode from the backend it runs on; a
    described chip is not the backend, so answer as the chip would."""
    import repro.kernels as K
    monkeypatch.setattr(K, "default_interpret", lambda: False)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
    return total


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "self"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tiled_kernel_compiles(one_chip, dtype, fused, d):
    """K > k_slab (refusal 1: weight block), bf16 rows (refusal 2: row
    DMA slices) and B·K ≥ 1.3M ids (refusal 3: SMEM) in one program."""
    from repro.kernels.neighbor_agg.neighbor_agg import \
        neighbor_agg_pallas_tiled
    from repro.kernels.neighbor_agg.ops import _kernel_width
    # the width ops.py hands the kernel: bf16 d=128 is a half-tile row
    # of 64 words (paired), every other case whole 128-lane tiles
    width = _kernel_width(jax.ShapeDtypeStruct((1, d), dtype),
                          ("tiled", 128, 8, 4))
    assert width == d
    n, b, k = 1 << 21, OUTER_ROWS, OUTER_K
    assert b * k >= 1_300_000 and k > 4

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [S((n, width), dtype), S((b, k), jnp.int32), S((b, k), dtype)]
    if fused:
        args += [S((b, width), dtype), S((b,), dtype)]

    def f(feats, idx, w, *self_args):
        sr, ws = self_args if fused else (None, None)
        return neighbor_agg_pallas_tiled(feats, idx, w, self_rows=sr,
                                         w_self=ws, interpret=False)

    compiled = jax.jit(f).lower(*args).compile()
    assert _has_kernel(compiled)


def test_featshard_compiles_on_four_chips(topo, compiled_kernels):
    """``neighbor_agg_featshard`` (forward and its scatter-add VJP) at
    d=128 on a 4-chip NODES mesh.  A described device holds no arrays,
    so the plan's index arrays are shapes."""
    from repro.kernels.neighbor_agg import featshard as FS
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    n, K, d = 1 << 16, 32, 128
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n, (n, K)).astype(np.int32)
    w = (rng.random((n, K)) < 0.9).astype(np.float32)
    host = FS._plan_arrays(idx, w, np.bincount(idx.ravel(), minlength=n),
                           4, -1)
    assert host["M"] and host["C"]          # both phases and the hot cache
    names = ["lidx_hot", "hot_mask", "lidx_miss", "serve_loc",
             "hot_src_loc", "hot_slot", "hot_valid", "hot_perm"]
    rows2 = NamedSharding(mesh, P("data", None))
    repl1 = NamedSharding(mesh, P(None))

    def sds(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    # the plan is a pytree: its index arrays enter as traced arguments
    plan = object.__new__(FS.FeatShardPlan)
    plan.mesh = mesh
    for k in ("S", "n", "n_pad", "n_loc", "K", "C", "M", "C_max"):
        setattr(plan, k, host[k])
    for k in names:
        setattr(plan, k, sds(host[k], repl1 if k == "hot_perm" else rows2))

    def loss(feats, ww, plan):
        out = FS.neighbor_agg_featshard(feats, ww, plan)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    feats = jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=rows2)
    ww = jax.ShapeDtypeStruct((n, K), jnp.bfloat16, sharding=rows2)
    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        feats, ww, plan).compile()
    assert _has_kernel(compiled)
    hlo = compiled.as_text()
    assert "all-gather" in hlo and "reduce-scatter" in hlo
    _fits(compiled)


def _papers_cfg(**kw):
    from repro.configs.gnn_papers100m import full_config
    cfg = full_config()
    assert cfg.use_agg_kernel and cfg.dtype == "bfloat16"
    return dataclasses.replace(cfg, **kw)


def _step_args(topo, cfg, make_step, input_specs):
    from repro import sharding as sh
    from repro.launch import gnn_steps
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    opt, step = make_step(cfg)
    with sh.activate(mesh):
        params = gnn_steps.gnn_abstract_params(cfg, mesh)
        opt_state = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=sh.named((), mesh)),
            jax.eval_shape(opt.init, params))
        args = input_specs(cfg, mesh)
        return jax.jit(step).lower(params, opt_state, *args).compile()


def test_minibatch_step_compiles_at_papers_width(topo, compiled_kernels):
    from repro.launch import gnn_steps
    cfg = _papers_cfg()
    compiled = _step_args(topo, cfg, gnn_steps.make_minibatch_step,
                          gnn_steps.minibatch_input_specs)
    assert _has_kernel(compiled)
    _fits(compiled)


def test_fullgraph_step_compiles_at_papers_width(topo, compiled_kernels):
    """At the 2^21-node cut ``chip_smoke.py`` trains on (ELL K=32)."""
    from repro.launch import gnn_steps
    cfg = _papers_cfg(n_nodes=1 << 21)
    compiled = _step_args(topo, cfg, gnn_steps.make_fullgraph_step,
                          gnn_steps.fullgraph_input_specs)
    assert _has_kernel(compiled)
    _fits(compiled)


def test_gat_fullgraph_step_compiles_at_arxiv_width(topo, compiled_kernels):
    """GAT at ogbn-arxiv's published widths (3 layers, 3 heads × 250, the
    last averaging 3 heads of 40 classes) over all 169,343 nodes, ELL
    K = 32: one kernel call per head and layer, and the step fits."""
    from repro.configs.base import GNNConfig
    from repro.launch import gnn_steps
    cfg = GNNConfig(name="gat-arxiv", model="gat", n_nodes=169343,
                    feat_dim=128, hidden=750, gat_heads=3, n_classes=40,
                    n_layers=3, fanout=(15, 10, 5), max_degree=32,
                    dtype="bfloat16", use_agg_kernel=True)
    compiled = _step_args(topo, cfg, gnn_steps.make_fullgraph_step,
                          gnn_steps.fullgraph_input_specs)
    assert compiled.as_text().count("tpu_custom_call") == 9
    _fits(compiled)
