"""Sharded feature tables + degree-ordered hot cache (PR 8).

Equivalence contract (extends the PR 5 sharded-kernel pattern):
- on a 1-DEVICE mesh the featshard op is BIT-identical to the unsharded
  tiled kernel — forward and gradients — for every cache size
  (C = auto / 0 / n), fused and unfused;
- on a 4-DEVICE CPU mesh (own subprocess) it matches the einsum
  reference fwd + grads (dw compared where w != 0: zero-weight remote
  refs are excluded from the serve set, so their never-consumed dw
  entries differ from the dense reference by design), the dfeats
  scatter-add VJP equals the replicated path's psum VJP, both sharded
  sources train loss-equal to the replicated layout, and the per-device
  table bytes obey the n·d/S + C·d bound;
- the host plan build is pure numpy and testable without a mesh: Zipf
  degree distributions give the hot cache a high hit rate, C=0 turns
  every non-local reference into a miss, C=n eliminates misses.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding as sh
from repro.configs.base import GNNConfig
from repro.core.engine import (ShardedFullGraphSource,
                               ShardedSampledSource, Trainer, TrainPlan)
from repro.core.featcache import DegreeHotRowCache, LRURowCache
from repro.data import make_sbm_graph
from repro.kernels.neighbor_agg.featshard import (_plan_arrays,
                                                  resolve_cache_rows)
from repro.kernels.neighbor_agg.ops import (build_featshard_plan,
                                            neighbor_agg,
                                            neighbor_agg_featshard)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KW = dict(d_tile=8, b_tile=4, k_slab=2)


def _cfg(g, **kw):
    base = dict(name="fs", model="gcn", n_nodes=g.n,
                feat_dim=g.feats.shape[1], hidden=16,
                n_classes=g.n_classes, n_layers=2, fanout=(4, 3),
                batch_size=32, loss="ce", use_agg_kernel=True,
                agg_b_tile=4, agg_d_tile=8,
                agg_k_slab=2)
    base.update(kw)
    return GNNConfig(**base)


@pytest.fixture(scope="module")
def graph():
    return make_sbm_graph(n=120, n_classes=4, avg_degree=8, feat_dim=16,
                          seed=7)


# ---------------------------------------------------------------------------
# Host plan build (pure numpy, no mesh required)
# ---------------------------------------------------------------------------

def _zipf_ell(n=256, k=8, seed=0, a=1.3):
    """ELL whose column ids follow a Zipf(a) rank distribution over a
    degree-sorted id space — the power-law regime the hot cache targets."""
    rng = np.random.default_rng(seed)
    ranks = np.minimum(rng.zipf(a, size=(n, k)) - 1, n - 1)
    idx = ranks.astype(np.int32)                # id == popularity rank
    w = rng.normal(size=(n, k)).astype(np.float32)
    degrees = np.bincount(idx.reshape(-1), minlength=n)
    return idx, w, degrees


def test_plan_hot_cache_hit_rate_on_zipf_degrees():
    idx, w, degrees = _zipf_ell()
    host = _plan_arrays(idx, w, degrees, n_shards=4,
                        cache_rows=-1)          # auto: C = n // 8 = 32
    st = host["stats"]
    assert st["feat_cache_rows"] == 32
    # top-32-of-256 under Zipf(1.3) catches the bulk of references; the
    # rest splits between local hits and misses
    assert st["feat_cache_hit_rate"] >= 0.75, st
    # the cache must beat the no-cache layout by a wide margin
    st0 = _plan_arrays(idx, w, degrees, n_shards=4,
                       cache_rows=0)["stats"]
    assert st["feat_cache_hit_rate"] >= st0["feat_cache_hit_rate"] + 0.3
    # accounting is exhaustive: every nonzero reference is classified
    nz = int((w != 0).sum())
    assert (st["feat_cache_hot_hits"] + st["feat_cache_local_hits"]
            + st["feat_cache_misses"]) == nz


def test_plan_cache_size_zero_all_nonlocal_miss():
    idx, w, degrees = _zipf_ell(n=64, k=4, seed=1)
    host = _plan_arrays(idx, w, degrees, n_shards=4, cache_rows=0)
    st = host["stats"]
    assert host["C"] == 0 and st["feat_cache_hot_hits"] == 0
    # with no hot set, every nonzero non-local reference is a miss
    owner = np.arange(64) // 16
    expect = int(((w != 0)
                  & (owner[idx] != owner[:, None])).sum())
    assert st["feat_cache_misses"] == expect
    assert host["M"] > 0


def test_plan_cache_covers_all_no_miss():
    idx, w, degrees = _zipf_ell(n=64, k=4, seed=2)
    host = _plan_arrays(idx, w, degrees, n_shards=4, cache_rows=64)
    st = host["stats"]
    assert host["M"] == 0                        # empty serve set
    assert st["feat_cache_misses"] == 0
    assert st["feat_cache_hit_rate"] == 1.0


def test_plan_rejects_indivisible_rows():
    idx, w, degrees = _zipf_ell(n=66, k=4, seed=3)
    with pytest.raises(ValueError, match="divide"):
        _plan_arrays(idx, w, degrees, n_shards=4, cache_rows=0)


def test_resolve_cache_rows():
    assert resolve_cache_rows(-1, 256) == 32     # auto n // 8
    assert resolve_cache_rows(None, 256) == 32
    assert resolve_cache_rows(-1, 4) == 1        # at least 1
    assert resolve_cache_rows(0, 256) == 0       # off
    assert resolve_cache_rows(1000, 256) == 256  # clamped to n


def test_table_bytes_bound_host_arithmetic():
    """ISSUE 8 acceptance bound, host side: resident bytes per device
    are (n/S + C)·d·itemsize — never the replicated n·d."""
    idx, w, degrees = _zipf_ell(n=256, k=8)
    d, item = 32, 4
    host = _plan_arrays(idx, w, degrees, n_shards=4, cache_rows=-1)
    per_dev = (host["n_loc"] + host["C"]) * d * item
    assert per_dev <= 256 * d * item // 4 + host["C"] * d * item
    assert per_dev < 256 * d * item              # strictly sub-replicated


# ---------------------------------------------------------------------------
# Host LRU / degree caches (sampled sources' accounting twin)
# ---------------------------------------------------------------------------

def test_lru_cache_hits_misses_and_eviction():
    c = LRURowCache(capacity=2, row_bytes=8)
    assert c.lookup([1, 2]) == 2                 # cold: both miss
    assert c.lookup([1, 2]) == 0                 # warm: both hit
    c.lookup([3])                                # evicts LRU id 1
    assert c.lookup([1]) == 1                    # 1 was evicted
    st = c.stats()
    assert st["feat_cache_hits"] == 2 and st["feat_cache_misses"] == 4
    assert st["feat_remote_gather_bytes"] == 4 * 8
    assert 0.0 < st["feat_cache_hit_rate"] < 1.0


def test_lru_cache_capacity_zero_all_miss():
    c = LRURowCache(capacity=0, row_bytes=4)
    assert c.lookup([5, 5, 5]) == 3              # no cache: every ref
    st = c.stats()
    assert st["feat_cache_hits"] == 0
    assert st["feat_cache_hit_rate"] == 0.0


def test_lru_duplicate_ids_hit_after_first_touch():
    c = LRURowCache(capacity=4)
    assert c.lookup([7, 7, 7]) == 1              # first touch misses


def test_degree_hot_cache_membership():
    c = DegreeHotRowCache(degrees=[5, 1, 9, 3], capacity=2)
    c.lookup([2, 0, 1, 3])                       # hot set = {2, 0}
    st = c.stats()
    assert st["feat_cache_hits"] == 2 and st["feat_cache_misses"] == 2


# ---------------------------------------------------------------------------
# Op level: 1-device mesh == unsharded tiled kernel, bit for bit
# ---------------------------------------------------------------------------

def _operands(fused, n=40, d=12, k=5, seed=0):
    """Square full-graph operands: table rows == ELL rows (n_pad = n)."""
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    idx = rng.integers(0, n, size=(n, k)).astype(np.int32)
    w = rng.normal(size=(n, k)).astype(np.float32)
    w[rng.random(size=w.shape) < 0.15] = 0.0     # zero-weight padding
    degrees = np.bincount(idx.reshape(-1), minlength=n)
    extra = ()
    if fused:
        extra = (jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
                 jnp.asarray(rng.normal(size=(n,)).astype(np.float32)))
    return feats, idx, jnp.asarray(w), degrees, extra


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("cache_rows", [-1, 0, 40])
def test_featshard_op_bit_equal_on_one_device_mesh(fused, cache_rows):
    feats, idx, w, degrees, extra = _operands(fused)
    mesh = sh.node_mesh(1)
    plan = build_featshard_plan(np.asarray(idx), np.asarray(w), degrees,
                                mesh, cache_rows=cache_rows)
    base = neighbor_agg(feats, jnp.asarray(idx), w, *extra,
                        use_kernel=True, kernel="tiled", **KW)
    fsout = neighbor_agg_featshard(feats, w, plan, *extra, **KW)
    assert np.array_equal(np.asarray(base), np.asarray(fsout))
    # grads bit-equal too: feats, w (+ self_rows, w_self)
    fdiff = (0, 1) + ((2, 3) if fused else ())
    gb = jax.grad(lambda *a: (neighbor_agg(
        a[0], jnp.asarray(idx), *a[1:], use_kernel=True, kernel="tiled",
        **KW) ** 2).sum(), argnums=fdiff)(feats, w, *extra)
    gs = jax.grad(lambda *a: (neighbor_agg_featshard(
        a[0], a[1], plan, *a[2:], **KW) ** 2).sum(),
        argnums=fdiff)(feats, w, *extra)
    for a, b in zip(gb, gs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_featshard_rejects_mismatched_operands():
    feats, idx, w, degrees, _ = _operands(False)
    mesh = sh.node_mesh(1)
    plan = build_featshard_plan(np.asarray(idx), np.asarray(w), degrees,
                                mesh, cache_rows=0)
    with pytest.raises(ValueError, match="rebuild the plan"):
        neighbor_agg_featshard(feats[:20], w, plan, **KW)
    with pytest.raises(ValueError, match="rebuild the plan"):
        neighbor_agg_featshard(feats, w[:, :3], plan, **KW)


# ---------------------------------------------------------------------------
# Engine level: feats_layout="sharded", 1-device mesh bit-equality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "graphsage"])
def test_featshard_fullgraph_bit_equal_one_device(graph, model):
    cfg = _cfg(graph, model=model)
    fscfg = dataclasses.replace(cfg, feats_layout="sharded",
                                feat_cache_rows=-1)
    plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0)
    r1 = Trainer(graph, cfg, plan, source=ShardedFullGraphSource()).run()
    t = Trainer(graph, fscfg, plan, source=ShardedFullGraphSource())
    r2 = t.run()
    assert r1.history.losses == r2.history.losses
    assert r1.history.val_accs == r2.history.val_accs
    assert r1.final_test_acc == r2.final_test_acc
    # the bind-time accounting surfaced through History.counters
    c = r2.history.counters
    assert c["feat_cache_hit_rate"] == 1.0       # 1 device: no misses
    assert c["feat_table_bytes_per_device"] > 0
    assert r1.history.counters == {}             # replicated: no counters


def test_featshard_sampled_source_lru_counters(graph):
    cfg = _cfg(graph, feats_layout="sharded", feat_cache_rows=16)
    plan = TrainPlan(lr=0.3, n_iters=3, eval_every=100, seed=0)
    t = Trainer(graph, cfg, plan,
                source=ShardedSampledSource(batch_size=32))
    res = t.run()
    c = res.history.counters
    assert c["feat_cache_rows"] == 16
    assert c["feat_cache_hits"] + c["feat_cache_misses"] > 0
    assert 0.0 <= c["feat_cache_hit_rate"] <= 1.0
    assert c["feat_remote_gather_bytes"] == (c["feat_cache_misses"]
                                             * graph.feats.shape[1] * 4)


def test_history_counters_roundtrip_through_checkpoint_dict():
    from repro.core.metrics import History
    h = History()
    h.counters["feat_cache_hit_rate"] = 0.75
    h.record(1.0)
    h2 = History.from_dict(h.to_dict())
    assert h2.counters == h.counters


# ---------------------------------------------------------------------------
# Inference: featshard layer-wise pass == replicated forward, 1 device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "graphsage"])
def test_featshard_inference_layers_match_forward(graph, model):
    from repro.core.gnn import full_graph_forward, init_gnn
    from repro.core.graph import to_ell
    from repro.core.inference import layerwise_embeddings

    cfg = _cfg(graph, model=model, feats_layout="sharded",
               feat_cache_rows=-1)
    params = init_gnn(jax.random.PRNGKey(0), cfg, graph.feats.shape[1])
    idx, w, w_self = to_ell(graph)
    rcfg = dataclasses.replace(cfg, feats_layout="replicated")
    _, ref_layers = full_graph_forward(
        params, rcfg, jnp.asarray(graph.feats), jnp.asarray(idx),
        jnp.asarray(w), jnp.asarray(w_self), return_layers=True)
    run = layerwise_embeddings(params, cfg, graph, mesh=sh.node_mesh())
    assert run.stats["feat_table_bytes_per_device"] > 0
    for a, b in zip(run.layers, ref_layers):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 4-device CPU mesh (subprocess): sharded table vs replicated/einsum
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro import sharding as sh
from repro.data import make_sbm_graph
from repro.configs.base import GNNConfig
from repro.core.engine import (ShardedFullGraphSource,
                               ShardedSampledSource, Trainer, TrainPlan)
from repro.kernels.neighbor_agg.ops import (build_featshard_plan,
                                            neighbor_agg_featshard,
                                            neighbor_agg_sharded)

mesh = sh.node_mesh()
KW = dict(d_tile=8, b_tile=4, k_slab=2)

# -- op level: fwd + grads vs the einsum reference, C auto and 0 ------------
rng = np.random.default_rng(0)
N, D, K = 40, 12, 5                      # N divides the 4 shards
feats = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
idx = rng.integers(0, N, size=(N, K)).astype(np.int32)
w_h = rng.normal(size=(N, K)).astype(np.float32)
w_h[rng.random(size=w_h.shape) < 0.15] = 0.0
w = jnp.asarray(w_h)
degrees = np.bincount(idx.reshape(-1), minlength=N)
jidx = jnp.asarray(idx)

def ref(f, ww):
    return jnp.einsum("bk,bkd->bd", ww, jnp.take(f, jidx, axis=0))

nzmask = w_h != 0
for C in (-1, 0, N):
    plan = build_featshard_plan(idx, w_h, degrees, mesh, cache_rows=C)
    out = neighbor_agg_featshard(feats, w, plan, **KW)
    np.testing.assert_allclose(out, ref(feats, w), rtol=1e-5, atol=1e-5)
    gf, gw = jax.grad(lambda f, ww: (neighbor_agg_featshard(
        f, ww, plan, **KW) ** 2).sum(), argnums=(0, 1))(feats, w)
    rf, rw = jax.grad(lambda f, ww: (ref(f, ww) ** 2).sum(),
                      argnums=(0, 1))(feats, w)
    # dfeats: the scatter-add VJP must equal the dense reference
    np.testing.assert_allclose(gf, rf, rtol=1e-4, atol=1e-5)
    # dw compared where w != 0: zero-weight REMOTE refs are excluded
    # from the serve set by design, so their never-consumed dw entries
    # legitimately differ from the dense reference
    np.testing.assert_allclose(np.asarray(gw)[nzmask],
                               np.asarray(rw)[nzmask],
                               rtol=1e-4, atol=1e-5)
    # ... and against the replicated-table psum VJP (PR 5 path): the
    # owner-scatter dfeats must agree with psum-of-replicated exactly
    # up to float tolerance
    sf = jax.grad(lambda f: (neighbor_agg_sharded(
        f, jidx, w, mesh=mesh, **KW) ** 2).sum())(feats)
    np.testing.assert_allclose(gf, sf, rtol=1e-4, atol=1e-5)
    # acceptance bound: per-device resident bytes <= n*d/S + C*d
    Ceff = plan.C
    assert plan.table_bytes_per_device(D) <= (N * D * 4) // 4 + Ceff * D * 4
print("FEATSHARD_OP_OK", flush=True)

# -- engine level: feats_layout sharded vs replicated, both sources ---------
g = make_sbm_graph(n=120, n_classes=4, avg_degree=8, feat_dim=16, seed=5)
base = GNNConfig(name="fsmd", model="gcn", n_nodes=g.n, feat_dim=16,
                 hidden=16, n_classes=g.n_classes, n_layers=2,
                 fanout=(4, 3), batch_size=32, loss="ce",
                 use_agg_kernel=True, agg_b_tile=4,
                 agg_d_tile=8, agg_k_slab=2)
plan = TrainPlan(lr=0.3, n_iters=3, eval_every=2, seed=0)
for model in ("gcn", "graphsage"):
    rcfg = dataclasses.replace(base, model=model)
    fcfg = dataclasses.replace(rcfg, feats_layout="sharded",
                               feat_cache_rows=-1)
    r_r = Trainer(g, rcfg, plan, source=ShardedFullGraphSource()).run()
    t = Trainer(g, fcfg, plan, source=ShardedFullGraphSource())
    r_f = t.run()
    np.testing.assert_allclose(r_r.history.losses, r_f.history.losses,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_r.final_test_acc, r_f.final_test_acc)
    c = r_f.history.counters
    assert 0.0 <= c["feat_cache_hit_rate"] <= 1.0, c
    assert c["feat_cache_misses"] > 0            # 4 shards: real misses
    # acceptance: per-device source-table bytes <= n*d/S + C*d
    item = g.feats.dtype.itemsize
    n_pad = t.source.feats_plan.n_pad
    Ceff = t.source.feats_plan.C
    bound = (n_pad * 16 * item) // 4 + Ceff * 16 * item
    assert c["feat_table_bytes_per_device"] <= bound, (c, bound)
    assert c["feat_remote_gather_bytes"] > 0
print("FEATSHARD_ENGINE_OK", flush=True)

# -- sampled source: LRU accounting on a 4-device mesh ----------------------
scfg = dataclasses.replace(base, feats_layout="sharded",
                           feat_cache_rows=16)
res = Trainer(g, scfg, plan,
              source=ShardedSampledSource(batch_size=32)).run()
c = res.history.counters
assert c["feat_cache_rows"] == 16 and c["feat_cache_misses"] > 0
print("FEATSHARD_LRU_OK", flush=True)

# -- inference: featshard layer-wise pass vs replicated forward -------------
from repro.core.gnn import full_graph_forward, init_gnn
from repro.core.graph import to_ell
from repro.core.inference import layerwise_embeddings
icfg = dataclasses.replace(base, feats_layout="sharded")
params = init_gnn(jax.random.PRNGKey(0), icfg, 16)
idx2, w2, ws2 = to_ell(g)
_, ref_layers = full_graph_forward(
    params, dataclasses.replace(icfg, feats_layout="replicated"),
    jnp.asarray(g.feats), jnp.asarray(idx2), jnp.asarray(w2),
    jnp.asarray(ws2), return_layers=True)
run = layerwise_embeddings(params, icfg, g, mesh=mesh)
for a, b in zip(run.layers, ref_layers):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
assert run.stats["feat_table_bytes_per_device"] > 0
print("FEATSHARD_INFER_OK", flush=True)
"""


def test_featshard_on_multidevice_cpu_mesh():
    """4 virtual CPU devices (own process: the XLA flag must be set
    before jax initializes): sharded-table op == einsum fwd/grads with
    the scatter-add dfeats matching the replicated path's psum, engine
    runs loss-equal to the replicated layout for both sharded sources,
    the per-device byte bound holds, and featshard inference matches
    the replicated forward."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for sentinel in ("FEATSHARD_OP_OK", "FEATSHARD_ENGINE_OK",
                     "FEATSHARD_LRU_OK", "FEATSHARD_INFER_OK"):
        assert sentinel in out.stdout, out.stdout
