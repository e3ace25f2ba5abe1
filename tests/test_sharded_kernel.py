"""Mesh-partitioned Pallas aggregation (PR 5): the shard_map'd kernel
entry points and their engine wiring.

Equivalence contract (extends the PR 3/PR 4 pattern):
- on a 1-DEVICE mesh the sharded kernel path is BIT-identical to the
  unsharded kernel path — forward and gradients (the shard-local VJP
  mirrors the unsharded one; the dfeats psum is an identity there);
- on a 4-DEVICE CPU mesh (interpret mode, own subprocess — the XLA
  device-count flag must be set before jax initializes) it matches the
  einsum path to float tolerance, fwd + grads, for BOTH sharded
  sources, compiling the sharded x kernel step exactly once.
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
from repro.core.engine import (FullGraphSource, SampledSource,
                               ShardedFullGraphSource,
                               ShardedSampledSource, Trainer, TrainPlan)
from repro.data import make_sbm_graph
from repro.kernels.neighbor_agg.ops import (neighbor_agg,
                                            neighbor_agg_batch_sharded,
                                            neighbor_agg_sharded)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KW = dict(d_tile=8, b_tile=4, k_slab=2)


def _cfg(g, **kw):
    base = dict(name="sk", model="gcn", n_nodes=g.n,
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
# Op level: 1-device mesh == unsharded kernel, bit for bit
# ---------------------------------------------------------------------------

def _operands(fused, b=26, n=37, d=19, k=5, seed=0):
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, size=(b, k)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    if not fused:
        return feats, idx, w
    sr = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ws = jnp.asarray(rng.normal(size=(b,)).astype(np.float32))
    return feats, idx, w, sr, ws


@pytest.mark.parametrize("fused", [False, True])
def test_sharded_op_bit_equal_on_one_device_mesh(fused):
    args = _operands(fused)
    mesh = sh.node_mesh(1)
    base = neighbor_agg(*args, use_kernel=True, kernel="tiled", **KW)
    shrd = neighbor_agg_sharded(*args, mesh=mesh, **KW)
    assert np.array_equal(np.asarray(base), np.asarray(shrd))
    # grads bit-equal too: feats, w (+ self_rows, w_self)
    diff = (0, 2) + ((3, 4) if fused else ())

    def loss(fn):
        return lambda *a: (fn(*a) ** 2).sum()

    gb = jax.grad(loss(lambda *a: neighbor_agg(
        *a, use_kernel=True, kernel="tiled", **KW)), argnums=diff)(*args)
    gs = jax.grad(loss(lambda *a: neighbor_agg_sharded(
        *a, mesh=mesh, **KW)), argnums=diff)(*args)
    for a, b in zip(gb, gs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fused", [False, True])
def test_batch_sharded_op_bit_equal_on_one_device_mesh(fused):
    rng = np.random.default_rng(3)
    b, k, d = 8, 5, 19
    h_nb = jnp.asarray(rng.normal(size=(b, k, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    args = (w, h_nb)
    if fused:
        args += (jnp.asarray(rng.normal(size=(b, d)).astype(np.float32)),
                 jnp.asarray(rng.normal(size=(b,)).astype(np.float32)))
    mesh = sh.node_mesh(1)

    def unsharded(ww, nb, *rest):
        table = nb.reshape(-1, d)
        ids = jnp.arange(b * k, dtype=jnp.int32).reshape(b, k)
        return neighbor_agg(table, ids, ww, *rest, use_kernel=True,
                            kernel="tiled", **KW)

    base = unsharded(*args)
    shrd = neighbor_agg_batch_sharded(*args, mesh=mesh, **KW)
    assert np.array_equal(np.asarray(base), np.asarray(shrd))
    diff = tuple(range(len(args)))
    gb = jax.grad(lambda *a: (unsharded(*a) ** 2).sum(),
                  argnums=diff)(*args)
    gs = jax.grad(lambda *a: (neighbor_agg_batch_sharded(
        *a, mesh=mesh, **KW) ** 2).sum(), argnums=diff)(*args)
    for a, b_ in zip(gb, gs):
        assert np.array_equal(np.asarray(a), np.asarray(b_))


def test_sharded_op_pads_rows_to_mesh_multiple():
    """Internal row padding: any B is legal for the ELL entry (eval
    feeds n-row ELLs that need not divide the mesh)."""
    args = _operands(False, b=7)
    mesh = sh.node_mesh(1)
    out = neighbor_agg_sharded(*args, mesh=mesh, **KW)
    assert out.shape[0] == 7


# ---------------------------------------------------------------------------
# Engine level: sharded sources x kernel, 1-device mesh bit-equality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "graphsage"])
def test_sharded_fullgraph_kernel_bit_equal_one_device(graph, model):
    """No guard error anymore, and the sharded x kernel loss sequence is
    bit-identical to the plain kernel path on a 1-device mesh."""
    cfg = _cfg(graph, model=model)
    plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0)
    r1 = Trainer(graph, cfg, plan, source=FullGraphSource()).run()
    t = Trainer(graph, cfg, plan, source=ShardedFullGraphSource())
    r2 = t.run()
    assert r1.history.losses == r2.history.losses
    assert r1.history.val_accs == r2.history.val_accs
    assert r1.final_test_acc == r2.final_test_acc
    assert t._step._cache_size() == 1


@pytest.mark.parametrize("model", ["gcn", "graphsage"])
def test_sharded_minibatch_kernel_bit_equal_one_device(graph, model):
    cfg = _cfg(graph, model=model)
    plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0,
                     track_full_loss_every=2)
    r1 = Trainer(graph, cfg, plan,
                 source=SampledSource(batch_size=32)).run()
    t = Trainer(graph, cfg, plan,
                source=ShardedSampledSource(batch_size=32))
    r2 = t.run()
    assert r1.history.losses == r2.history.losses
    assert r1.history.val_accs == r2.history.val_accs
    assert r1.history.full_losses == r2.history.full_losses
    assert r1.final_test_acc == r2.final_test_acc
    assert t._step._cache_size() == 1


def test_sharded_kernel_step_cached_across_trainers(graph):
    """The sharded x kernel step must reuse ONE compiled step across
    Trainer instances (memoized node_mesh keeps the consts' identity —
    and with it the per-graph step-cache key — stable)."""
    cfg = _cfg(graph)
    plan = TrainPlan(lr=0.3, n_iters=2, seed=0)
    t1 = Trainer(graph, cfg, plan, source=ShardedFullGraphSource())
    t1.run()
    t2 = Trainer(graph, cfg, plan, source=ShardedFullGraphSource())
    assert t2._step is t1._step
    t2.run()
    assert t2._step._cache_size() == 1


# ---------------------------------------------------------------------------
# 4-device CPU mesh (subprocess): kernel path == einsum path, fwd+grads
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
from repro.kernels.neighbor_agg.ops import (neighbor_agg_batch_sharded,
                                            neighbor_agg_sharded)

mesh = sh.node_mesh()
KW = dict(d_tile=8, b_tile=4, k_slab=2)

# -- op level: fwd + VJP (incl. the psum'd dfeats) vs the einsum ref --------
rng = np.random.default_rng(0)
N, D, B, K = 37, 19, 26, 5       # B deliberately NOT divisible by 4
feats = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
idx = jnp.asarray(rng.integers(0, N, size=(B, K)).astype(np.int32))
w = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))

def ref(f, ww):
    return jnp.einsum("bk,bkd->bd", ww, jnp.take(f, idx, axis=0))

out = neighbor_agg_sharded(feats, idx, w, mesh=mesh, **KW)
np.testing.assert_allclose(out, ref(feats, w), rtol=1e-5, atol=1e-5)
gs = jax.grad(lambda f, ww: (neighbor_agg_sharded(
    f, idx, ww, mesh=mesh, **KW) ** 2).sum(), argnums=(0, 1))(feats, w)
gr = jax.grad(lambda f, ww: (ref(f, ww) ** 2).sum(),
              argnums=(0, 1))(feats, w)
for a, b in zip(gs, gr):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

# indivisible rows are rejected on the batch-sharded (fan-out) entry
try:
    neighbor_agg_batch_sharded(w[:6], jnp.zeros((6, K, D)), mesh=mesh, **KW)
    raise SystemExit("expected ValueError for B=6 on 4 shards")
except ValueError:
    pass

# -- engine level: sharded sources, kernel vs einsum on the SAME mesh -------
g = make_sbm_graph(n=202, n_classes=4, avg_degree=8, feat_dim=16, seed=5)
base = GNNConfig(name="md", model="gcn", n_nodes=g.n, feat_dim=16,
                 hidden=16, n_classes=g.n_classes, n_layers=2,
                 fanout=(4, 3), batch_size=30, loss="ce")
kcfg = dataclasses.replace(base, use_agg_kernel=True, agg_b_tile=4,
                           agg_d_tile=8, agg_k_slab=2)
plan = TrainPlan(lr=0.3, n_iters=4, eval_every=2, seed=0)
for make in (lambda: ShardedFullGraphSource(),
             lambda: ShardedSampledSource(batch_size=30)):
    r_e = Trainer(g, base, plan, source=make()).run()
    t_k = Trainer(g, kcfg, plan, source=make())
    r_k = t_k.run()
    np.testing.assert_allclose(r_e.history.losses, r_k.history.losses,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(r_e.history.val_accs, r_k.history.val_accs,
                               rtol=1e-5, atol=1e-5)
    # compile-once for the sharded x kernel step
    assert t_k._step._cache_size() == 1, t_k._step._cache_size()
print("MULTIDEV_KERNEL_OK")
"""


def test_sharded_kernel_on_multidevice_cpu_mesh():
    """4 virtual CPU devices (own process: the flag must be set before
    jax initializes): the shard_map'd kernel matches the einsum path —
    op-level fwd/VJP and both sharded sources' training runs — and the
    sharded x kernel step compiles exactly once."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_KERNEL_OK" in out.stdout
