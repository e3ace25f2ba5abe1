"""GNN core: both training paradigms, model equivalences, sampler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core.graph import full_adjacency_dense, to_ell
from repro.core.sampler import expand_batch, sample_batch, gather_features
from repro.core.trainer import train_full_graph, train_minibatch


def _cfg(g, model="graphsage", n_layers=2, loss="ce", fanout=None):
    return GNNConfig(name="t", model=model, n_nodes=g.n,
                     feat_dim=g.feats.shape[1], hidden=32,
                     n_classes=g.n_classes, n_layers=n_layers,
                     fanout=tuple(fanout or (5, 3)[:n_layers]),
                     batch_size=64, loss=loss)


def test_ell_matches_dense_adjacency(small_graph):
    """ELL Ã-aggregation == dense Ã row-multiply (paper §2 definition)."""
    g = small_graph
    idx, w, w_self = to_ell(g)
    a = full_adjacency_dense(g)
    x = g.feats
    dense_agg = a @ x
    ell_agg = (np.einsum("nk,nkd->nd", w, x[idx])
               + w_self[:, None] * x)
    np.testing.assert_allclose(ell_agg, dense_agg, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("model", ["gcn", "graphsage", "gat"])
def test_minibatch_full_fanout_matches_fullgraph(small_graph, model):
    """With fan-out >= d_max and the full training set as one batch, the
    mini-batch forward equals the full-graph forward on a 1-layer model —
    the paper's 'full-graph is the (b=n, beta=d_max) special case'."""
    g = small_graph
    cfg = _cfg(g, model=model, n_layers=1, fanout=(g.d_max,))
    params = G.init_gnn(jax.random.key(0), cfg, g.feats.shape[1])

    idx, w, w_self = to_ell(g)
    full = G.full_graph_forward(params, cfg, jnp.asarray(g.feats),
                                jnp.asarray(idx), jnp.asarray(w),
                                jnp.asarray(w_self))
    rng = np.random.default_rng(0)
    targets = g.train_nodes[:64]
    fb = expand_batch(rng, g, targets, (g.d_max,))
    feats = [jnp.asarray(f) for f in gather_features(g, fb)]
    mini = G.minibatch_forward(
        params, cfg, feats,
        [jnp.asarray(m.astype(np.float32)) for m in fb.masks],
        [jnp.asarray(x) for x in fb.weights],
        [jnp.asarray(x) for x in fb.self_w])
    np.testing.assert_allclose(np.asarray(mini),
                               np.asarray(full)[targets],
                               atol=1e-4, rtol=1e-4)


def test_sampler_respects_fanout_and_graph(small_graph):
    g = small_graph
    rng = np.random.default_rng(3)
    fb = sample_batch(rng, g, 32, (5, 3))
    assert fb.nodes[1].shape == (32, 5)
    assert fb.nodes[2].shape == (32, 5, 3)
    # every masked-in neighbor must be a real neighbor
    for b in range(32):
        u = int(fb.nodes[0][b])
        nbrs = set(g.neighbors(u).tolist())
        for j in range(5):
            if fb.masks[0][b, j]:
                assert int(fb.nodes[1][b, j]) in nbrs
    # weights are zero exactly on padding
    assert ((fb.weights[0] > 0) == fb.masks[0]).all()


@pytest.mark.parametrize("loss", ["ce", "mse"])
def test_both_paradigms_learn(small_graph, loss):
    g = small_graph
    cfg = _cfg(g, loss=loss)
    lr = 0.3 if loss == "ce" else 0.05   # the paper tunes lr per loss
    rf = train_full_graph(g, cfg, lr=lr, n_iters=25)
    rm = train_minibatch(g, cfg, lr=lr, n_iters=25)
    assert rf.history.losses[-1] < rf.history.losses[0] * 0.9
    assert rm.history.losses[-1] < rm.history.losses[0]
    assert rf.final_test_acc > 1.5 / g.n_classes
    assert rm.final_test_acc > 1.5 / g.n_classes


def test_gat_output_is_class_logits(small_graph):
    g = small_graph
    cfg = _cfg(g, model="gat")
    params = G.init_gnn(jax.random.key(0), cfg, g.feats.shape[1])
    idx, w, w_self = to_ell(g)
    out = G.full_graph_forward(params, cfg, jnp.asarray(g.feats),
                               jnp.asarray(idx), jnp.asarray(w),
                               jnp.asarray(w_self))
    assert out.shape == (g.n, g.n_classes)


# ---------------------------------------------------------------------------
# GAT on the full-graph kernel path
# ---------------------------------------------------------------------------

def _gat_cfg(g, kernel, n_layers=2, hidden=15, heads=3):
    # hidden 15 over 3 heads: dh = 5, no multiple of a lane tile; the
    # last layer's heads are 4 class logits each, averaged.  f32 tiles
    # of 8 lanes: dh = 5 pads to a whole tile, 4 pairs two rows a tile
    return GNNConfig(name="gat", model="gat", n_nodes=g.n,
                     feat_dim=g.feats.shape[1], hidden=hidden,
                     gat_heads=heads, n_classes=g.n_classes,
                     n_layers=n_layers, fanout=(4,) * n_layers,
                     batch_size=32, max_degree=6, use_agg_kernel=kernel,
                     agg_b_tile=8, agg_d_tile=8, agg_k_slab=4)


def _gat_ell(g):
    """The capped ELL (max_deg 6: low-degree rows keep masked slots) with
    row 5's edges dropped, so that its only edge is its self edge."""
    idx, w, w_self = to_ell(g, max_deg=6)
    w = w.copy()
    w[5] = 0.0
    assert (w[5] == 0).all() and ((w > 0).sum(1) < 6).any()
    return idx, w, w_self


def _np_gat(params, feats, idx, mask):
    """GAT in float64 numpy, node by node: per head z = W h, e_ij =
    LeakyReLU_0.2(a_src·z_i + a_dst·z_j) over the real neighbours and
    the self edge, softmax, sum of z_j; heads concatenated with ReLU,
    averaged on the last layer."""
    h = np.asarray(feats, np.float64)
    for li, p in enumerate(params):
        w, a_s, a_d = (np.asarray(p[k], np.float64)
                       for k in ("w", "a_src", "a_dst"))
        z = np.einsum("nd,dhe->nhe", h, w)
        out = np.zeros_like(z)
        for i in range(h.shape[0]):
            js = list(idx[i][mask[i]]) + [i]
            e = (z[i] * a_s).sum(-1)[None] + (z[js] * a_d).sum(-1)
            e = np.where(e > 0, e, 0.2 * e)
            a = np.exp(e - e.max(0))
            out[i] = np.einsum("mh,mhe->he", a / a.sum(0), z[js])
        h = (out.mean(1) if li == len(params) - 1
             else np.maximum(out.reshape(out.shape[0], -1), 0.0))
    return h


def _gat_loss(cfg, ell, feats):
    idx, w, w_self = (jnp.asarray(x) for x in ell)

    def loss(params):
        out = G.full_graph_forward(params, cfg, jnp.asarray(feats), idx, w,
                                   w_self)
        return jnp.sum(jnp.sin(out)), out
    return loss


def test_gat_kernel_path_matches_einsum_and_numpy(small_graph):
    """Forward against float64 numpy (float32 sums in another order:
    1e-5), and the gradients of W, a_src and a_dst of the kernel path
    (interpret mode) against the einsum path's (the same float32
    arithmetic but for the summation order of the weighted sum: 1e-5 of
    each leaf's largest entry)."""
    g = small_graph
    ell = _gat_ell(g)
    params = G.init_gnn(jax.random.key(3), _gat_cfg(g, False),
                        g.feats.shape[1])
    want = _np_gat(params, g.feats, ell[0], ell[1] > 0)
    got = {}
    with jax.default_matmul_precision("highest"):
        for kernel in (False, True):
            fn = _gat_loss(_gat_cfg(g, kernel), ell, g.feats)
            (_, out), grads = jax.value_and_grad(fn, has_aux=True)(params)
            np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                                       atol=1e-5)
            got[kernel] = grads
    for gk, ge in zip(jax.tree.leaves(got[True]), jax.tree.leaves(got[False])):
        scale = float(jnp.abs(ge).max())
        assert scale > 0
        np.testing.assert_allclose(np.asarray(gk), np.asarray(ge),
                                   atol=1e-5 * scale, rtol=0)


def test_gat_self_edge_uses_slope_0_2():
    """The self edge's logit goes through the same LeakyReLU (slope 0.2)
    as the neighbours': target score -1, neighbour score 0.5 (logit
    -0.5 -> -0.1), own score -1 (logit -2 -> -0.4)."""
    s_t = jnp.array([[-1.0]])
    alpha, alpha_self = G._gat_attention(s_t, jnp.array([[[0.5]]]),
                                         jnp.array([[-1.0]]),
                                         jnp.array([[1.0]]))
    want = np.exp(-0.4) / (np.exp(-0.4) + np.exp(-0.1))
    np.testing.assert_allclose(float(alpha_self[0, 0]), want, rtol=1e-6)
    np.testing.assert_allclose(float(alpha[0, 0, 0]), 1.0 - want, rtol=1e-6)


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    from jax.extend.core import Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, Jaxpr):
                    yield from _walk(inner)


def test_gat_kernel_path_holds_no_nkd_array(small_graph):
    """Forward and backward of the kernel path: one kernel call per head
    per layer, and no array of n·K·d elements (d the narrowest head)
    anywhere; the einsum path has one, so the walk can see it."""
    g = small_graph
    ell = _gat_ell(g)
    n, k = ell[0].shape
    floor = n * k * min(g.n_classes, 15 // 3)
    biggest, calls = {}, {}
    for kernel in (False, True):
        cfg = _gat_cfg(g, kernel)
        params = G.init_gnn(jax.random.key(0), cfg, g.feats.shape[1])
        fn = _gat_loss(cfg, ell, g.feats)
        closed = jax.make_jaxpr(jax.grad(lambda p: fn(p)[0]))(params)
        eqns = list(_walk(closed.jaxpr))
        biggest[kernel] = max(int(np.prod(v.aval.shape)) for e in eqns
                              for v in e.outvars
                              if hasattr(v.aval, "shape"))
        fwd = jax.make_jaxpr(lambda p: fn(p)[0])(params)
        calls[kernel] = sum(e.primitive.name == "pallas_call"
                            for e in _walk(fwd.jaxpr))
    assert biggest[False] >= floor > biggest[True]
    assert calls == {False: 0, True: 3 * 2}


def test_gat_heads_must_split_hidden(small_graph):
    with pytest.raises(ValueError, match="gat_heads"):
        _gat_cfg(small_graph, False, hidden=16).validate()
    _gat_cfg(small_graph, False).validate()
