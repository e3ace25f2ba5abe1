"""Distributed GNN step functions for the dry-run + production launcher.

Full-graph training (the paper's paradigm 1) at production scale:
  * node arrays (features, ELL neighbor ids/weights, labels) shard over the
    data axes ("pod" x "data"); the cross-partition neighbor gather becomes
    XLA all-gathers of the feature table — the communication the paper
    attributes to full-graph systems (DistGNN/Sancus), measured in the
    roofline collective term.
  * GNN weights are small and stay replicated (tensor parallelism buys
    nothing at hidden=256; the model axis idles for GNN full-graph).

Mini-batch training (paradigm 2) is pure data parallelism over the sampled
fan-out trees; host sampling is the infeed.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as sh
from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.optim import sgd


def gnn_abstract_params(cfg: GNNConfig, mesh):
    key = jax.random.key(0)
    shapes = jax.eval_shape(
        lambda k: G.init_gnn(k, cfg, cfg.feat_dim), key)
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=sh.named((None,) * l.ndim, mesh)),
        shapes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_fullgraph_step(cfg: GNNConfig):
    opt = sgd(0.1)

    def step(params, opt_state, feats, idx, w, w_self, labels):
        def loss_fn(p):
            logits = G.full_graph_forward(p, cfg, feats, idx, w, w_self)
            return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params2, opt2 = opt.update(grads, opt_state, params)
        return params2, opt2, loss

    return opt, step


def fullgraph_input_specs(cfg: GNNConfig, mesh) -> Tuple[Any, ...]:
    n, k, r = cfg.n_nodes, cfg.max_degree, cfg.feat_dim
    if k is None:
        raise ValueError(f"{cfg.name}: the full-graph step's ELL shape "
                         f"needs cfg.max_degree")
    f32, i32 = jnp.float32, jnp.int32
    sds = lambda shp, dt, spec: jax.ShapeDtypeStruct(
        shp, dt, sharding=sh.named(spec, mesh))
    return (
        sds((n, r), f32, (sh.NODES, None)),       # feats
        sds((n, k), i32, (sh.NODES, None)),       # ELL neighbor ids
        sds((n, k), f32, (sh.NODES, None)),       # ã weights
        sds((n,), f32, (sh.NODES,)),              # self-loop weights
        sds((n,), i32, (sh.NODES,)),              # labels
    )


def make_minibatch_step(cfg: GNNConfig):
    opt = sgd(0.1)

    def step(params, opt_state, feats, masks, weights, self_w, labels):
        def loss_fn(p):
            logits = G.minibatch_forward(p, cfg, feats, masks, weights,
                                         self_w)
            return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params2, opt2 = opt.update(grads, opt_state, params)
        return params2, opt2, loss

    return opt, step


def minibatch_input_specs(cfg: GNNConfig, mesh) -> Tuple[Any, ...]:
    b, r = cfg.batch_size, cfg.feat_dim
    f32, i32 = jnp.float32, jnp.int32
    sds = lambda shp, dt, spec: jax.ShapeDtypeStruct(
        shp, dt, sharding=sh.named(spec, mesh))
    feats, masks, weights, self_w = [], [], [], []
    shape = (b,)
    feats.append(sds(shape + (r,), f32, (sh.BATCH, None)))
    self_w.append(sds(shape, f32, (sh.BATCH,)))
    for beta in cfg.fanout:
        edge = shape + (beta,)
        masks.append(sds(edge, f32, (sh.BATCH,) + (None,) * len(shape)))
        weights.append(sds(edge, f32, (sh.BATCH,) + (None,) * len(shape)))
        shape = edge
        feats.append(sds(shape + (r,), f32,
                         (sh.BATCH,) + (None,) * len(shape)))
        self_w.append(sds(shape, f32, (sh.BATCH,) + (None,) * (len(shape) - 1)))
    labels = sds((b,), i32, (sh.BATCH,))
    return feats, masks, weights, self_w, labels
