"""GCN / GraphSAGE(mean) / GAT — the paper's three models (§5), each with
a full-graph (ELL) and a mini-batch (fan-out tree) forward path sharing
the same parameters and one layer function, ``gnn_layer``, over an
aggregator per data layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig

F32 = jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def layer_dims(cfg: GNNConfig, feat_dim: int) -> List[tuple]:
    dims = []
    d_in = feat_dim
    for l in range(cfg.n_layers):
        d_out = cfg.n_classes if l == cfg.n_layers - 1 else cfg.hidden
        dims.append((d_in, d_out))
        d_in = d_out
    return dims


def gat_head_dims(cfg: GNNConfig, feat_dim: int) -> List[tuple]:
    """GAT's ``(heads, dh)`` per layer: hidden layers split their width
    over the heads and concatenate them; the last layer emits full class
    logits per head and averages them."""
    dims = layer_dims(cfg, feat_dim)
    return [(cfg.gat_heads, d_out if li == len(dims) - 1
             else max(d_out // cfg.gat_heads, 1))
            for li, (_, d_out) in enumerate(dims)]


def init_gnn(key, cfg: GNNConfig, feat_dim: int) -> List[Dict[str, Any]]:
    params = []
    heads = gat_head_dims(cfg, feat_dim) if cfg.model == "gat" else None
    for li, (d_in, d_out) in enumerate(layer_dims(cfg, feat_dim)):
        k = jax.random.fold_in(key, li)
        sc = 1.0 / math.sqrt(d_in)
        if cfg.model == "gcn":
            p = {"w": sc * jax.random.normal(k, (d_in, d_out), F32)}
        elif cfg.model == "graphsage":
            k1, k2 = jax.random.split(k)
            p = {"w_self": sc * jax.random.normal(k1, (d_in, d_out), F32),
                 "w_neigh": sc * jax.random.normal(k2, (d_in, d_out), F32)}
        else:  # gat
            h, dh = heads[li]
            k1, k2, k3 = jax.random.split(k, 3)
            p = {"w": sc * jax.random.normal(k1, (d_in, h, dh), F32),
                 "a_src": 0.1 * jax.random.normal(k2, (h, dh), F32),
                 "a_dst": 0.1 * jax.random.normal(k3, (h, dh), F32)}
        params.append(p)
    return params


# ---------------------------------------------------------------------------
# layer primitives (shared by both paths)
# ---------------------------------------------------------------------------

def agg_dtype(cfg: GNNConfig, dt):
    """The dtype a layer's aggregation gathers and sums in: bfloat16
    under ``cfg.dtype == "bfloat16"``, else the table's own ``dt``."""
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else dt


def _kernel_agg(cfg: GNNConfig, table, idx, w, self_rows=None,
                w_self=None, mesh=None):
    """Σ_k w[b,k] · table[idx[b,k]] (+ fused w_self[b] · self_rows[b]
    epilogue) via the batch-tiled, double-buffered Pallas kernel.  With
    ``mesh`` the kernel runs shard-locally over the NODES axis
    (shard_map: rows sharded, table replicated, dfeats psum'd in the
    VJP); without it, single-device dispatch."""
    if mesh is not None:
        from repro.kernels.neighbor_agg.ops import neighbor_agg_sharded
        return neighbor_agg_sharded(
            table, idx, w, self_rows, w_self, mesh=mesh,
            b_tile=cfg.agg_b_tile,
            d_tile=cfg.agg_d_tile, k_slab=cfg.agg_k_slab)
    from repro.kernels.neighbor_agg.ops import neighbor_agg
    return neighbor_agg(table, idx, w, self_rows, w_self,
                        use_kernel=True, kernel="tiled",
                        b_tile=cfg.agg_b_tile,
                        d_tile=cfg.agg_d_tile, k_slab=cfg.agg_k_slab)


def _wsum(cfg: GNNConfig, w_edge, h_nb, h_self=None, w_self=None,
          mesh=None):
    """Weighted neighbor sum over ALREADY-GATHERED features:
    out[..., :] = Σ_k w_edge[..., k] * h_nb[..., k, :]
                  [+ w_self[...] * h_self[..., :]].

    With cfg.use_agg_kernel the fan-out tree is flattened to a [B*K, d]
    table + identity ids so the mini-batch path exercises the same tiled
    kernel (zero-weight padding edges stay exact); the optional self
    term rides the kernel's fused accumulator-init epilogue instead of
    a separate output-sized elementwise pass.  With ``mesh`` the
    flattened rows run shard-locally over the NODES axis (the table is
    derived from the row-sharded tree level, so no collective is
    needed)."""
    fused = h_self is not None
    if not cfg.use_agg_kernel:
        out = jnp.einsum("...k,...kd->...d", w_edge, h_nb)
        return out + w_self[..., None] * h_self if fused else out
    k, d = h_nb.shape[-2], h_nb.shape[-1]
    lead = h_nb.shape[:-2]
    b = h_nb.reshape(-1, d).shape[0] // k
    if mesh is not None:
        from repro.kernels.neighbor_agg.ops import neighbor_agg_batch_sharded
        out = neighbor_agg_batch_sharded(
            w_edge.reshape(b, k), h_nb.reshape(b, k, d),
            h_self.reshape(b, d) if fused else None,
            w_self.reshape(b) if fused else None, mesh=mesh,
            b_tile=cfg.agg_b_tile,
            d_tile=cfg.agg_d_tile, k_slab=cfg.agg_k_slab)
        return out.reshape(lead + (d,))
    table = h_nb.reshape(-1, d)
    idx = jnp.arange(b * k, dtype=jnp.int32).reshape(b, k)
    out = _kernel_agg(cfg, table, idx, w_edge.reshape(b, k),
                      self_rows=h_self.reshape(b, d) if fused else None,
                      w_self=w_self.reshape(b) if fused else None)
    return out.reshape(lead + (d,))


def _gat_attention(s_t, s_nb, s_self, mask):
    """GAT's attention weights over a row's K neighbour slots plus its
    self edge, in float32: ``e = LeakyReLU_0.2(s_t[i] + s_n[j])`` with
    ``s_t = z·a_src`` the target's score and ``s_n = z·a_dst`` the
    neighbour's, masked slots left out, softmax over the rest and the
    self edge (always valid).  ``s_t`` and ``s_self`` (the target's own
    ``s_n``) are ``[..., rows]``, ``s_nb`` ``[..., rows, K]`` and
    ``mask`` broadcasts to it: the slots on the minor axis, and the heads
    leading where the caller has them, for a TPU pads a minor axis of 3
    heads to 128 lanes.  -> ``(alpha [..., rows, K], alpha_self
    [..., rows])``."""
    with jax.named_scope("gat_attention"):
        s_t, s_nb, s_self = (x.astype(F32) for x in (s_t, s_nb, s_self))
        e = jax.nn.leaky_relu(s_t[..., None] + s_nb, 0.2)
        e = jnp.where(mask > 0, e, -1e30)
        e_self = jax.nn.leaky_relu(s_t + s_self, 0.2)
        top = jnp.maximum(e.max(-1), e_self)
        ex = jnp.exp(e - top[..., None])               # masked slots: 0
        ex_self = jnp.exp(e_self - top)
        denom = ex.sum(-1) + ex_self
        return ex / denom[..., None], ex_self / denom


def gat_transform(p, h):
    """``z = h @ W`` once per row, the heads side by side:
    ``[..., d_in] -> [..., H·dh]``."""
    w = p["w"]
    return h @ w.reshape(w.shape[0], -1)


def _gat_layer(p, h_self, h_nb, mask):
    """The mini-batch GAT layer on a fan-out level: every slot holds its
    own row of the tree, so ``W`` applies per slot and the weighted sum
    stays an einsum.  Heads concatenated."""
    heads, dh = p["a_src"].shape
    z_s = gat_transform(p, h_self).reshape(h_self.shape[:-1] + (heads, dh))
    z_n = gat_transform(p, h_nb).reshape(h_nb.shape[:-1] + (heads, dh))

    def score(z, a):                                       # heads leading
        return jnp.moveaxis(jnp.einsum("...he,he->...h", z, a), -1, 0)
    alpha, alpha_self = _gat_attention(
        score(z_s, p["a_src"]), score(z_n, p["a_dst"]),
        score(z_s, p["a_dst"]), mask)
    out = (jnp.einsum("h...k,...khe->...he", alpha, z_n)
           + jnp.moveaxis(alpha_self, 0, -1)[..., None] * z_s)
    return out.reshape(out.shape[:-2] + (-1,))                 # concat heads


def gat_ell_layer(cfg: GNNConfig, p, z, table, idx, mask, last: bool,
                  rows=None, mesh=None):
    """One GAT layer's attention aggregation over ELL rows, heads joined:
    ``out[i, h] = Σ_k α[h,i,k]·z[idx[i,k], h] + α_self[h,i]·z[i, h]``,
    concatenated, or averaged on the last layer.

    ``z`` ``[n, H·dh]`` is the layer's transform of every node
    (``gat_transform``) and ``table`` the same rows in the aggregation
    dtype, the gather source; ``idx``/``mask`` are the ELL ids and real
    slots of the output rows, which are ``rows`` (global ids) or, where
    None, all n.  Head by head: the scores are per node, the edge logits
    an ``[rows, K]`` gather of them, and with ``cfg.use_agg_kernel`` the
    weighted sum is one call of the tiled kernel on the head's column
    block, ``α`` the edge weights and the self edge the fused self term
    (the kernel's VJP gives ``∂α`` as its weight gradient), so no
    ``[rows, K, d]`` array exists; otherwise an einsum over the gathered
    rows.  Each head's sum is rounded to ``table``'s dtype, as the kernel
    writes it."""
    heads, dh = p["a_src"].shape
    nb = None if cfg.use_agg_kernel else jnp.take(table, idx, axis=0)
    self_tab = table if rows is None else jnp.take(table, rows, axis=0)
    outs = []
    for hd in range(heads):
        c = slice(hd * dh, (hd + 1) * dh)
        s_t, s_n = z[:, c] @ p["a_src"][hd], z[:, c] @ p["a_dst"][hd]
        s_self = s_n
        if rows is not None:
            s_t, s_self = jnp.take(s_t, rows), jnp.take(s_n, rows)
        alpha, alpha_self = _gat_attention(s_t, jnp.take(s_n, idx), s_self,
                                           mask)
        if cfg.use_agg_kernel:
            out = _kernel_agg(cfg, table[:, c], idx, alpha,
                              self_rows=self_tab[:, c], w_self=alpha_self,
                              mesh=mesh)
        else:
            out = (jnp.einsum("nk,nkd->nd", alpha, nb[..., c])
                   + alpha_self[:, None] * self_tab[:, c]
                   ).astype(table.dtype)
        outs.append(out.astype(z.dtype))
    return sum(outs) / heads if last else jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# one layer for every path, over two aggregators
# ---------------------------------------------------------------------------

def layer_source(cfg: GNNConfig, p, h):
    """What a layer gathers from, for the whole input table ``h``:
    GAT's per-node transform ``z``; ``h @ W`` where the layer narrows
    (``d_out < d_in``: Ã(hW) == (Ãh)W, and likewise for the GraphSAGE
    neighbour branch, so the gather moves ``d_out``-wide rows); else
    ``h`` itself."""
    if cfg.model == "gat":
        return gat_transform(p, h)
    w = p["w"] if cfg.model == "gcn" else p["w_neigh"]
    return h @ w if w.shape[1] < h.shape[1] else h


def gnn_layer(cfg: GNNConfig, p, h_self, src, agg, last: bool):
    """One layer of the configured model, on every path: the only branch
    on ``cfg.model`` in the forward maths.

    ``h_self`` is the layer's input at its output rows.  ``src`` is what
    it gathers from: ``layer_source``'s table, a fan-out level's
    neighbour rows, or None for ``layer_source(cfg, p, h_self)`` (then
    ``h_self`` is the whole table).  A source narrower than the layer's
    input was transformed already, so the layer skips its ``W``.  ``agg``
    hides the data layout: ``EllAggregator`` (ELL rows) or ``_TreeAgg``
    (one fan-out level).  ReLU on all layers but the last."""
    dt = h_self.dtype

    def source():
        return layer_source(cfg, p, h_self) if src is None else src

    if cfg.model == "gcn":
        s = source()
        w = p["w"]
        a = agg.gcn(s, dt)
        out = a if s.shape[-1] < w.shape[0] else a @ w
    elif cfg.model == "graphsage":
        wn = p["w_neigh"]
        # count before the source's transform: the jaxpr audit's step
        # hashes pin this order of equations
        cnt = jnp.maximum(agg.mask.sum(-1, keepdims=True), 1.0)
        s = source()
        mean = agg.sage(s, dt) / cnt
        out = h_self @ p["w_self"] + (
            mean if s.shape[-1] < wn.shape[0] else mean @ wn)
    else:
        out = agg.gat(p, source(), last)
    return out if last else jax.nn.relu(out)


@dataclasses.dataclass(frozen=True)
class _TreeAgg:
    """``gnn_layer``'s aggregation over one fan-out level: the targets'
    rows ``h_self`` [..., d], the edges' ``mask`` (in ``h_self``'s dtype)
    and weights ``w_edge`` [..., K], the self-loop weights ``w_self``
    [...]; the source is the next level's rows [..., K, d], never
    transformed first.  GAT applies ``W`` per slot (``_gat_layer``),
    since every slot holds a row of its own."""
    cfg: GNNConfig
    h_self: Any
    mask: Any
    w_edge: Any
    w_self: Any
    mesh: Any = None

    def gcn(self, h_nb, dt):
        return _wsum(self.cfg, self.w_edge, h_nb, self.h_self, self.w_self,
                     mesh=self.mesh)

    def sage(self, h_nb, dt):
        return _wsum(self.cfg, self.mask, h_nb, mesh=self.mesh)

    def gat(self, p, h_nb, last):
        out = _gat_layer(p, self.h_self, h_nb, self.mask)
        if last:  # average heads into class logits
            out = out.reshape(out.shape[:-1] + (self.cfg.gat_heads, -1)
                              ).mean(-2)
        return out


class EllAggregator:
    """``gnn_layer``'s aggregation over ELL rows, shared by
    ``full_graph_forward``, ``layer0_agg`` and the layer-wise inference
    passes, so that a hoisted layer-0 result and an inference chunk are
    the forward's own.  ``dt`` is the feature table's dtype.

    ``idx``/``w`` ``[r, K]`` and ``w_self`` ``[r]`` are the ELL rows of
    the output rows: ``rows`` (their global ids, for a chunk) or, where
    None, all n.  The kernel paths return a sum in the aggregation dtype,
    as the kernel writes it, and ``gcn``/``sage`` cast it to the layer's
    dtype; the einsum path casts it in the same program, because XLA may
    keep an einsum's excess precision up to that cast.  ``agg0``
    (``layer0_agg``'s result) stands in for the first ``gcn``/``sage``
    sum; the forward drops it after layer 0.  ``feats_plan``
    (gcn/graphsage on the kernel path, all rows) runs the sums through
    ``neighbor_agg_featshard`` on a NODES-row-sharded table."""

    def __init__(self, cfg: GNNConfig, idx, w, w_self, dt, mesh=None,
                 feats_plan=None, rows=None, agg0=None):
        from repro import sharding as sh
        self.cfg, self.idx, self.w, self.w_self = cfg, idx, w, w_self
        self.mesh, self.rows, self.agg0 = mesh, rows, agg0
        self.maskb = w > 0                      # GAT's real slots
        self.mask = self.maskb.astype(dt)       # GraphSAGE's count
        self.agg_dt = agg_dtype(cfg, dt)
        # the sums read the mask in agg_dt, cast from a bool (not through
        # the f32 mask: a second [n, K] pass under bfloat16) of its own
        # compare, an equation the jaxpr audit's step hashes pin
        self.mask_agg = (w > 0).astype(self.agg_dt)
        fs_active = (feats_plan is not None and cfg.use_agg_kernel
                     and cfg.model in ("gcn", "graphsage"))
        self.fs_plan = feats_plan if fs_active else None
        self.tab_axes = (sh.NODES, None) if fs_active else (None, None)

    def replicate(self, src):
        """Cast + constrain a layer's gather source ONCE; every consumer
        (aggregation, gather, fused self branch) shares the result, so
        each layer emits a single table constraint.  Under a feats plan
        the "replicated" name is historical: the constraint is
        NODES-row-sharded and no full copy exists anywhere."""
        from repro import sharding as sh
        return sh.constrain(src.astype(self.agg_dt), self.tab_axes)

    def _self_rows(self, srcr):
        return (srcr if self.rows is None
                else jnp.take(srcr, self.rows, axis=0))

    def _sum(self, srcr, w_edge, out_dt, self_w=None):
        """Σ_k w_edge[n,k] · srcr[idx[n,k]] (+ the fused self_w[n] ·
        srcr[n] on the kernel paths) without the [n,K,d] blowup;
        ``srcr`` is the already cast+constrained table."""
        cfg, agg_dt = self.cfg, self.agg_dt
        # fused epilogue: the self row IS the source table's row, so the
        # kernel consumes the same constrained table twice
        fused = ({} if self_w is None else
                 dict(self_rows=self._self_rows(srcr),
                      w_self=self_w.astype(agg_dt)))
        if self.fs_plan is not None:
            from repro.kernels.neighbor_agg.ops import neighbor_agg_featshard
            return neighbor_agg_featshard(
                srcr, w_edge.astype(agg_dt), self.fs_plan, **fused,
                b_tile=cfg.agg_b_tile,
                d_tile=cfg.agg_d_tile,
                k_slab=cfg.agg_k_slab)
        if cfg.use_agg_kernel:
            return _kernel_agg(cfg, srcr, self.idx, w_edge.astype(agg_dt),
                               mesh=self.mesh, **fused)
        return jnp.einsum("nk,nkd->nd", w_edge.astype(agg_dt),
                          jnp.take(srcr, self.idx, axis=0)).astype(out_dt)

    def sage_sum(self, srcr, out_dt):
        """Σ_k mask[n,k]·srcr[idx[n,k]]."""
        return self._sum(srcr, self.mask_agg, out_dt)

    def gcn_sum(self, srcr, out_dt):
        """Σ_k w[n,k]·srcr[idx[n,k]] + w_self[n]·srcr[n]."""
        if self.cfg.use_agg_kernel:
            return self._sum(srcr, self.w, out_dt, self.w_self)
        # the self branch rides the SAME cast table as the sum (one
        # constraint per layer, matching the fused kernel's operands)
        return self._sum(srcr, self.w, out_dt) + (
            self.w_self.astype(self.agg_dt)[:, None]
            * self._self_rows(srcr)).astype(out_dt)

    def gcn(self, src, dt):
        s = (self.gcn_sum(self.replicate(src), dt) if self.agg0 is None
             else self.agg0)
        return s.astype(dt)

    def sage(self, src, dt):
        s = (self.sage_sum(self.replicate(src), dt) if self.agg0 is None
             else self.agg0)
        return s.astype(dt)

    def gat(self, p, z, last):
        return gat_ell_layer(self.cfg, p, z, self.replicate(z), self.idx,
                             self.maskb, last, rows=self.rows,
                             mesh=self.mesh)


def layer0_agg(cfg: GNNConfig, feats, ell_idx, ell_w, w_self):
    """Layer 0's neighbour aggregation of the fixed feature table,
    exactly as ``full_graph_forward`` computes it on one device (GCN: the
    sum with its self term; GraphSAGE: the masked neighbour sum before
    ``/ cnt``): on the kernel path in the dtype the kernel writes, on the
    einsum path in the features' dtype.  It does not depend on the
    parameters, so a full-graph source computes it once per bind and
    passes it back as the forward's ``agg0``.  -> None where layer 0's
    aggregation does depend on them: GAT (its ``α``) and a layer 0 that
    transforms before it gathers (``d_out < d_in``)."""
    d_in, d_out = layer_dims(cfg, feats.shape[1])[0]
    if cfg.model not in ("gcn", "graphsage") or d_out < d_in:
        return None
    agg = EllAggregator(cfg, ell_idx, ell_w, w_self, feats.dtype)
    summed = agg.gcn_sum if cfg.model == "gcn" else agg.sage_sum
    return summed(agg.replicate(feats), feats.dtype)


# ---------------------------------------------------------------------------
# full-graph forward (ELL)
# ---------------------------------------------------------------------------

def full_graph_forward(params, cfg: GNNConfig, feats, ell_idx, ell_w,
                       w_self, mesh=None, feats_plan=None,
                       return_layers=False, agg0=None):
    """feats [n, r]; ell_idx/ell_w [n, K]; w_self [n] -> logits [n, C]:
    ``gnn_layer`` per layer over the ELL aggregator.

    Distributed-execution shape (§Perf H1, measured in EXPERIMENTS.md):
      * the gather SOURCE is explicitly replicated across the mesh before
        jnp.take — one all-gather of [n, d] instead of GSPMD's
        all-reduce of the [n, K, d] gather output (K x the wire bytes);
      * when a layer shrinks its width (d_out < d_in), the linear
        transform runs BEFORE aggregation (``layer_source``) so the
        gather moves d_out-wide rows;
      * aggregation traffic runs in cfg.dtype (bf16 at production scale).
    All three are exact (up to float associativity).

    With cfg.use_agg_kernel the gcn/graphsage Ã-aggregation runs through
    the batch-tiled Pallas software-gather kernel on the replicated
    source table — no [n, K, d] gather is materialized (the kernel DMAs
    rows tile-by-tile and keeps the (b_tile, d_tile) accumulator in
    VMEM).  GAT transforms once per node and runs its attention-weighted
    sum through the same kernel, one call per head (``gat_ell_layer``).

    ``mesh`` (sharded sources) partitions the KERNEL path over the
    NODES mesh axis via shard_map — ELL rows shard, the source table
    replicates, and the VJP psum-reduces the table gradient; the einsum
    path ignores it (GSPMD partitions that one by itself).

    ``feats_plan`` (a ``FeatShardPlan``, built per bind by the sharded
    sources under ``cfg.feats_layout == "sharded"``) switches the
    gcn/graphsage kernel path to ``neighbor_agg_featshard``: the source
    table is constrained NODES-row-sharded instead of replicated — no
    device ever holds the full [n, d] table — with the plan's
    degree-ordered hot cache splitting the gather into shard-local hits
    and one compacted cold-miss all_gather.  Every layer's output table
    stays NODES-sharded, so it feeds the next layer (and the layer-wise
    inference pass) without a relayout.  GAT ignores the plan: its
    per-head calls gather from the replicated table.

    ``return_layers`` additionally returns every layer's POST-activation
    table ``[h_1, ..., h_L]`` (``h_L`` = the logits) — the per-layer
    oracle ``core.inference`` validates its layer-wise path against.
    The default path is untouched (the flag only appends to a Python
    list), so the pre-existing golden loss sequences stay bit-for-bit.

    ``agg0`` (``layer0_agg``'s result for the same ``feats`` and ELL)
    takes the place of layer 0's aggregation: the same bits, without the
    layer's gather.
    """
    h = feats
    agg = EllAggregator(cfg, ell_idx, ell_w, w_self, h.dtype, mesh=mesh,
                        feats_plan=feats_plan, agg0=agg0)
    layers = []
    for li, p in enumerate(params):
        h = gnn_layer(cfg, p, h, None, agg, li == len(params) - 1)
        agg.agg0 = None                          # layer 0's only
        if return_layers:
            layers.append(h)
    return (h, layers) if return_layers else h


# ---------------------------------------------------------------------------
# mini-batch forward (fan-out tree)
# ---------------------------------------------------------------------------

def minibatch_forward(params, cfg: GNNConfig, hop_feats: Sequence,
                      masks: Sequence, weights: Sequence, self_w: Sequence,
                      mesh=None):
    """hop_feats[d]: [b, f1..fd, r]; masks/weights[d]: [b, f1..f(d+1)].
    Layer l aggregates hop d+1 into hop d for d < L - l.  ``mesh``
    (sharded sources) runs the kernel path shard-locally over the
    NODES-sharded target axis; the einsum path ignores it."""
    hs = list(hop_feats)
    for li, p in enumerate(params):
        last = li == len(params) - 1
        hs = [gnn_layer(cfg, p, hs[d], hs[d + 1],
                        _TreeAgg(cfg, hs[d], masks[d].astype(hs[d].dtype),
                                 weights[d], self_w[d], mesh), last)
              for d in range(len(hs) - 1)]
    assert len(hs) == 1
    return hs[0]                                      # [b, C]


# ---------------------------------------------------------------------------
# losses (paper: CE and MSE, §3)
# ---------------------------------------------------------------------------

def gnn_loss(logits, labels, kind: str, n_classes: int, valid=None,
             weight=None):
    """CE / MSE over target rows.  ``valid`` (float 0/1 per row, or
    None) masks padded rows out of the mean: padded rows contribute
    exact zeros and the divisor is the valid count, so the result
    matches the unpadded mean up to float summation order.  ``weight``
    (float per row, or None) scales each row's loss BEFORE the mean and
    does NOT enter the divisor — importance-sampled batches pass
    w_j = 1/(n·p_j) so the weighted batch mean stays an unbiased
    estimator of the full training objective regardless of whether the
    sampling scores were normalized."""
    if kind == "mse":
        onehot = jax.nn.one_hot(labels, n_classes, dtype=F32)
        rows = jnp.sum(jnp.square(logits.astype(F32) - onehot), axis=-1)
        if weight is not None:
            rows = rows * weight
        if valid is None:
            return 0.5 * jnp.mean(rows)
        return 0.5 * (jnp.sum(rows * valid) / jnp.sum(valid))
    logz = jax.scipy.special.logsumexp(logits.astype(F32), axis=-1)
    ll = jnp.take_along_axis(logits.astype(F32), labels[..., None],
                             axis=-1)[..., 0]
    rows = logz - ll
    if weight is not None:
        rows = rows * weight
    if valid is None:
        return jnp.mean(rows)
    return jnp.sum(rows * valid) / jnp.sum(valid)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(F32))
