"""Layer-wise full-graph GNN inference (the serving tier's embedding
pass; docs/training_api.md "Inference & serving").

Training-time mini-batch inference pays exponential fan-out: answering b
queries through a k-layer model touches O(b · Π β_l) nodes.  Layer-wise
inference (the inference_helper design, SNIPPETS.md Snippet 1) inverts
the loop order: materialize ALL nodes' layer-l embeddings before any
layer-(l+1) work, so a k-layer model over n nodes costs O(k · n) ELL
gathers total and every query afterwards is a table lookup.

The node axis is CHUNKED: each layer streams [chunk_size]-row slices of
the host ELL through the forward's own layer, ``gnn.gnn_layer`` over
``gnn.EllAggregator`` at the chunk's rows — ``cfg.use_agg_kernel``
routes a chunk through the batch-tiled Pallas
kernel (shard-locally over a NODES mesh when ``mesh`` is given, the PR-5
sharded path), otherwise the einsum gather.  Chunk staging reuses the
engine's ``Prefetcher`` + ``HostStagingRing``: a background thread
copies the next chunk's ELL rows into recycled staging buffers while
the device computes the current one.

Equivalence contract (test-enforced, tests/test_inference.py):
- a chunk runs ``full_graph_forward``'s own layer restricted to its
  rows, so the per-layer tables are ``allclose`` to the naive forward's
  for every model and both aggregation paths, at any chunk size
  (including ones that do not divide n), and bit-equal to the compiled
  forward's under bfloat16;
- on a 1-device mesh the kernel path is BIT-identical to the unsharded
  kernel path (inherited from ``neighbor_agg_sharded``);
- ``prefetch`` on/off is bit-identical (same chunks, same ops).

``core.embedding_store`` builds the cached per-layer tables on top of
this; ``core.serving`` answers queries from them.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GNNConfig
from repro.core import faults
from repro.core import gnn as G
from repro.core.engine import _static_cfg
from repro.core.graph import Graph, to_ell
from repro.core.prefetch import HostStagingRing, Prefetcher


# ---------------------------------------------------------------------------
# Compiled per-chunk layer step
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _chunk_apply(cfg: GNNConfig, last: bool, mesh, p, h, src, rows, idx,
                 w, w_self, feats_plan=None):
    """One node-chunk of one layer: ``gnn.gnn_layer`` with the forward's
    own ELL aggregator, restricted to the chunk's output rows.

    ``h`` [n, d_in] is the full previous-layer table and ``src`` the
    layer's gather source (``gnn.layer_source`` of ``h``); ``rows`` [c]
    are the chunk's global node ids, ``idx``/``w`` [c, K] its ELL rows
    and ``w_self`` [c] the self-loop weights.  Padded tail rows carry
    zero weights (their aggregation is exactly zero) and are trimmed by
    the caller.  ``rows=None`` is one chunk of every row, with ``src``
    None (the layer transforms ``h`` itself), as the featshard pass runs
    it under its ``feats_plan``.  Jitted once per (normalized cfg, last,
    mesh, shapes) at module level, so the store's incremental re-embeds
    reuse the build pass's compiled functions.
    """
    agg = G.EllAggregator(cfg, idx, w, w_self, h.dtype, mesh=mesh,
                          feats_plan=feats_plan, rows=rows)
    h_self = h if rows is None else jnp.take(h, rows, axis=0)
    return G.gnn_layer(cfg, p, h_self, src, agg, last)


# ---------------------------------------------------------------------------
# Chunk staging pipeline (Prefetcher + HostStagingRing reuse)
# ---------------------------------------------------------------------------

class _ChunkStream:
    """Sequential [chunk_size]-row slices of the host ELL, staged into
    recycled ``HostStagingRing`` buffers — by a background ``Prefetcher``
    thread by default, so host-side slicing/padding overlaps the device
    compute of the previous chunk.  The chunk sequence CYCLES: one full
    pass per layer (``passes`` = n_layers), since the ELL rows are
    layer-independent."""

    def __init__(self, ell: Tuple[np.ndarray, np.ndarray, np.ndarray],
                 n: int, chunk_size: int, passes: int,
                 prefetch: bool = True, depth: int = 2):
        self._idx, self._w, self._w_self = ell
        self.n = n
        self.cs = chunk_size
        self.K = self._idx.shape[1]
        self.n_chunks = -(-n // chunk_size)
        # queued payloads (depth) + one being staged + one at the consumer
        self._ring = HostStagingRing(depth + 2)
        counter = itertools.count()

        def sample_fn(rng, graph, batch_size, fanouts):
            return next(counter) % self.n_chunks

        self._sample = sample_fn
        self._pf: Optional[Prefetcher] = None
        if prefetch:
            self._pf = Prefetcher(
                None, 0, (), seed=0, depth=depth,
                n_batches=passes * self.n_chunks,
                payload_fn=self._stage, sample_fn=sample_fn)

    def _stage(self, graph, ci: int):
        """Copy chunk ``ci``'s ELL rows into a staging slot (padded to
        the fixed chunk width with zero-weight rows, so every chunk has
        ONE compiled shape).  Runs on the Prefetcher worker thread."""
        c0 = ci * self.cs
        c1 = min(c0 + self.cs, self.n)
        m = c1 - c0
        specs = [((self.cs,), np.int32), ((self.cs, self.K), np.int32),
                 ((self.cs, self.K), np.float32), ((self.cs,), np.float32)]
        slot = self._ring.acquire()
        try:
            rows_b, idx_b, w_b, ws_b = self._ring.buffers(slot, specs)
            rows_b[:m] = np.arange(c0, c1, dtype=np.int32)
            idx_b[:m] = self._idx[c0:c1]
            w_b[:m] = self._w[c0:c1]
            ws_b[:m] = self._w_self[c0:c1]
            if m < self.cs:          # zero-weight padding rows
                rows_b[m:] = 0
                idx_b[m:] = 0
                w_b[m:] = 0.0
                ws_b[m:] = 0.0
        except BaseException:
            # never strand a slot on a dying worker (engine convention)
            self._ring.release(slot)
            raise
        return slot, (rows_b, idx_b, w_b, ws_b, m)

    def next(self):
        """-> ((rows, idx, w, w_self) device arrays, n_valid, slot).

        CPU ``device_put`` ZERO-COPIES sufficiently aligned host buffers
        — the returned device arrays may alias the slot's staging
        memory, so the slot must stay unreleased until the chunk's
        consuming COMPUTATION has finished (the engine's release-after-
        step-sync rule), not merely until the transfer lands.  The
        caller hands the slot back via ``release`` after syncing."""
        if self._pf is not None:
            _, payload = self._pf.next()
        else:
            payload = self._stage(None, self._sample(None, None, 0, ()))
        slot, (rows, idxb, wb, wsb, m) = payload
        dev = jax.device_put((rows, idxb, wb, wsb))
        return dev, m, slot

    def release(self, slot: int) -> None:
        self._ring.release(slot)

    def close(self):
        self._ring.close()
        if self._pf is not None:
            pf, self._pf = self._pf, None
            pf.close()


# ---------------------------------------------------------------------------
# Layer-wise inference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class InferenceRun:
    """Per-layer embedding tables plus timing stats.

    ``layers[l]`` is the POST-activation [n, d_l] table (what feeds
    layer l+1); ``layers[-1]`` are the logits — per-layer equal to
    ``full_graph_forward(..., return_layers=True)``."""
    layers: List[jax.Array]
    stats: Dict[str, float]

    @property
    def logits(self):
        return self.layers[-1]


def _featshard_run(params, scfg: GNNConfig, feats, ell,
                   fsplan) -> InferenceRun:
    """The featshard inference pass: per-layer tables NODES-sharded over
    ``fsplan.mesh`` end-to-end.  No chunk stream — the plan already
    splits every row's gather into shard-local hits and one compacted
    cold all_gather, so each layer is ONE sharded device step and the
    per-device high-water mark is O(n·d / S + C·d), never a full
    table."""
    from repro import sharding as sh
    if scfg.model not in ("gcn", "graphsage") or not scfg.use_agg_kernel:
        raise ValueError(
            "featshard inference needs use_agg_kernel=True and a "
            f"gcn/graphsage model, got model={scfg.model!r}, "
            f"use_agg_kernel={scfg.use_agg_kernel} (GAT runs on the "
            "replicated table — use the chunked path)")
    idx, w, w_self = ell
    n = int(feats.shape[0])
    pad = fsplan.n_pad - n
    if pad < 0 or w.shape != (n, fsplan.K):
        raise ValueError(
            f"featshard inference: ELL shape {w.shape} does not match "
            f"the plan (n_pad={fsplan.n_pad}, K={fsplan.K}) — build the "
            f"plan from THIS ell/mesh (layerwise_embeddings does)")
    feats = np.asarray(feats)
    if pad:                      # zero rows/weights: aggregate to zero
        feats = np.pad(feats, ((0, pad), (0, 0)))
        w = np.pad(w, ((0, pad), (0, 0)))
        w_self = np.pad(w_self, (0, pad))
    mesh = fsplan.mesh
    rows2 = sh.named((sh.NODES, None), mesh)
    row1 = sh.named((sh.NODES,), mesh)
    h = jax.device_put(np.ascontiguousarray(feats), rows2)
    w_d = jax.device_put(np.ascontiguousarray(w), rows2)
    ws_d = jax.device_put(np.ascontiguousarray(w_self), row1)
    layers: List[jax.Array] = []
    per_layer: List[float] = []
    t0 = time.perf_counter()
    for li, p in enumerate(params):
        lt0 = time.perf_counter()
        last = li == len(params) - 1
        h = _chunk_apply(scfg, last, None, p, h, src=None, rows=None,
                         idx=None, w=w_d, w_self=ws_d, feats_plan=fsplan)
        jax.block_until_ready(h)
        # h itself stays padded + NODES-sharded for the next layer; the
        # returned table is trimmed to the real rows
        layers.append(h[:n] if pad else h)
        per_layer.append(round(time.perf_counter() - lt0, 6))
        faults.maybe_crash("infer.after_layer")
    total = time.perf_counter() - t0
    d = feats.shape[1]
    item = np.dtype(G.agg_dtype(scfg, feats.dtype)).itemsize
    stats = {
        "n_nodes": n, "n_layers": len(params), "chunk_size": n,
        "n_chunks": 1, "chunk_steps": len(params),
        "total_s": round(total, 6), "per_layer_s": per_layer,
        "ms_per_node": round(1000.0 * total / n, 6),
        "feat_table_bytes_per_device": fsplan.table_bytes_per_device(
            d, item),
        "feat_remote_gather_bytes": fsplan.remote_bytes_per_call(d, item),
        **fsplan.stats,
    }
    return InferenceRun(layers=layers, stats=stats)


def layerwise_layers(params, cfg: GNNConfig, feats,
                     ell: Tuple[np.ndarray, np.ndarray, np.ndarray], *,
                     chunk_size: int = 1024, mesh=None,
                     prefetch: bool = True, feats_plan=None
                     ) -> InferenceRun:
    """Layer-wise inference over host ELL arrays ``(idx, w, w_self)``.

    Per layer: the (optional) width-shrinking pre-transform runs ONCE on
    the full table, then every node chunk aggregates against it through
    the configured kernel/einsum path; the concatenated rows become the
    next layer's table.  Memory high-water mark is O(n · d) tables plus
    one [chunk, K, d] gather — never the [n, K, d] blowup, and never the
    exponential fan-out tree.

    ``feats_plan`` (a ``FeatShardPlan`` built from THIS ell) switches to
    the NODES-sharded table pass (``_featshard_run``): chunking and
    ``mesh`` are ignored — the plan's mesh partitions everything and
    every per-layer table stays row-sharded."""
    scfg = _static_cfg(cfg)
    if feats_plan is not None:
        return _featshard_run(params, scfg, feats, ell, feats_plan)
    n = int(feats.shape[0])
    if n == 0:
        raise ValueError("layerwise_layers: empty graph (n=0)")
    cs = max(1, min(int(chunk_size) if chunk_size else n, n))
    h = jnp.asarray(feats)
    stream = _ChunkStream(ell, n, cs, passes=len(params),
                          prefetch=prefetch)
    layers: List[jax.Array] = []
    per_layer: List[float] = []
    t0 = time.perf_counter()
    try:
        for li, p in enumerate(params):
            lt0 = time.perf_counter()
            last = li == len(params) - 1
            src = G.layer_source(scfg, p, h)
            outs = []
            for _ in range(stream.n_chunks):
                (rows, cidx, cw, cws), m, slot = stream.next()
                out = _chunk_apply(scfg, last, mesh, p, h, src, rows,
                                   cidx, cw, cws)
                # sync BEFORE recycling the slot: the chunk operands may
                # alias the staging buffers (zero-copy device_put)
                jax.block_until_ready(out)
                stream.release(slot)
                outs.append(out if m == cs else out[:m])
            h = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 0)
            jax.block_until_ready(h)
            layers.append(h)
            per_layer.append(round(time.perf_counter() - lt0, 6))
            faults.maybe_crash("infer.after_layer")
    finally:
        stream.close()
    total = time.perf_counter() - t0
    stats = {
        "n_nodes": n, "n_layers": len(params), "chunk_size": cs,
        "n_chunks": stream.n_chunks,
        "chunk_steps": len(params) * stream.n_chunks,
        "total_s": round(total, 6),
        "per_layer_s": per_layer,
        "ms_per_node": round(1000.0 * total / n, 6),
    }
    return InferenceRun(layers=layers, stats=stats)


def layerwise_embeddings(params, cfg: GNNConfig, graph: Graph, *,
                         max_deg: Optional[int] = None,
                         chunk_size: int = 1024, mesh=None,
                         prefetch: bool = True,
                         feats_plan=None) -> InferenceRun:
    """Layer-wise inference straight from a ``Graph`` (ELL derived here;
    ``max_deg=None`` keeps ALL neighbors — inference uses the full
    neighborhood, §4.1).  Under ``cfg.feats_layout == "sharded"`` with
    the kernel on and a ``mesh``, a featshard plan is built from this
    inference ELL (NOT reused from training — the full neighborhood has
    its own K) and the NODES-sharded table pass runs instead of the
    chunk stream."""
    ell = to_ell(graph, max_deg=max_deg)
    if (feats_plan is None and cfg.feats_layout == "sharded"
            and cfg.use_agg_kernel and mesh is not None
            and cfg.model in ("gcn", "graphsage")):
        from repro import sharding as sh
        from repro.kernels.neighbor_agg.ops import build_featshard_plan
        idx, w, _ = ell
        pad = (-graph.n) % sh.nodes_shards(mesh)
        if pad:
            idx = np.pad(idx, ((0, pad), (0, 0)))
            w = np.pad(w, ((0, pad), (0, 0)))
        feats_plan = build_featshard_plan(
            idx, w, graph.degrees, mesh,
            cache_rows=cfg.feat_cache_rows)
    return layerwise_layers(params, cfg, graph.feats, ell,
                            chunk_size=chunk_size, mesh=mesh,
                            prefetch=prefetch, feats_plan=feats_plan)


def layerwise_logits(params, cfg: GNNConfig, graph: Graph,
                     **kw) -> jax.Array:
    """Final-layer logits [n, C] only."""
    return layerwise_embeddings(params, cfg, graph, **kw).logits
