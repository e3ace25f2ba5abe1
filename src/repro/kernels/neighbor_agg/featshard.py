"""NODES-sharded feature tables + degree-ordered hot cache.

Every earlier sharded entry point (``neighbor_agg_sharded``) replicates
the full ``[n, d]`` gather source on each device, so the largest graph is
capped by ONE device's memory.  This module drops that constraint:

- the table is row-sharded over the NODES mesh axis (owner shard of row
  ``i`` = ``i // (n_pad // S)``, the same contiguous-block layout
  ``ShardedFullGraphSource`` already uploads at rest);
- a **degree-ordered hot cache** — the top-C highest-degree rows — is
  replicated on every shard (power-law degree distributions make a small
  C catch most gather references);
- each shard's ELL gather is split at plan-build time into *hot/local
  hits* (phase 1: purely shard-local) and *cold remote misses* (phase 2):
  the misses are compacted into per-owner serve lists and move via ONE
  ``all_gather`` of only the miss set.  The serve gather depends only on
  the local table block, so XLA overlaps the collective with the phase-1
  Pallas aggregation; phase 2 then accumulates into the same output
  through the tiled kernel's fused self-weight epilogue (accumulator
  init = the phase-1 partial), i.e. both phases land in one VMEM tile
  accumulator.
- the custom VJP **scatter-adds** ``dfeats`` back to owner shards — a
  ``psum_scatter`` of the compacted ``[S·M, d]`` serve-grad buffer plus a
  ``psum`` of only the ``[C, d]`` hot rows — instead of psum-ing a
  replicated ``[n, d]`` table.

Per-device table memory drops from ``O(n·d)`` to
``O(n·d / S + C·d)`` (``table_bytes_per_device``); cross-shard traffic
per call is ``(S-1)·(M + C_max)`` rows (``remote_bytes_per_call``).

The plan is STATIC per (graph ELL, mesh, C): all index remapping happens
once at bind time on the host (``build_featshard_plan``).  The resulting
plan is a pytree: its device index arrays are leaves, passed to the op
(and to every jitted function that takes the plan) as arguments, and its
layout (mesh, sizes) is static.  On a 1-device mesh every reference is hot or local and the miss
set is empty, so the op is bit-identical to the unsharded tiled kernel —
forward AND gradients (test-enforced, tests/test_featshard.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def resolve_cache_rows(cache_rows: Optional[int], n: int) -> int:
    """Hot-cache size C for ``GNNConfig.feat_cache_rows``: ``-1``/None →
    auto (n // 8, at least 1), ``0`` → no cache, else min(cache_rows, n).
    Only REAL rows (< n) are cacheable; padding rows have no edges."""
    if cache_rows is None or cache_rows < 0:
        return min(n, max(1, n // 8))
    return min(int(cache_rows), n)


# ---------------------------------------------------------------------------
# Host-side plan build (pure numpy — testable without a multi-device mesh)
# ---------------------------------------------------------------------------

def _plan_arrays(idx, w, degrees, n_shards: int, cache_rows: int) -> dict:
    """Classify every ELL entry against the (owner-map, hot-set) split and
    build the remapped per-shard index arrays.

    ``idx``/``w`` are the HOST ELL arrays already padded to an
    ``n_shards`` multiple of rows (zero-weight padding entries are
    treated as hits so they never generate serve traffic); ``degrees``
    ranks the n REAL rows for the hot set.
    """
    idx = np.asarray(idx)
    w = np.asarray(w)
    n_pad, K = idx.shape
    S = int(n_shards)
    if n_pad % S:
        raise ValueError(
            f"featshard plan: n_pad={n_pad} rows must divide the {S} "
            f"NODES shards (pad with zero-weight rows first)")
    n_loc = n_pad // S
    n = int(np.asarray(degrees).shape[0])
    C = resolve_cache_rows(cache_rows, n)

    # degree-ordered hot set (stable sort: deterministic under ties)
    order = np.argsort(-np.asarray(degrees, np.float64), kind="stable")
    hot_ids = order[:C].astype(np.int64)
    slot_of = np.full(n_pad, -1, np.int64)
    slot_of[hot_ids] = np.arange(C, dtype=np.int64)

    owner = np.arange(n_pad, dtype=np.int64) // n_loc     # owner map
    j = idx.astype(np.int64)
    nz = w != 0
    is_hot = slot_of[j] >= 0
    b_owner = owner[:, None]                              # shard of row b
    is_local = owner[j] == b_owner
    miss = nz & ~(is_hot | is_local)

    # phase 1: indices into concat(hot[C], local[n_loc]).  Every hot or
    # local reference keeps its faithful remap EVEN at zero weight, so
    # dw = <g, table[lidx]> matches the unsharded kernel bit-for-bit
    # wherever the row is reachable; only remote rows (misses, plus
    # zero-weight remote refs that must not join the serve set) point at
    # row 0 with zero effective weight.
    lidx_hot = np.where(is_hot, slot_of[j], C + (j - b_owner * n_loc))
    lidx_hot = np.where(is_hot | is_local, lidx_hot, 0).astype(np.int32)
    hot_mask = (~miss).astype(np.float32)

    # phase 2: compacted per-owner serve lists.  The gathered buffer is
    # laid out [S * M] identically on every shard (owner-major), so miss
    # indices owner*M + pos are shard-independent.
    j_miss = j[miss]
    miss_owner = owner[j_miss]
    serve_ids = [np.unique(j_miss[miss_owner == t]) for t in range(S)]
    M = int(max((len(s) for s in serve_ids), default=0))
    lidx_miss = np.zeros((n_pad, K), np.int32)
    serve_loc = np.zeros((S, max(M, 1)), np.int32)
    if M:
        pos_of = np.zeros(n_pad, np.int64)
        for t, ids in enumerate(serve_ids):               # disjoint by owner
            pos_of[ids] = np.arange(len(ids))
            serve_loc[t, : len(ids)] = ids - t * n_loc
        lidx_miss = np.where(miss, owner[j] * M + pos_of[j], 0
                             ).astype(np.int32)

    # hot-cache (re)build plumbing: which LOCAL rows each shard owns of
    # the hot set, and the static permutation that reassembles the
    # all_gathered owner-major parts back into slot order.
    C_max = 0
    hot_src_loc = hot_slot = hot_valid = hot_perm = None
    if C:
        hot_owner = owner[hot_ids]
        slots_by_t = [np.nonzero(hot_owner == t)[0] for t in range(S)]
        C_max = int(max(len(s) for s in slots_by_t))      # >= 1 when C > 0
        hot_src_loc = np.zeros((S, C_max), np.int32)
        hot_slot = np.zeros((S, C_max), np.int32)
        hot_valid = np.zeros((S, C_max), np.float32)
        hot_perm = np.zeros(C, np.int32)
        for t, slots in enumerate(slots_by_t):
            q = len(slots)
            hot_src_loc[t, :q] = hot_ids[slots] - t * n_loc
            hot_slot[t, :q] = slots
            hot_valid[t, :q] = 1.0
            hot_perm[slots] = t * C_max + np.arange(q)

    nz_total = int(nz.sum())
    n_miss = int(miss.sum())
    n_hot = int((nz & is_hot).sum())
    n_local = int((nz & is_local & ~is_hot).sum())
    stats = {
        "feat_table_shards": S,
        "feat_cache_rows": C,
        "feat_cache_hot_hits": n_hot,
        "feat_cache_local_hits": n_local,
        "feat_cache_misses": n_miss,
        "feat_cache_hit_rate": ((nz_total - n_miss) / nz_total
                                if nz_total else 1.0),
        # rows RECEIVED per device per aggregation call: the serve
        # all_gather ((S-1)·M remote rows) + the hot-cache fill
        # ((S-1)·C_max remote rows)
        "remote_rows_per_call": (S - 1) * (M + C_max),
    }
    return {
        "S": S, "n": n, "n_pad": n_pad, "n_loc": n_loc, "K": K,
        "C": C, "M": M, "C_max": C_max,
        "hot_ids": hot_ids,
        "lidx_hot": lidx_hot, "hot_mask": hot_mask,
        "lidx_miss": lidx_miss, "serve_loc": serve_loc,
        "hot_src_loc": hot_src_loc, "hot_slot": hot_slot,
        "hot_valid": hot_valid, "hot_perm": hot_perm,
        "stats": stats,
    }


# ---------------------------------------------------------------------------
# Device-resident plan
# ---------------------------------------------------------------------------

#: the plan's device index arrays (pytree leaves; None where unused)
_PLAN_ARRAYS = ("lidx_hot", "hot_mask", "lidx_miss", "serve_loc",
                "hot_src_loc", "hot_slot", "hot_valid", "hot_perm")
#: the plan's static layout (pytree aux data, hashed by jit)
_PLAN_META = ("S", "n", "n_pad", "n_loc", "K", "C", "M", "C_max")


@jax.tree_util.register_pytree_node_class
class FeatShardPlan:
    """Device-resident featshard plan for one (graph ELL, mesh, C).

    A pytree: the index arrays are leaves, so a jitted function takes
    the plan as an ordinary argument (the arrays stay device buffers,
    never executable constants), and the mesh plus the sizes are its
    static aux data, so two plans of the same layout share one trace.
    The host-side ``hot_ids``/``stats`` do not cross a jit boundary.
    """

    def __init__(self, mesh, host: dict):
        from repro import sharding as sh
        self.mesh = mesh
        for k in _PLAN_META:
            setattr(self, k, host[k])
        self.hot_ids = host["hot_ids"]
        self.stats = dict(host["stats"])
        rows2 = sh.named((sh.NODES, None), mesh)
        repl1 = sh.named((None,), mesh)

        def put(a):
            return jax.device_put(np.ascontiguousarray(a), rows2)

        self.lidx_hot = put(host["lidx_hot"])
        self.hot_mask = put(host["hot_mask"]) if self.M else None
        self.lidx_miss = put(host["lidx_miss"]) if self.M else None
        self.serve_loc = put(host["serve_loc"]) if self.M else None
        if self.C:
            self.hot_src_loc = put(host["hot_src_loc"])
            self.hot_slot = put(host["hot_slot"])
            self.hot_valid = put(host["hot_valid"])
            self.hot_perm = jax.device_put(host["hot_perm"], repl1)
        else:
            self.hot_src_loc = self.hot_slot = None
            self.hot_valid = self.hot_perm = None

    def tree_flatten(self):
        return (tuple(getattr(self, a) for a in _PLAN_ARRAYS),
                (self.mesh,) + tuple(int(getattr(self, k))
                                     for k in _PLAN_META))

    @classmethod
    def tree_unflatten(cls, aux, children):
        plan = object.__new__(cls)
        plan.mesh = aux[0]
        for k, v in zip(_PLAN_META, aux[1:]):
            setattr(plan, k, v)
        for a, v in zip(_PLAN_ARRAYS, children):
            setattr(plan, a, v)
        plan.hot_ids, plan.stats = None, {}
        return plan

    # -- bind-time accounting: per-device bytes -------------------------
    def table_bytes_per_device(self, d: int, itemsize: int = 4) -> int:
        """Resident gather-source bytes per device: the local row block
        plus the replicated hot cache — n·d/S + C·d, NOT n·d."""
        return (self.n_loc + self.C) * d * itemsize

    def remote_bytes_per_call(self, d: int, itemsize: int = 4) -> int:
        """Bytes received per device per aggregation call (compacted
        serve all_gather + hot-cache fill)."""
        return self.stats["remote_rows_per_call"] * d * itemsize


def build_featshard_plan(idx, w, degrees, mesh,
                         cache_rows: int = -1) -> FeatShardPlan:
    """Build the static featshard plan from HOST ELL arrays (already
    padded to a shard-count multiple of rows — ``ShardedFullGraphSource``
    pads at bind) and per-node degrees."""
    from repro import sharding as sh
    host = _plan_arrays(idx, w, degrees, sh.nodes_shards(mesh), cache_rows)
    return FeatShardPlan(mesh, host)


# ---------------------------------------------------------------------------
# The two-phase op (shard_map + manual custom VJP)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_op(mesh, has_miss: bool, C: int, static, fused: bool):
    """The custom-VJP op for one plan layout.  The plan's index arrays
    are its last operand, so they enter every jitted caller as
    arguments; their cotangent is None."""
    from repro import sharding as sh
    from repro.kernels.neighbor_agg.ops import _tiled_call, _tiled_grads

    ax = sh.nodes_axis(mesh)
    row2, row1, repl1 = P(ax, None), P(ax), P(None)
    has_hot = C > 0

    aux_specs = (row2,)
    if has_miss:
        aux_specs += (row2, row2, row2)
    if has_hot:
        aux_specs += (row2, repl1)
    # the VJP additionally needs the hot scatter-back maps
    baux_specs = aux_specs + ((row2, row2) if has_hot else ())

    def _aux(arrs, with_back):
        a = dict(zip(_PLAN_ARRAYS, arrs))
        out = (a["lidx_hot"],)
        if has_miss:
            out += (a["hot_mask"], a["lidx_miss"], a["serve_loc"])
        if has_hot:
            out += (a["hot_src_loc"], a["hot_perm"])
            if with_back:
                out += (a["hot_slot"], a["hot_valid"])
        return out

    def _unpack(rest, with_back):
        it = iter(rest)
        lh = next(it)
        hm = lm = sl = None
        if has_miss:
            hm, lm, sl = next(it), next(it), next(it)
        hsrc = hperm = hslot = hvalid = None
        if has_hot:
            hsrc, hperm = next(it), next(it)
            if with_back:
                hslot, hvalid = next(it), next(it)
        return lh, hm, lm, sl, hsrc, hperm, hslot, hvalid

    def _hot_table(f, hsrc, hperm):
        """Rebuild the [C, d] hot cache from the sharded table: each
        shard contributes its owned hot rows, one small all_gather of
        [S·C_max, d] owner-major parts, then the static slot permutation.
        Values refresh per call (layer tables change); the ID set is
        fixed per bind."""
        parts = jnp.take(f, hsrc[0], axis=0)              # [C_max, d]
        gathered = jax.lax.all_gather(parts, ax, tiled=True)
        return jnp.take(gathered, hperm, axis=0)          # [C, d]

    def _serve_gather(f, sl):
        """Compacted cold-miss move: each shard serves its [M] requested
        local rows, one all_gather -> the owner-major [S·M, d] buffer
        phase 2 gathers from."""
        serve = jnp.take(f, sl[0], axis=0)                # [M, d]
        return jax.lax.all_gather(serve, ax, tiled=True)  # [S·M, d]

    def _local_fwd(f, ww, sr, ws, lh, hm, lm, sl, hsrc, hperm):
        # the serve gather is issued FIRST and depends only on the local
        # block, so XLA overlaps the collective with the phase-1 Pallas
        # aggregation over hot/local rows
        gathered = _serve_gather(f, sl) if has_miss else None
        table1 = (jnp.concatenate([_hot_table(f, hsrc, hperm), f], 0)
                  if has_hot else f)
        w1 = ww * hm.astype(ww.dtype) if has_miss else ww
        out = _tiled_call(table1, lh, w1, sr, ws, static)
        if has_miss:
            # phase 2 accumulates the cold rows into the SAME output
            # through the fused epilogue (accumulator init = the phase-1
            # partial, w_self = 1)
            w2 = ww * (1.0 - hm).astype(ww.dtype)
            ones = jnp.ones((out.shape[0],), ww.dtype)
            out = _tiled_call(gathered, lm, w2, out, ones, static)
        return out

    def _fwd(feats, w, self_rows, w_self, arrs):
        aux = _aux(arrs, False)
        ops_in = (feats, w) + ((self_rows, w_self) if fused else ())
        specs = (row2, row2) + ((row2, row1) if fused else ())

        def local(f, ww, *rest):
            rest = list(rest)
            sr = rest.pop(0) if fused else None
            ws = rest.pop(0) if fused else None
            lh, hm, lm, sl, hsrc, hperm, _, _ = _unpack(rest, False)
            return _local_fwd(f, ww, sr, ws, lh, hm, lm, sl, hsrc, hperm)

        return sh.shard_map(local, mesh, specs + aux_specs,
                            row2)(*ops_in, *aux)

    def _bwd(feats, w, self_rows, w_self, arrs, g):
        ops_in = ((feats, w) + ((self_rows, w_self) if fused else ())
                  + _aux(arrs, True) + (g,))
        specs = ((row2, row2) + ((row2, row1) if fused else ())
                 + baux_specs + (row2,))
        out_specs = (row2, row2) + ((row2, row1) if fused else ())

        def local(f, ww, *rest):
            rest = list(rest)
            sr = rest.pop(0) if fused else None
            ws = rest.pop(0) if fused else None
            gg = rest.pop()                  # g is the LAST operand
            lh, hm, lm, sl, hsrc, hperm, hslot, hvalid = \
                _unpack(rest, True)
            table1 = (jnp.concatenate([_hot_table(f, hsrc, hperm), f], 0)
                      if has_hot else f)
            w1 = ww * hm.astype(ww.dtype) if has_miss else ww
            # phase 2's cotangent into the phase-1 partial is exactly g
            # (w_self = 1), so phase 1 backpropagates g directly
            df1, dw1, dsr, dws = _tiled_grads(static, table1, lh, w1,
                                              sr, ws, gg)
            dloc = df1[C:] if has_hot else df1
            dw = dw1
            if has_miss:
                gathered = _serve_gather(f, sl)
                w2 = ww * (1.0 - hm).astype(ww.dtype)
                dgath, dw2, _, _ = _tiled_grads(static, gathered, lm, w2,
                                                None, None, gg)
                dw = jnp.where(hm > 0, dw1, dw2)
                # scatter-add the cold-row grads back to OWNER shards:
                # psum_scatter hands each shard its [M, d] serve slice
                # summed across requesters — never an [n, d] psum
                dserve = jax.lax.psum_scatter(dgath, ax,
                                              scatter_dimension=0,
                                              tiled=True)
                dloc = dloc.at[sl[0]].add(dserve.astype(dloc.dtype))
            if has_hot:
                # only the C hot rows cross every shard
                dhot = jax.lax.psum(df1[:C], ax)
                back = (jnp.take(dhot, hslot[0], axis=0)
                        * hvalid[0][:, None])
                dloc = dloc.at[hsrc[0]].add(back.astype(dloc.dtype))
            return (dloc, dw) + ((dsr, dws) if fused else ())

        return sh.shard_map(local, mesh, specs, out_specs)(*ops_in)

    @jax.custom_vjp
    def op(feats, w, self_rows, w_self, arrs):
        return _fwd(feats, w, self_rows, w_self, arrs)

    def op_fwd(feats, w, self_rows, w_self, arrs):
        return (_fwd(feats, w, self_rows, w_self, arrs),
                (feats, w, self_rows, w_self, arrs))

    def op_bwd(res, g):
        grads = tuple(_bwd(*res, g))
        return (grads if fused else grads + (None, None)) + (None,)

    op.defvjp(op_fwd, op_bwd)
    return op


def neighbor_agg_featshard(feats, w, plan: FeatShardPlan, self_rows=None,
                           w_self=None, *, d_tile: int = 128,
                           b_tile: int = 8, k_slab: int = 4):
    """``out[b] = Σ_k w[b,k]·feats[idx[b,k]] [+ w_self[b]·self_rows[b]]``
    with the SOURCE TABLE row-sharded over the plan's NODES mesh (no
    replicated [n, d] copy anywhere): phase-1 tiled Pallas aggregation
    over hot-cache/local hits overlapped with the compacted cold-miss
    ``all_gather``, phase-2 accumulation of the cold rows into the same
    output, and a scatter-add (not psum-of-replicated) VJP.

    ``feats`` [n_pad, d] and optional ``self_rows`` [n_pad, d] are
    NODES-row-sharded; ``w`` [n_pad, K] / ``w_self`` [n_pad] row-sharded
    with the SAME zero pattern the plan was built from (the plan encodes
    the index remap, so ``ell_idx`` itself is not an operand).  Output
    rows stay NODES-sharded — layer l's output table feeds layer l+1
    without a relayout.  On a 1-device mesh this is bit-identical to
    ``neighbor_agg(..., kernel="tiled")``, forward and gradients."""
    fused = self_rows is not None
    assert fused == (w_self is not None), \
        "self_rows and w_self must be passed together"
    if feats.shape[0] != plan.n_pad or w.shape != (plan.n_pad, plan.K):
        raise ValueError(
            f"neighbor_agg_featshard: operands (feats {feats.shape}, "
            f"w {w.shape}) do not match the plan "
            f"(n_pad={plan.n_pad}, K={plan.K}) — rebuild the plan for "
            f"this ELL/mesh")
    static = ("tiled", int(d_tile), int(b_tile), int(k_slab))
    arrs = tuple(getattr(plan, a) for a in _PLAN_ARRAYS)
    op = _make_op(plan.mesh, plan.M > 0, int(plan.C), static, fused)
    return op(feats, w, self_rows, w_self, arrs)
