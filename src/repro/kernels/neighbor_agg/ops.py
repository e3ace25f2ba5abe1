"""jit'd public wrapper for the neighbor-aggregation kernels.

Handles B/K/D padding to the kernel tile shape, dtype plumbing, the
kernel / pure-jnp dispatch, and a custom VJP so BOTH training paths
(full-graph GD and mini-batch SGD) can differentiate through the
kernel.  The kernel compiles with Mosaic on a TPU backend and runs in
the Pallas interpreter on any other (``repro.kernels.default_interpret``;
no caller chooses).  The gradients:

    d/dfeats = scatter-add of w[b,k] * g[b]   (segment-sum over idx)
    d/dw     = <g[b], feats[idx[b,k]]>

Padding is with zero-weight edges pointing at row 0, which the kernels
treat exactly (0 * row == 0).

Mesh-partitioned entry points (kernels/README.md "Sharding"):
``neighbor_agg_sharded`` runs the tiled kernel shard-locally over the
NODES mesh axis via shard_map — output rows / ids / weights sharded,
the feature table replicated so the software gather never crosses a
shard — with the custom VJP extended to psum-reduce ``dfeats`` across
shards; ``neighbor_agg_batch_sharded`` is the mini-batch twin over an
already-gathered fan-out level, where the flattened table itself is
row-sharded and NO collective is needed in either direction."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.neighbor_agg.neighbor_agg import (
    lanes_per_row, neighbor_agg_pallas, neighbor_agg_pallas_tiled)
from repro.kernels.neighbor_agg.ref import neighbor_agg_ref


def kernel_cols(d: int, dtype, d_tile: int) -> int:
    """Columns the tiled kernel's D pads to.  It gathers rows of 32-bit
    words (an f32 column or two bf16 columns per word): a row of at most
    half a lane tile pads to exactly half a tile, which the kernel pairs
    two rows per tile; a wider row pads to whole tiles."""
    lanes = lanes_per_row(dtype)
    words = -(-d // lanes)
    if d_tile % 2 == 0 and 2 * words <= d_tile:
        return d_tile // 2 * lanes
    return -(-words // d_tile) * d_tile * lanes


def _kernel_width(feats, static) -> int:
    """Columns the kernel's D pads to (``kernel_cols`` for the tiled
    kernel, whole ``d_tile`` blocks for the row kernel)."""
    kernel, d_tile = static[:2]
    d = feats.shape[1]
    if kernel == "row":
        return -(-d // d_tile) * d_tile
    return kernel_cols(d, feats.dtype, d_tile)


def _pad_cols(x, width):
    return x if x.shape[1] == width else jnp.pad(
        x, ((0, 0), (0, width - x.shape[1])))


def _run_kernel(feats, idx, w, static):
    # D pads here, inside the custom VJP, so its residuals and backward
    # work at the true width (padding bf16 172 -> 256 columns would
    # inflate every backward buffer by half)
    kernel, d_tile, b_tile, k_slab = static
    d = feats.shape[1]
    feats_p = _pad_cols(feats, _kernel_width(feats, static))
    if kernel == "row":
        out = neighbor_agg_pallas(feats_p, idx, w, d_tile=d_tile)
    else:
        out = neighbor_agg_pallas_tiled(feats_p, idx, w, b_tile=b_tile,
                                        d_tile=d_tile, k_slab=k_slab)
    return out[:, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _agg(feats, idx, w, static):
    return _run_kernel(feats, idx, w, static)


def _agg_fwd(feats, idx, w, static):
    return _run_kernel(feats, idx, w, static), (feats, idx, w)


def _agg_bwd(static, res, g):
    # scan over the K axis so the backward's peak memory is O(N*D + B*D),
    # matching the forward kernel's no-[B,K,D]-blowup property instead of
    # materializing the full gather it exists to avoid
    feats, idx, w = res
    g32 = g.astype(jnp.float32)                       # [B, D]

    def body(dfeats, xs):
        idx_k, w_k = xs                               # [B], [B]
        rows = jnp.take(feats, idx_k, axis=0).astype(jnp.float32)
        dw_k = jnp.einsum("bd,bd->b", g32, rows)
        dfeats = dfeats.at[idx_k].add(
            w_k.astype(jnp.float32)[:, None] * g32)
        return dfeats, dw_k

    dfeats, dw_t = jax.lax.scan(
        body, jnp.zeros(feats.shape, jnp.float32), (idx.T, w.T))
    dfeats = dfeats.astype(feats.dtype)
    dw = dw_t.T.astype(w.dtype)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dfeats, didx, dw


_agg.defvjp(_agg_fwd, _agg_bwd)


# -- fused self-weight epilogue variant -------------------------------------
# out[b] = Σ_k w[b,k]·feats[idx[b,k]] + w_self[b]·self_rows[b] in ONE kernel
# (the epilogue folds into the accumulator init; see neighbor_agg.py)

def _run_kernel_fused(feats, idx, w, self_rows, w_self, static):
    _, d_tile, b_tile, k_slab = static
    d = feats.shape[1]
    width = _kernel_width(feats, static)
    out = neighbor_agg_pallas_tiled(
        _pad_cols(feats, width), idx, w,
        self_rows=_pad_cols(self_rows, width), w_self=w_self,
        b_tile=b_tile, d_tile=d_tile, k_slab=k_slab)
    return out[:, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _agg_self(feats, idx, w, self_rows, w_self, static):
    return _run_kernel_fused(feats, idx, w, self_rows, w_self, static)


def _agg_self_fwd(feats, idx, w, self_rows, w_self, static):
    return (_run_kernel_fused(feats, idx, w, self_rows, w_self, static),
            (feats, idx, w, self_rows, w_self))


def _agg_self_bwd(static, res, g):
    feats, idx, w, self_rows, w_self = res
    dfeats, didx, dw = _agg_bwd(static, (feats, idx, w), g)
    g32 = g.astype(jnp.float32)
    dself = (w_self.astype(jnp.float32)[:, None] * g32
             ).astype(self_rows.dtype)
    dwself = jnp.einsum("bd,bd->b", g32, self_rows.astype(jnp.float32)
                        ).astype(w_self.dtype)
    return dfeats, didx, dw, dself, dwself


_agg_self.defvjp(_agg_self_fwd, _agg_self_bwd)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _tiled_call(feats, idx, w, self_rows, w_self, static):
    """Tile-pad + tiled-kernel dispatch, shared by the jit wrapper below
    and the shard-local bodies of the sharded entry points (the padding
    must be IDENTICAL in both so the sharded path stays bit-equal to the
    unsharded one on a 1-device mesh).  B and K pad here; D pads inside
    the kernel call (``_run_kernel``)."""
    _, d_tile, b_tile, k_slab = static
    b = idx.shape[0]
    idx_p = _pad_to(_pad_to(idx, 0, b_tile), 1, k_slab)
    w_p = _pad_to(_pad_to(w, 0, b_tile), 1, k_slab)
    if self_rows is not None:
        self_p = _pad_to(self_rows, 0, b_tile)
        wself_p = _pad_to(w_self, 0, b_tile)
        out = _agg_self(feats, idx_p, w_p, self_p, wself_p, static)
    else:
        out = _agg(feats, idx_p, w_p, static)
    return out[:b]


def _tiled_grads(static, feats, idx, w, self_rows, w_self, g):
    """Gradients of ``_tiled_call`` spelled out: the same pad ->
    ``_agg*_bwd`` -> slice composition jax's transpose machinery
    produces for the jit wrapper, so the shard-local backward of the
    sharded entry points is bit-identical to the unsharded kernel
    path's.  Returns ``(dfeats, dw, dself_rows, dw_self)`` (the last
    two ``None`` when not fused)."""
    _, d_tile, b_tile, k_slab = static
    b, k = idx.shape
    idx_p = _pad_to(_pad_to(idx, 0, b_tile), 1, k_slab)
    w_p = _pad_to(_pad_to(w, 0, b_tile), 1, k_slab)
    g_p = _pad_to(g, 0, b_tile)
    if self_rows is not None:
        self_p = _pad_to(self_rows, 0, b_tile)
        wself_p = _pad_to(w_self, 0, b_tile)
        df, _, dw, dself, dwself = _agg_self_bwd(
            static, (feats, idx_p, w_p, self_p, wself_p), g_p)
        return df, dw[:b, :k], dself[:b], dwself[:b]
    df, _, dw = _agg_bwd(static, (feats, idx_p, w_p), g_p)
    return df, dw[:b, :k], None, None


@functools.partial(jax.jit, static_argnames=("use_kernel", "kernel",
                                             "d_tile", "b_tile", "k_slab"))
def neighbor_agg(feats, idx, w, self_rows=None, w_self=None, *,
                 use_kernel: bool = False, kernel: str = "tiled",
                 d_tile: int = 128, b_tile: int = 8, k_slab: int = 4):
    """out[b] = Σ_k w[b,k] · feats[idx[b,k]]  [+ w_self[b] · self_rows[b]].

    feats [N, D]; idx [B, K] int32; w [B, K] (0 ⇒ padding edge);
    optional self_rows [B, D] + w_self [B] fuse the callers' self-loop
    epilogue into the tiled kernel's accumulator init (on the "row" /
    jnp dispatch paths the epilogue is applied outside the kernel).
    kernel: "tiled" (batch-tiled, double-buffered, production) | "row"
    (seed reference).  Differentiable wrt feats, w, self_rows and
    w_self in all dispatch modes.
    """
    assert kernel in ("row", "tiled"), kernel
    fused = self_rows is not None
    assert fused == (w_self is not None), \
        "self_rows and w_self must be passed together"
    if not use_kernel:
        out = neighbor_agg_ref(feats, idx, w)
        return out + w_self[:, None] * self_rows if fused else out
    static = (kernel, d_tile, b_tile, k_slab)
    if kernel == "row":
        out = _agg(feats, idx, w, static)
        return out + w_self[:, None] * self_rows if fused else out
    # padded rows carry w_self = 0, so the fused epilogue stays exact
    return _tiled_call(feats, idx, w, self_rows if fused else None,
                       w_self if fused else None, static)


# ---------------------------------------------------------------------------
# Mesh-partitioned entry points (shard_map over the NODES axis)
# ---------------------------------------------------------------------------
# The tiled kernel runs SHARD-LOCALLY: every shard owns a contiguous row
# block of the output / idx / w (+ self_rows / w_self) and gathers from a
# replicated feature table, so the forward needs no collective at all.
# Only the VJP's dfeats — a scatter-add into the REPLICATED table — must
# be psum-reduced across shards; dw / dself_rows / dw_self are row-local
# like their primals.  See kernels/README.md "Sharding".

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _agg_sharded(feats, idx, w, self_rows, w_self, sstatic):
    from repro import sharding as sh
    mesh, static = sstatic
    fused = self_rows is not None
    ins, row = sh.ell_agg_specs(mesh, fused)
    if fused:
        def local(f, i, ww, sr, ws):
            return _tiled_call(f, i, ww, sr, ws, static)
        return sh.shard_map(local, mesh, ins, row)(feats, idx, w,
                                                   self_rows, w_self)

    def local(f, i, ww):
        return _tiled_call(f, i, ww, None, None, static)
    return sh.shard_map(local, mesh, ins, row)(feats, idx, w)


def _agg_sharded_fwd(feats, idx, w, self_rows, w_self, sstatic):
    return (_agg_sharded(feats, idx, w, self_rows, w_self, sstatic),
            (feats, idx, w, self_rows, w_self))


def _agg_sharded_bwd(sstatic, res, g):
    from repro import sharding as sh
    mesh, static = sstatic
    feats, idx, w, self_rows, w_self = res
    fused = self_rows is not None
    ax = sh.nodes_axis(mesh)
    ins, row = sh.ell_agg_specs(mesh, fused)
    repl = ins[0]
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    if fused:
        def local(f, i, ww, sr, ws, gg):
            df, dw, dsr, dws = _tiled_grads(static, f, i, ww, sr, ws, gg)
            return jax.lax.psum(df, ax), dw, dsr, dws

        row1 = ins[4]                       # the w_self spec: P(NODES)
        df, dw, dsr, dws = sh.shard_map(
            local, mesh, ins + (row,), (repl, row, row, row1)
        )(feats, idx, w, self_rows, w_self, g)
        return df, didx, dw, dsr, dws

    def local(f, i, ww, gg):
        df, dw, _, _ = _tiled_grads(static, f, i, ww, None, None, gg)
        return jax.lax.psum(df, ax), dw

    df, dw = sh.shard_map(local, mesh, ins + (row,),
                          (repl, row))(feats, idx, w, g)
    return df, didx, dw, None, None


_agg_sharded.defvjp(_agg_sharded_fwd, _agg_sharded_bwd)


def neighbor_agg_sharded(feats, idx, w, self_rows=None, w_self=None, *,
                         mesh=None, use_kernel: bool = True,
                         d_tile: int = 128, b_tile: int = 8,
                         k_slab: int = 4):
    """``out[b] = Σ_k w[b,k]·feats[idx[b,k]] [+ w_self[b]·self_rows[b]]``
    partitioned over the NODES axis of ``mesh``: output rows / ``idx`` /
    ``w`` / ``self_rows`` / ``w_self`` shard their leading axis, the
    feature table replicates (the per-shard software gather is then
    purely local).  Rows pad internally up to a shard-count multiple
    with zero-weight edges, so any B is legal.

    On a 1-device mesh this is bit-identical to
    ``neighbor_agg(..., kernel="tiled")`` — forward AND gradients (the
    shard-local VJP mirrors the unsharded one exactly; the dfeats psum
    is an identity there).  ``mesh=None`` or ``use_kernel=False``
    dispatch straight to ``neighbor_agg`` (einsum path partitioning is
    GSPMD's job, not shard_map's)."""
    fused = self_rows is not None
    assert fused == (w_self is not None), \
        "self_rows and w_self must be passed together"
    if mesh is None or not use_kernel:
        return neighbor_agg(feats, idx, w, self_rows, w_self,
                            use_kernel=use_kernel, kernel="tiled",
                            d_tile=d_tile, b_tile=b_tile, k_slab=k_slab)
    from repro import sharding as sh
    b = idx.shape[0]
    n_sh = sh.nodes_shards(mesh)
    idx = _pad_to(idx, 0, n_sh)
    w = _pad_to(w, 0, n_sh)
    if fused:
        self_rows = _pad_to(self_rows, 0, n_sh)
        w_self = _pad_to(w_self, 0, n_sh)
    static = ("tiled", d_tile, b_tile, k_slab)
    out = _agg_sharded(feats, idx, w, self_rows, w_self, (mesh, static))
    return out[:b] if out.shape[0] != b else out


# -- already-gathered (mini-batch fan-out) variant --------------------------
# The flattened [B*K, D] table is DERIVED from the row-sharded h_nb, so
# table rows live on the same shard as the output rows they feed: both
# the forward and the VJP are collective-free.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _agg_batch_sharded(w, h_nb, h_self, w_self, sstatic):
    from repro import sharding as sh
    mesh, static = sstatic
    fused = h_self is not None
    ax = sh.nodes_axis(mesh)
    from jax.sharding import PartitionSpec as P

    def row(nd):
        return P(*((ax,) + (None,) * (nd - 1)))

    def local(ww, nb, *rest):
        bl, k = ww.shape
        d = nb.shape[-1]
        table = nb.reshape(bl * k, d)
        ids = jnp.arange(bl * k, dtype=jnp.int32).reshape(bl, k)
        sr, ws = rest if rest else (None, None)
        return _tiled_call(table, ids, ww, sr, ws, static)

    ops = (w, h_nb) + ((h_self, w_self) if fused else ())
    ins = tuple(row(o.ndim) for o in ops)
    return sh.shard_map(local, mesh, ins, row(2))(*ops)


def _agg_batch_sharded_fwd(w, h_nb, h_self, w_self, sstatic):
    return (_agg_batch_sharded(w, h_nb, h_self, w_self, sstatic),
            (w, h_nb, h_self, w_self))


def _agg_batch_sharded_bwd(sstatic, res, g):
    from repro import sharding as sh
    mesh, static = sstatic
    w, h_nb, h_self, w_self = res
    fused = h_self is not None
    ax = sh.nodes_axis(mesh)
    from jax.sharding import PartitionSpec as P

    def row(nd):
        return P(*((ax,) + (None,) * (nd - 1)))

    def local(ww, nb, *rest):
        *sr_ws, gg = rest
        bl, k = ww.shape
        d = nb.shape[-1]
        table = nb.reshape(bl * k, d)
        ids = jnp.arange(bl * k, dtype=jnp.int32).reshape(bl, k)
        sr, ws = sr_ws if sr_ws else (None, None)
        df, dw, dsr, dws = _tiled_grads(static, table, ids, ww, sr, ws, gg)
        dnb = df.reshape(nb.shape)
        return (dw, dnb) + ((dsr, dws) if fused else ())

    ops = (w, h_nb) + ((h_self, w_self) if fused else ()) + (g,)
    ins = tuple(row(o.ndim) for o in ops)
    outs = (row(2), row(h_nb.ndim)) + ((row(2), row(1)) if fused else ())
    grads = sh.shard_map(local, mesh, ins, outs)(*ops)
    return tuple(grads) if fused else tuple(grads) + (None, None)


_agg_batch_sharded.defvjp(_agg_batch_sharded_fwd, _agg_batch_sharded_bwd)


# -- NODES-sharded feature table + degree-ordered hot cache -----------------
# The out-of-core entry point: no replicated [n, d] table anywhere.  Kept
# in its own module (featshard.py); re-exported here so callers keep one
# import surface for every neighbor-agg front-end.
from repro.kernels.neighbor_agg.featshard import (  # noqa: E402
    FeatShardPlan, build_featshard_plan, neighbor_agg_featshard,
    resolve_cache_rows)


def neighbor_agg_batch_sharded(w, h_nb, h_self=None, w_self=None, *, mesh,
                               d_tile: int = 128, b_tile: int = 8,
                               k_slab: int = 4):
    """Tiled-kernel weighted sum over an ALREADY-GATHERED fan-out level
    (``h_nb [B, K, D]``, ``w [B, K]`` [+ fused ``h_self [B, D]`` /
    ``w_self [B]``]) with the target rows sharded over NODES: each shard
    flattens its local block to a ``[b_loc*K, D]`` table with identity
    ids and runs the same tiled kernel the unsharded mini-batch path
    uses — no collective in the forward or the VJP.  B must divide by
    the NODES shard count (the sharded mini-batch source rounds its
    batch up at bind, and fan-out products keep every level
    divisible)."""
    fused = h_self is not None
    assert fused == (w_self is not None), \
        "h_self and w_self must be passed together"
    from repro import sharding as sh
    n_sh = sh.nodes_shards(mesh)
    if w.shape[0] % n_sh:
        raise ValueError(
            f"neighbor_agg_batch_sharded: B={w.shape[0]} must be a "
            f"multiple of the {n_sh} NODES shards (the sharded sources "
            f"round b up to a mesh multiple at bind)")
    static = ("tiled", d_tile, b_tile, k_slab)
    return _agg_batch_sharded(w, h_nb, h_self, w_self, (mesh, static))
