"""Pallas TPU kernels: weighted neighbor aggregation (software gather).

TPU adaptation of the GNN gather hot-spot (DESIGN.md §3): TPUs have no
hardware gather from HBM, so the neighbor ids are read from SMEM as
scalars and drive per-row DMAs — each grid step moves exactly the
feature rows it needs HBM->VMEM and accumulates

    out[b, d_tile] += w[b, k] * feats[idx[b, k], d_tile]

into a revisited output block (grid order puts k innermost so the output
tile stays resident in VMEM across the K accumulation steps).

Two variants:

* `neighbor_agg_pallas` — the seed row kernel: one (1, d_tile) feature
  row per grid step, grid (B, D // d_tile, K).  An interpret-mode
  reference shape only: its flat scalar-prefetched ids and (1, 1)
  weight blocks do not compile for a TPU at real sizes.

* `neighbor_agg_pallas_tiled` — batch-tiled AND pipelined: each grid
  step owns a (b_tile, d_tile) OUTPUT block and a K-slab of k_slab
  neighbors, grid (B // b_tile, T, K // k_slab) with T lane tiles of
  32-bit words per row.  The b_tile * k_slab row DMAs of a slab are
  issued together (overlapped in hardware), the row block's (b_tile, K)
  weights and ids are loaded once per row block instead of once per
  (row, k) pair, and the accumulator tile amortizes its init/flush over
  b_tile rows.  The operand layout that the TPU compiler accepts is
  described at ``neighbor_agg_pallas_tiled`` and in kernels/README.md.
  Zero-weight padding rows DMA like any other row but contribute
  exactly 0, so masked/padded inputs stay exact.

  Slab DMAs are DOUBLE-BUFFERED across the (innermost, sequential) K
  grid axis: the row buffer and its DMA semaphores carry a leading
  2-slot axis, slab ki lives in slot ki % 2, and while step ki
  accumulates its slab the DMAs for slab ki + 1 are already in flight
  into the other slot (flash_attn-style block pipelining).  Only the
  FIRST slab of each (bi, di) output tile is an exposed wait; every
  other slab's HBM latency hides behind the previous slab's FMAs.

  Optional fused epilogue: with `self_rows`/`w_self` the accumulator
  initializes to w_self[b] * self_rows[b, :] instead of zeros, so the
  callers' separate `w_self * h_self` elementwise pass (and its extra
  output-sized HBM round trip) disappears; a bias row would fold into
  the same init.

VMEM working set per tiled step (32-bit words):
rows (2, k_slab, b_tile, d_tile) + acc (1 or 2, b_tile, d_tile) +
weights (b_tile, K) [+ self tile (b_tile, d_tile) + w_self (b_tile, 1)]
— keep it under ~2 MB (analysis/pallas_audit.py gates it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


# ---------------------------------------------------------------------------
# seed row kernel: one feature row tile per grid step
# ---------------------------------------------------------------------------

def _row_kernel(idx_ref, w_ref, feat_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    weight = w_ref[0, 0].astype(jnp.float32)
    row = feat_ref[...].astype(jnp.float32)
    acc_ref[...] += weight * row

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def neighbor_agg_pallas(feats, idx, w, *, d_tile: int = 128,
                        interpret=None):
    """feats [N, D]; idx [B, K] int32; w [B, K].  Returns [B, D].

    Interpret-mode reference only: its flat scalar-prefetched ids and
    (1, 1) weight blocks do not compile for a TPU at real sizes (the
    tiled kernel below is the one every path uses).
    D must be a multiple of d_tile (ops.py pads).
    """
    interpret = resolve_interpret(interpret)
    n, d = feats.shape
    b, k = idx.shape
    assert d % d_tile == 0, (d, d_tile)
    grid = (b, d // d_tile, k)

    flat_idx = idx.reshape(-1)               # scalar-prefetch operand

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # w[b, k] as a (1, 1) block
            pl.BlockSpec((1, 1), lambda bi, di, ki, idx_p: (bi, ki)),
            # the gathered feature row tile — index_map reads the
            # scalar-prefetched neighbor id
            pl.BlockSpec((1, d_tile),
                         lambda bi, di, ki, idx_p: (idx_p[bi * k + ki], di)),
        ],
        out_specs=pl.BlockSpec((1, d_tile),
                               lambda bi, di, ki, idx_p: (bi, di)),
        scratch_shapes=[pltpu.VMEM((1, d_tile), jnp.float32)],
    )
    fn = pl.pallas_call(
        _row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), feats.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )
    return fn(flat_idx, w, feats)


# ---------------------------------------------------------------------------
# batch-tiled kernel: (b_tile, lanes) output block, K-slab per step
# ---------------------------------------------------------------------------

_HI16 = np.uint32(0xFFFF0000)


def _unpack(x, packed: bool):
    """32-bit row words -> f32 parts.  f32 rows are one part; packed bf16
    rows (two bf16 per uint32 word) split into the low and high halves,
    each widened exactly to f32 by placing its bits in the top 16."""
    if not packed:
        return (x.astype(jnp.float32),)
    lo = jax.lax.bitcast_convert_type(x << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(x & _HI16, jnp.float32)
    return lo, hi


def _bf16_bits(x):
    """f32 -> its round-to-nearest-even bf16 bits in the low 16 of a
    uint32 (NaN stays NaN)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    r = (u + 0x7FFF + ((u >> 16) & 1)) >> 16
    return jnp.where(x != x, np.uint32(0x7FC0), r)


def _pack(parts, packed: bool, dtype):
    if not packed:
        return parts[0].astype(dtype)
    lo, hi = parts
    return (_bf16_bits(hi) << 16) | _bf16_bits(lo)


def _make_tiled_kernel(b_tile: int, n_tiles: int, k_slab: int,
                       packed: bool, fuse_self: bool, paired: bool = False):
    n_parts = 2 if packed else 1

    def kernel(idx_ref, w_ref, *refs):
        refs = list(refs)
        par_ref = refs.pop(0) if paired else None
        if fuse_self:
            wself_ref, self_ref, feat_ref, out_ref, rows_ref, acc_ref, \
                sems = refs
        else:
            feat_ref, out_ref, rows_ref, acc_ref, sems = refs
        ti = pl.program_id(1)
        ki = pl.program_id(2)
        nk = pl.num_programs(2)

        def slab_copies(slab, slot):
            """The b_tile * k_slab row DMAs of K-slab `slab` into
            double-buffer slot `slot` (software gather: the ids of this
            row block, staged in SMEM, address HBM rows directly)."""
            copies = []
            for j in range(k_slab):
                for i in range(b_tile):
                    nid = idx_ref[i, slab * k_slab + j]
                    # paired: row nid is one half of word row nid // 2
                    src = nid // 2 if paired else nid * n_tiles + ti
                    copies.append(pltpu.make_async_copy(
                        feat_ref.at[src],
                        rows_ref.at[slot, j, i],
                        sems.at[slot, j, i]))
            return copies

        # two-slot rotation: slab s lives in slot s % 2.  The first slab
        # of each output tile is started here (exposed wait); every later
        # slab was prefetched by the PREVIOUS step and is already in
        # flight while that step accumulated.
        @pl.when(ki == 0)
        def _init():
            for c in slab_copies(0, 0):
                c.start()
            if fuse_self:    # fused epilogue: acc starts at w_self * self
                ws = wself_ref[...]
                for p, part in enumerate(_unpack(self_ref[...], packed)):
                    acc_ref[p] = ws * part
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ki + 1 < nk)
        def _prefetch_next():
            for c in slab_copies(ki + 1, (ki + 1) % 2):
                c.start()

        for c in slab_copies(ki, ki % 2):
            c.wait()

        # the row block's (b_tile, K) f32 weights; slab ki's columns sit at
        # a grid-dependent lane offset, so each is picked by an exact
        # one-hot select over the whole block
        w_blk = w_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, w_blk.shape, 1)
        slot = ki % 2

        def pick(blk, j):
            return jnp.sum(jnp.where(col == ki * k_slab + j, blk, 0.0),
                           axis=1, keepdims=True)

        for j in range(k_slab):
            wj = pick(w_blk, j)
            parts = _unpack(rows_ref[slot, j], packed)
            if paired:
                # an odd id's row is the upper half of the word row: a
                # half-tile lane rotation brings it to the lower half
                odd = pick(par_ref[...], j) > 0.5
                half = rows_ref.shape[-1] // 2
                parts = [jnp.where(odd, pltpu.roll(x, half, 1), x)[:, :half]
                         for x in parts]
            for p, part in enumerate(parts):
                acc_ref[p] += wj * part

        @pl.when(ki == nk - 1)
        def _flush():
            out_ref[...] = _pack([acc_ref[p] for p in range(n_parts)],
                                 packed, out_ref.dtype)

    return kernel


def _words(x):
    """[M, D] bf16 -> [M, D/2] uint32 (two bf16 per word).  A bf16 table
    in HBM packs row PAIRS into its 32-bit tiles, so a single-row DMA of
    it is not addressable; the word view makes every row its own."""
    m, d = x.shape
    return jax.lax.bitcast_convert_type(x.reshape(m, d // 2, 2), jnp.uint32)


def _unwords(x):
    m, w = x.shape
    return jax.lax.bitcast_convert_type(x, jnp.bfloat16).reshape(m, 2 * w)


def lanes_per_row(dtype) -> int:
    """Feature columns carried by one 32-bit lane of the gathered rows."""
    return 2 if jnp.dtype(dtype) == jnp.bfloat16 else 1


def neighbor_agg_pallas_tiled(feats, idx, w, *, self_rows=None, w_self=None,
                              b_tile: int = 8, d_tile: int = 128,
                              k_slab: int = 4, interpret=None):
    """Batch-tiled, double-buffered software gather: feats [N, D] (f32 or
    bf16); idx [B, K] int32; w [B, K] (0 ⇒ padding edge, exact).
    Returns [B, D] in feats.dtype, accumulated in f32.

    With `self_rows` [B, D] + `w_self` [B] the epilogue
    out[b] += w_self[b] * self_rows[b] is fused into the accumulator
    init (both must be given together).

    `d_tile` counts 32-bit LANES per grid step: an f32 lane is one
    feature column, a bf16 lane two (bf16 rows are gathered as uint32
    words).  A row of W = D / lanes_per_row(feats.dtype) words must be
    either half a lane tile (W == d_tile / 2, "paired") or a multiple
    of d_tile; B must be a multiple of b_tile and K of k_slab (ops.py
    pads all three; padded rows/edges carry zero weight).
    interpret=None compiles for a TPU backend and interprets on any
    other.

    Operand layout, chosen so the TPU compiler accepts every block and
    DMA for any B, K and D (it needs d_tile == 128 there):
    * ids go to SMEM one (b_tile, K) row block per grid row, so SMEM use
      does not grow with B·K;
    * weights are cast to f32 and blocked (b_tile, K), a block spanning
      the whole minor dim (a (b_tile, k_slab) block is refused for
      K > k_slab, and a [K/k_slab, B, k_slab] re-layout pads its
      k_slab-wide minor dim to 128 lanes in HBM);
    * the table is viewed as [N·T, d_tile] 32-bit words (T lane tiles
      per row): a single-row DMA is only addressable when the source
      row is exactly one lane tile, and bf16 rows would otherwise share
      their 32-bit words with the neighbouring row;
    * a half-tile row (a 128-column bf16 row is 64 words) is PAIRED:
      rows 2m and 2m+1 share word row m of an [N/2, d_tile] view, the
      DMA fetches that word row, and a lane rotation picked by the id's
      parity (a (b_tile, K) block like the weights) selects the half.
      Padding such a row to a whole tile instead would copy the table
      at twice its size on every call.
    """
    interpret = resolve_interpret(interpret)
    n, d = feats.shape
    b, k = idx.shape
    dt = jnp.dtype(feats.dtype)
    assert dt in (jnp.float32, jnp.bfloat16), dt
    packed = dt == jnp.bfloat16
    words_per_row = d // lanes_per_row(dt)
    assert d % lanes_per_row(dt) == 0, (d, dt)
    paired = 2 * words_per_row == d_tile
    assert paired or words_per_row % d_tile == 0, (d, d_tile, dt)
    assert b % b_tile == 0, (b, b_tile)
    assert k % k_slab == 0, (k, k_slab)
    fuse_self = self_rows is not None
    assert fuse_self == (w_self is not None), \
        "self_rows and w_self must be passed together"
    n_tiles = 1 if paired else words_per_row // d_tile
    out_lanes = words_per_row if paired else d_tile
    nk = k // k_slab
    words = _words if packed else (lambda x: x)
    table = words(feats)
    if paired:
        if n % 2:                        # an even row count pairs up
            table = jnp.pad(table, ((0, 1), (0, 0)))
        table = table.reshape(-1, d_tile)
    else:
        table = table.reshape(n * n_tiles, d_tile)

    in_specs = [
        # this row block's ids, in SMEM (scalar reads address the DMAs)
        pl.BlockSpec((b_tile, k), lambda bi, ti, ki: (bi, 0),
                     memory_space=pltpu.SMEM),
        # the row block's weights, resident across its K steps
        pl.BlockSpec((b_tile, k), lambda bi, ti, ki: (bi, 0)),
    ]
    operands = [idx, w.astype(jnp.float32)]
    if paired:
        # each id's parity: which half of its word row holds the row
        in_specs.append(pl.BlockSpec((b_tile, k), lambda bi, ti, ki: (bi, 0)))
        operands.append((idx % 2).astype(jnp.float32))
    if fuse_self:
        in_specs += [
            # w_self as a (b_tile, 1) column, self rows as the same
            # (b_tile, out_lanes) block shape as the output tile
            pl.BlockSpec((b_tile, 1), lambda bi, ti, ki: (bi, 0)),
            pl.BlockSpec((b_tile, out_lanes), lambda bi, ti, ki: (bi, ti)),
        ]
        operands += [w_self.astype(jnp.float32).reshape(b, 1),
                     words(self_rows)]
    # full feature table stays in HBM; rows are DMA'd manually
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(table)

    fn = pl.pallas_call(
        _make_tiled_kernel(b_tile, n_tiles, k_slab, packed, fuse_self,
                           paired),
        grid=(b // b_tile, n_tiles, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b_tile, out_lanes),
                               lambda bi, ti, ki: (bi, ti)),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * out_lanes),
                                       table.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, k_slab, b_tile, d_tile), table.dtype),
            pltpu.VMEM((2 if packed else 1, b_tile, out_lanes),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((2, k_slab, b_tile)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="neighbor_agg_tiled",
    )
    out = fn(*operands)
    return _unwords(out) if packed else out
