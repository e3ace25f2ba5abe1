"""Neighbor-aggregation kernel micro-bench: jnp oracle vs Pallas row
kernel vs batch-tiled kernel (interpret mode on CPU — correctness +
working-set accounting; wall time is NOT a TPU number, the derived
bytes/flops are hardware-independent).

bytes accounting (fix for the seed formula, which charged one row-DMA
plus 4+4 id/weight bytes per (b, k) pair regardless of tiling):

* feature rows: every kernel moves b*k*d*itemsize feature bytes HBM->VMEM
  (one row tile per (b, k, d_tile) triple — gathers don't dedupe).
* ids: scalar-prefetched ONCE per call (b*k*4), both kernels.
* weights: re-fetched per d-tile pass.  The row kernel issues a (1, 1)
  block load per (b, d_tile, k) step — HBM reads have a minimum DMA
  granularity, so each scalar load costs a full `_DMA_GRAIN` line.  The
  tiled kernel loads one contiguous (b_tile, k_slab) block per step,
  amortizing the grain across b_tile*k_slab weights.
* output: written once (the accumulator lives in VMEM), b*d*itemsize.

exposed-wait accounting (the double-buffering win): a "serialized DMA
wait" is a kernel step that must stall on HBM with no compute to hide
behind.  The row kernel waits its single row DMA EVERY grid step.  The
tiled kernel double-buffers K-slabs across the sequential K grid axis,
so only the FIRST slab of each (b_tile, d_tile) output tile is exposed;
the other K/k_slab - 1 slab waits overlap the previous slab's FMAs.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_rows, write_csv
from repro.analysis.pallas_audit import row_agg_budget, tiled_agg_budget
from repro.kernels.neighbor_agg.ops import neighbor_agg

_DMA_GRAIN = 32          # min HBM read granularity per distinct load, bytes

# one set of tile constants feeds BOTH the kernel invocation and the
# bytes accounting, so retuning can't silently desync them
B_TILE, D_TILE, K_SLAB = 8, 128, 4

# per-step VMEM working set from the SAME budget model `make analyze`
# gates against the backend limit (analysis/pallas_audit.py) — keeping
# the bench and the checker on one formula
_VMEM_BYTES = {
    "row": sum(row_agg_budget(D_TILE).values()),
    "tiled": sum(tiled_agg_budget(B_TILE, D_TILE, K_SLAB).values()),
}


def _accounting(kernel, n, d, b, k, itemsize=4,
                b_tile=B_TILE, d_tile=D_TILE, k_slab=K_SLAB):
    d_pad = -(-d // d_tile) * d_tile
    d_passes = d_pad // d_tile
    feat_bytes = b * k * d_pad * itemsize
    idx_bytes = b * k * 4
    out_bytes = b * d_pad * itemsize
    if kernel == "row":
        grid_steps = b * d_passes * k
        w_loads = grid_steps                      # one (1,1) block per step
        w_bytes = w_loads * _DMA_GRAIN
        dmas_per_step = 1
        # no pipelining: every step stalls on its own row DMA
        exposed_waits = grid_steps
    else:
        b_pad = -(-b // b_tile) * b_tile
        k_pad = -(-k // k_slab) * k_slab
        feat_bytes = b_pad * k_pad * d_pad * itemsize
        idx_bytes = b_pad * k_pad * 4
        out_bytes = b_pad * d_pad * itemsize
        grid_steps = (b_pad // b_tile) * d_passes * (k_pad // k_slab)
        w_loads = grid_steps                      # one (b_tile,k_slab) block
        w_bytes = w_loads * max(b_tile * k_slab * 4, _DMA_GRAIN)
        dmas_per_step = b_tile * k_slab
        # double-buffered slabs: only the warm-up slab of each output
        # tile is an exposed wait; the rest prefetch behind the FMAs
        exposed_waits = (b_pad // b_tile) * d_passes
    total = feat_bytes + idx_bytes + w_bytes + out_bytes
    return {
        "grid_steps": grid_steps,
        "dmas_per_step": dmas_per_step,
        "exposed_waits": exposed_waits,
        "feat_bytes": feat_bytes,
        "w_bytes": w_bytes,
        "bytes_moved": total,
    }


def run(quick: bool = True, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows = []
    cases = [(4096, 128, 256, 15), (16384, 256, 512, 10)]
    if quick:
        cases = [(1024, 128, 64, 15)]
    for n, d, b, k in cases:
        feats = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
        w = jnp.asarray(rng.random((b, k)) * (rng.random((b, k)) > 0.3),
                        jnp.float32)
        ref = neighbor_agg(feats, idx, w, use_kernel=False)
        ref.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            neighbor_agg(feats, idx, w, use_kernel=False).block_until_ready()
        t_ref = (time.perf_counter() - t0) / 3
        for kernel in ("row", "tiled"):
            ker = neighbor_agg(feats, idx, w, use_kernel=True,
                               kernel=kernel, b_tile=B_TILE, d_tile=D_TILE,
                               k_slab=K_SLAB)
            err = float(jnp.max(jnp.abs(ref - ker)))
            flops = 2.0 * b * k * d
            acct = _accounting(kernel, n, d, b, k)
            rows.append({
                "kernel": kernel, "n": n, "d": d, "b": b, "k": k,
                "jnp_us_per_call": round(t_ref * 1e6, 1),
                "kernel_max_err": err,
                "flops": int(flops),
                "vmem_bytes": _VMEM_BYTES[kernel],
                **acct,
                "arithmetic_intensity": round(flops / acct["bytes_moved"],
                                              3),
                "v5e_hbm_bound_us": round(
                    acct["bytes_moved"] / 819e9 * 1e6, 3),
            })
    write_csv("kernel_microbench", rows)
    print_rows("kernel", rows)
    return rows


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
