"""Pallas kernel checker: VMEM budgets, DMA/semaphore pairing, bounds.

Three checks over the repo's kernels (``neighbor_agg`` row + tiled,
``featshard`` — which dispatches through the same tiled kernel — and
``flash_attn``):

1. **VMEM budget** — recompute the per-grid-step VMEM working set from
   the kernels' block + scratch shapes (grid-blocked operands count
   twice: Pallas double-buffers them automatically) and compare against
   the per-backend limit (~16 MB/core on TPU, pallas_guide.md
   §TPU Architecture).  The result is a machine-readable table
   (``budget_table``) that ``bench_kernel.py`` records per case and
   ``kernels/README.md`` embeds.

2. **DMA/semaphore pairing** — the tiled kernel hand-rolls a two-slot
   K-slab rotation (slab ki in slot ki % 2, next slab prefetched while
   the current one accumulates).  ``simulate_dma_pairing`` executes the
   REAL kernel body over a small concrete grid with stub ``pl`` /
   ``pltpu`` / ``jnp`` objects, so every ``pl.when`` control path runs
   as plain Python and every ``make_async_copy`` start/wait lands in an
   event log.  The checker then asserts, per semaphore and in grid
   order: no wait on an un-started copy, no second start before the
   wait (a silently overwritten in-flight DMA), a wait descriptor that
   matches its start, and zero in-flight copies at every output-tile
   boundary (so any megacore partition of the parallel axes is safe).

3. **Gather-id bounds** — every gather index that addresses an
   operand row must be in range; the simulator checks the ids the
   kernel actually dereferences, and ``check_index_bounds`` validates
   the real host-side index tables (ELL, featshard plan) an audit graph
   produces.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding

#: per-core VMEM by backend (bytes).  CPU interpret mode has no real
#: VMEM, but the budget is checked against the TPU target the kernels
#: are written for.
VMEM_LIMIT = {"tpu": 16 * 2 ** 20}
#: warn above this fraction of the limit — leaves headroom for the
#: compiler's own spills and for operands we cannot see statically
WARN_FRACTION = 0.75


# ---------------------------------------------------------------------------
# VMEM budgets (block/scratch shape formulas, mirroring the kernels)
# ---------------------------------------------------------------------------

def tiled_agg_budget(b_tile: int, d_tile: int, k_slab: int, *,
                     packed: bool = False,
                     fuse_self: bool = False) -> Dict[str, int]:
    """Per-step VMEM bytes of ``neighbor_agg_pallas_tiled``
    (neighbor_agg.py ``_make_tiled_kernel``): the manually-DMA'd row
    double buffer + f32 accumulator scratch, plus the grid-blocked
    operands (w / optional fused-self blocks / out), each double-
    buffered by the Pallas pipeline.  Rows, self rows and out are 32-bit
    words (``packed`` bf16: two columns per word, two f32 accumulator
    parts).  The weight block spans the whole K; it is counted here at
    K = k_slab (it adds b_tile·K·8 B, 2 KiB at b_tile=8, K=32).  feats
    stays in HBM (ANY) and the ids in SMEM — 0."""
    n_parts = 2 if packed else 1
    parts = {
        "scratch rows[2,k_slab,b_tile,d_tile]":
            2 * k_slab * b_tile * d_tile * 4,
        "scratch acc[parts,b_tile,d_tile] f32":
            n_parts * b_tile * d_tile * 4,
        "block w[b_tile,K] f32 x2": 2 * b_tile * k_slab * 4,
        "block out[b_tile,d_tile] x2": 2 * b_tile * d_tile * 4,
    }
    if fuse_self:
        parts["block w_self[b_tile,1] x2"] = 2 * b_tile * 4
        parts["block self[b_tile,d_tile] x2"] = 2 * b_tile * d_tile * 4
    return parts


def row_agg_budget(d_tile: int, *, feat_itemsize: int = 4,
                   out_itemsize: int = 4) -> Dict[str, int]:
    """Per-step VMEM bytes of the seed row kernel (``_row_kernel``)."""
    return {
        "scratch acc[1,d_tile] f32": d_tile * 4,
        "block w[1,1] x2": 2 * 4,
        "block feat_row[1,d_tile] x2": 2 * d_tile * feat_itemsize,
        "block out[1,d_tile] x2": 2 * d_tile * out_itemsize,
    }


def flash_attn_budget(q_block: int, k_block: int, d: int, *,
                      itemsize: int = 4) -> Dict[str, int]:
    """Per-step VMEM bytes of ``flash_attn._kernel`` (no manual DMAs:
    q/k/v/o ride grid-blocked specs; acc/m/l are f32 scratch)."""
    return {
        "block q[1,q_block,d] x2": 2 * q_block * d * itemsize,
        "block k[1,k_block,d] x2": 2 * k_block * d * itemsize,
        "block v[1,k_block,d] x2": 2 * k_block * d * itemsize,
        "block o[1,q_block,d] x2": 2 * q_block * d * itemsize,
        "scratch acc[q_block,d] f32": q_block * d * 4,
        "scratch m[q_block] f32": q_block * 4,
        "scratch l[q_block] f32": q_block * 4,
    }


def budget_row(kernel: str, case: str, parts: Dict[str, int],
               backend: str = "tpu") -> Dict:
    total = sum(parts.values())
    limit = VMEM_LIMIT[backend]
    return {"kernel": kernel, "case": case, "backend": backend,
            "vmem_bytes": total, "vmem_limit": limit,
            "vmem_frac": round(total / limit, 5),
            "breakdown": dict(parts)}


def default_budget_table() -> List[Dict]:
    """The committed kernel cases: the GNNConfig default tiling (f32 +
    bf16 feature tables, with and without the fused self epilogue), the
    seed row kernel, and flash_attn at its default blocks."""
    rows = []
    for packed, tag in ((False, "f32"), (True, "bf16")):
        for fuse in (False, True):
            case = f"b8 d128 k4 {tag}" + (" +self" if fuse else "")
            rows.append(budget_row(
                "neighbor_agg_tiled", case,
                tiled_agg_budget(8, 128, 4, packed=packed,
                                 fuse_self=fuse)))
    rows.append(budget_row("neighbor_agg_row", "d128 f32",
                           row_agg_budget(128)))
    rows.append(budget_row("flash_attn", "q128 k128 d128 f32",
                           flash_attn_budget(128, 128, 128)))
    rows.append(budget_row("flash_attn", "q128 k128 d128 bf16",
                           flash_attn_budget(128, 128, 128, itemsize=2)))
    return rows


def audit_budgets(table: Optional[Sequence[Dict]] = None) -> List[Finding]:
    out: List[Finding] = []
    for row in (default_budget_table() if table is None else table):
        site = f"kernel:{row['kernel']}[{row['case']}]"
        if row["vmem_bytes"] > row["vmem_limit"]:
            out.append(Finding(
                "pallas", "error", site,
                f"VMEM working set {row['vmem_bytes']} B exceeds the "
                f"{row['backend']} limit {row['vmem_limit']} B "
                f"({100 * row['vmem_frac']:.1f}%)"))
        elif row["vmem_frac"] > WARN_FRACTION:
            out.append(Finding(
                "pallas", "warning", site,
                f"VMEM working set {row['vmem_bytes']} B is "
                f"{100 * row['vmem_frac']:.1f}% of the {row['backend']} "
                f"limit — no headroom for compiler spills"))
    return out


# ---------------------------------------------------------------------------
# DMA/semaphore pairing: execute the kernel body with stub pl/pltpu
# ---------------------------------------------------------------------------

class _Ref:
    """Stand-in for a pallas Ref: numpy-backed for compute refs, token-
    producing (via ``.at``) for DMA source/dest/semaphore refs."""

    def __init__(self, name: str, arr: Optional[np.ndarray] = None,
                 harness: Optional["_Harness"] = None):
        self.name = name
        self.arr = arr
        self._h = harness

    @property
    def at(self):
        return _At(self)

    @property
    def dtype(self):
        return self.arr.dtype

    @property
    def shape(self):
        return self.arr.shape

    def __array__(self, dtype=None):       # jnp/np.zeros_like support
        a = self.arr
        return a if dtype is None else a.astype(dtype)

    def __getitem__(self, key):
        return self.arr if key is Ellipsis else self.arr[key]

    def __setitem__(self, key, val):
        if key is Ellipsis:
            self.arr[...] = np.asarray(val, self.arr.dtype)
        else:
            self.arr[key] = val


class _At:
    def __init__(self, ref: _Ref):
        self._ref = ref

    def __getitem__(self, key):
        if not isinstance(key, tuple):
            key = (key,)
        h = self._ref._h
        if h is not None:
            h.on_index(self._ref.name, key)
        return (self._ref.name, tuple(_freeze(k) for k in key))


def _freeze(k):
    if isinstance(k, slice):
        return ("slice", k.start, k.stop, k.step)
    if isinstance(k, (int, np.integer)):
        return int(k)
    return k                      # ("ds", start, size) tokens pass through


class _DMA:
    def __init__(self, harness: "_Harness", src, dst, sem):
        self._h = harness
        self.desc = (src, dst, sem)

    def start(self, priority: int = 0):
        self._h.events.append(("start",) + (self.desc,) + (self._h.point,))

    def wait(self):
        self._h.events.append(("wait",) + (self.desc,) + (self._h.point,))


class _StubPL:
    def __init__(self, harness: "_Harness"):
        self._h = harness

    def program_id(self, axis: int) -> int:
        return self._h.point[axis]

    def num_programs(self, axis: int) -> int:
        return self._h.grid[axis]

    def when(self, cond):
        def deco(fn):
            if bool(cond):
                fn()
            return fn
        return deco

    def ds(self, start, size):
        return ("ds", int(start), int(size))


class _StubPLTPU:
    def __init__(self, harness: "_Harness"):
        self._h = harness

    def make_async_copy(self, src, dst, sem):
        return _DMA(self._h, src, dst, sem)


class _Harness:
    """Runs one kernel function over a concrete grid, recording DMA
    start/wait events and checking dereferenced gather ids."""

    def __init__(self, grid: Tuple[int, int, int], n_rows: int):
        self.grid = grid
        self.point = (0, 0, 0)
        self.n_rows = n_rows
        self.events: List[Tuple] = []
        self.bad_ids: List[Tuple[str, int]] = []

    def on_index(self, name: str, key: Tuple) -> None:
        # the feature-table gather: the first index is the word row
        # nid * n_tiles + tile of the SMEM-staged neighbor id — it must
        # address a real row
        n_tiles = self.grid[1]
        if name == "feat" and key:
            row = key[0]
            if isinstance(row, (int, np.integer)) \
                    and not 0 <= int(row) < self.n_rows * n_tiles:
                self.bad_ids.append((name, int(row) // n_tiles))


def simulate_dma_pairing(make_kernel, *, b_tile: int = 2, d_tile: int = 8,
                         k_slab: int = 2, nk: int = 3,
                         fuse_self: bool = False, n_rows: int = 16,
                         site: str = "kernel:neighbor_agg_tiled",
                         grid_bd: Tuple[int, int] = (2, 2),
                         idx: Optional[np.ndarray] = None
                         ) -> List[Finding]:
    """Execute ``make_kernel(b_tile, n_tiles, k_slab, packed,
    fuse_self)``'s kernel (f32 rows, n_tiles = ``grid_bd[1]``) over a
    ``(grid_bd[0], grid_bd[1], nk)`` grid in row-major order (K
    innermost + sequential, matching the kernel's
    ``dimension_semantics``) and verify DMA/semaphore discipline.  The
    SMEM id block handed to each grid row is that row's slice of
    ``idx`` [B, K], as the Pallas pipeline stages it.

    The kernel's module-level ``pl`` / ``pltpu`` / ``jnp`` names are
    swapped for stubs via ``__globals__`` for the duration — local to
    the kernel's defining module and restored in a ``finally``."""
    k_total = nk * k_slab
    gb, gd = grid_bd
    b = gb * b_tile
    grid = (gb, gd, nk)
    site = f"{site}[fuse_self={fuse_self},nk={nk}]"
    h = _Harness(grid, n_rows)
    kernel = make_kernel(b_tile, gd, k_slab, False, fuse_self)

    rng = np.random.default_rng(0)
    if idx is None:
        idx = rng.integers(0, n_rows, size=b * k_total).astype(np.int32)
    idx = np.asarray(idx).reshape(-1)[:b * k_total].reshape(b, k_total)
    refs = dict(
        idx=_Ref("idx", idx[:b_tile]),
        w=_Ref("w", np.ones((b_tile, k_total), np.float32)),
        wself=_Ref("wself", np.ones((b_tile, 1), np.float32)),
        self_=_Ref("self", np.ones((b_tile, d_tile), np.float32)),
        feat=_Ref("feat", harness=h),
        out=_Ref("out", np.zeros((b_tile, d_tile), np.float32)),
        rows=_Ref("rows", np.zeros((2, k_slab, b_tile, d_tile),
                                   np.float32)),
        acc=_Ref("acc", np.zeros((1, b_tile, d_tile), np.float32)),
        sems=_Ref("sem", harness=h),
    )
    if fuse_self:
        args = (refs["idx"], refs["w"], refs["wself"], refs["self_"],
                refs["feat"], refs["out"], refs["rows"], refs["acc"],
                refs["sems"])
    else:
        args = (refs["idx"], refs["w"], refs["feat"], refs["out"],
                refs["rows"], refs["acc"], refs["sems"])

    g = kernel.__globals__
    saved = {k: g[k] for k in ("pl", "pltpu", "jnp") if k in g}
    g["pl"] = _StubPL(h)
    g["pltpu"] = _StubPLTPU(h)
    g["jnp"] = np
    findings: List[Finding] = []
    try:
        for bi in range(gb):
            refs["idx"].arr = idx[bi * b_tile:(bi + 1) * b_tile]
            for di in range(gd):
                pane_start = len(h.events)
                for ki in range(nk):
                    h.point = (bi, di, ki)
                    kernel(*args)
                findings += _check_pane(
                    h.events[pane_start:], site, pane=(bi, di))
    except Exception as e:  # a crash in the stubbed body is a finding,
        # not an analyzer error: the control path is unexecutable
        findings.append(Finding(
            "pallas", "error", site,
            f"kernel body raised under control-path simulation at grid "
            f"point {h.point}: {type(e).__name__}: {e}"))
    finally:
        g.update(saved)

    for name, nid in h.bad_ids[:4]:
        findings.append(Finding(
            "pallas", "error", site,
            f"gather id {nid} addresses {name} rows "
            f"outside [0, {n_rows})"))
    return findings


def _check_pane(events: Sequence[Tuple], site: str,
                pane: Tuple[int, int]) -> List[Finding]:
    """Per-semaphore alternation over one output tile's event stream:
    start -> wait (with matching descriptor), nothing left in flight at
    the pane boundary."""
    out: List[Finding] = []
    in_flight: Dict[Tuple, Tuple] = {}   # sem token -> (src, dst, point)
    for kind, (src, dst, sem), point in events:
        if kind == "start":
            if sem in in_flight:
                out.append(Finding(
                    "pallas", "error", f"{site}:sem{sem[1]}",
                    f"copy started at grid point {point} while the "
                    f"previous copy on this semaphore (started at "
                    f"{in_flight[sem][2]}) was never waited — the "
                    "in-flight DMA is silently overwritten"))
            in_flight[sem] = (src, dst, point)
        else:
            if sem not in in_flight:
                out.append(Finding(
                    "pallas", "error", f"{site}:sem{sem[1]}",
                    f"wait at grid point {point} on a semaphore with no "
                    "started copy (hangs on real hardware)"))
                continue
            s_src, s_dst, s_point = in_flight.pop(sem)
            if (s_src, s_dst) != (src, dst):
                out.append(Finding(
                    "pallas", "error", f"{site}:sem{sem[1]}",
                    f"wait descriptor at {point} does not match the "
                    f"copy started at {s_point}: started "
                    f"{s_src}->{s_dst}, waited {src}->{dst}"))
    for sem, (_, _, s_point) in sorted(in_flight.items()):
        out.append(Finding(
            "pallas", "error", f"{site}:sem{sem[1]}",
            f"copy started at {s_point} never waited within its output "
            f"tile {pane} — leaks into the next tile (and deadlocks a "
            "megacore partition at the pane boundary)"))
    return out


def audit_dma_pairing(make_kernel=None) -> List[Finding]:
    """Pairing audit over the repo's tiled kernel (or a fixture factory
    with the same signature): warm-up (nk=1), steady state + tail
    (nk=2,3), both epilogue variants.  featshard reuses this kernel via
    ``ops._tiled_call``, so its DMA discipline is covered here."""
    if make_kernel is None:
        from repro.kernels.neighbor_agg.neighbor_agg import \
            _make_tiled_kernel as make_kernel
    findings: List[Finding] = []
    for fuse in (False, True):
        for nk in (1, 2, 3):
            findings += simulate_dma_pairing(
                make_kernel, nk=nk, fuse_self=fuse)
    return findings


# ---------------------------------------------------------------------------
# Host-side index-table bounds (real data)
# ---------------------------------------------------------------------------

def check_index_bounds(idx, n_rows: int, site: str) -> List[Finding]:
    idx = np.asarray(idx)
    if idx.size == 0:
        return []
    lo, hi = int(idx.min()), int(idx.max())
    if lo < 0 or hi >= n_rows:
        return [Finding(
            "pallas", "error", site,
            f"index table range [{lo}, {hi}] escapes the operand's "
            f"[0, {n_rows}) rows — the kernel DMA would read out of "
            "bounds")]
    return []


def audit_index_tables(graph, mesh=None,
                       cache_rows: int = -1) -> List[Finding]:
    """Bounds-check the index tables the kernels actually consume for
    ``graph``: the ELL neighbor ids against the feature table, and (on
    a mesh) every featshard-plan index array against its target."""
    from repro import sharding as sh
    from repro.core.graph import to_ell
    findings: List[Finding] = []
    idx, w, _ = to_ell(graph)
    findings += check_index_bounds(idx, graph.n, "bounds:ell.idx")
    if mesh is None:
        mesh = sh.node_mesh()
    from repro.kernels.neighbor_agg.ops import build_featshard_plan
    pad = (-graph.n) % sh.nodes_shards(mesh)
    if pad:
        idx = np.pad(idx, ((0, pad), (0, 0)))
        w = np.pad(w, ((0, pad), (0, 0)))
    plan = build_featshard_plan(idx, w, graph.degrees, mesh,
                                cache_rows=cache_rows)
    n_loc = plan.n_loc
    checks = [
        ("bounds:featshard.lidx_hot", plan.lidx_hot, n_loc + plan.C_max),
        ("bounds:featshard.lidx_miss", plan.lidx_miss,
         max(plan.S * plan.M, 1)),
        ("bounds:featshard.serve_loc", plan.serve_loc, n_loc),
        ("bounds:featshard.hot_src_loc", plan.hot_src_loc, n_loc),
    ]
    for site, arr, n in checks:
        if arr is not None:
            findings += check_index_bounds(np.asarray(arr), n, site)
    return findings
