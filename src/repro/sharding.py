"""Logical sharding axes -> mesh PartitionSpecs.

Params/activations are annotated with *logical* axis names; they resolve
against whatever mesh is active ("data","model") or ("pod","data","model").
The batch logical axis spans ("pod","data") on a multi-pod mesh so the global
batch shards over every chip.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH = "batch"    # data-parallel axis (pod x data)
MODEL = "model"    # tensor-parallel axis
NODES = "nodes"    # GNN node-parallel axis (alias of batch axes)

# Production tensor-parallel degree (the "model" axis of both meshes).
# Head / expert / vocab dims are padded or replicated based on divisibility
# against this constant; smoke-test meshes use model=1, which any dim divides.
MODEL_PAR = 16


def pad_to(n: int, m: int = MODEL_PAR) -> int:
    return ((n + m - 1) // m) * m


def shard_heads(n: int) -> bool:
    """Shard a heads-like dim over `model` only when it stays divisible."""
    return n % MODEL_PAR == 0


def padded_heads(n: int) -> int:
    """Query heads are padded up to a MODEL_PAR multiple when big enough to
    shard (llama4: 40 -> 48); small head counts (smoke configs) stay as-is
    and replicate."""
    if n % MODEL_PAR == 0 or n < MODEL_PAR:
        return n
    return pad_to(n)


ALL = "all"        # every mesh axis (for unshardable-batch decode caches)
FSDP = "fsdp"      # weight sharding over the data axis (ZeRO-3 style).
#                    NOT over "pod": cross-pod traffic stays gradient-only.


def axis_map(mesh: Mesh) -> dict:
    names = mesh.axis_names
    if "pod" in names:
        batch_axes: Any = ("pod", "data")
        all_axes: Any = ("pod", "data", "model")
    else:
        batch_axes = "data"
        all_axes = ("data", "model")
    return {BATCH: batch_axes, NODES: batch_axes, MODEL: "model",
            ALL: all_axes, FSDP: "data"}


def resolve(logical: Sequence[Optional[str]], mesh: Mesh) -> P:
    m = axis_map(mesh)
    return P(*[m.get(ax) if ax is not None else None for ax in logical])


def named(logical: Sequence[Optional[str]], mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, resolve(logical, mesh))


def tree_named(spec_tree: Any, mesh: Mesh) -> Any:
    """Map a pytree of logical-spec tuples to NamedShardings."""
    return jax.tree.map(
        lambda s: named(s, mesh),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(e is None or isinstance(e, str) for e in x),
    )


# --- active mesh for intra-jit sharding constraints ------------------------
# get_abstract_mesh() is empty inside jit traces in this jax version, so the
# launcher/dry-run explicitly activates the mesh around tracing.
_ACTIVE_MESH: Optional[Mesh] = None


class activate(object):
    """Context manager: `with sharding.activate(mesh): jit(...).lower(...)`
    Makes sh.constrain() resolve logical axes during tracing (also enters
    the legacy `with mesh:` context so bare-PartitionSpec constraints bind).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        global _ACTIVE_MESH
        self._prev = _ACTIVE_MESH
        _ACTIVE_MESH = self.mesh
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        global _ACTIVE_MESH
        _ACTIVE_MESH = self._prev
        return self._ctx.__exit__(*exc)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def batch_mesh_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


@functools.lru_cache(maxsize=None)
def _node_mesh_cached(n_devices: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n_devices]), ("data",))


def node_mesh(n_devices: Optional[int] = None) -> Mesh:
    """One-axis ("data",) mesh over the local devices — the NODES
    logical axis resolves onto it, so a NODES-sharded array lays its
    rows out data-parallel over every local device (GNN full-graph
    training; see engine.ShardedFullGraphSource).

    Memoized per device count: repeated binds (every sweep grid point
    re-binds its source) hand back the SAME Mesh object, the static
    part of every cached step's key."""
    return _node_mesh_cached(len(jax.devices()) if n_devices is None
                             else n_devices)


def row_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NODES-sharded leading axis, replicated on the rest — the layout
    shared by ShardedFullGraphSource's ELL rows and
    ShardedSampledSource's per-batch target axis."""
    return named((NODES,) + (None,) * (ndim - 1), mesh)


# --- NODES-partitioned kernels (shard_map) ---------------------------------

def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking OFF: the neighbor-agg
    kernels place their psum explicitly in the custom VJP (see
    kernels/README.md "Sharding")."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def nodes_axis(mesh: Mesh):
    """The mesh axis name(s) the NODES logical axis resolves onto
    ("data", or ("pod", "data") on a multi-pod mesh)."""
    return axis_map(mesh)[NODES]


def nodes_shards(mesh: Mesh) -> int:
    """Number of shards along the NODES logical axis."""
    ax = nodes_axis(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ax = (ax,) if isinstance(ax, str) else ax
    return int(np.prod([sizes[a] for a in ax]))


def ell_agg_specs(mesh: Mesh, fused: bool) -> Tuple[Tuple[P, ...], P]:
    """(in_specs, out_spec) for the NODES-partitioned neighbor
    aggregation: output rows / ``idx`` / ``w`` (+ ``self_rows`` /
    ``w_self`` when fused) shard their leading axis over NODES, the
    feature table replicates — the per-shard gather is then purely
    local and only the VJP's dfeats needs a cross-shard psum."""
    ax = nodes_axis(mesh)
    row2, row1, repl = P(ax, None), P(ax), P(None, None)
    ins = (repl, row2, row2) + ((row2, row1) if fused else ())
    return ins, row2


def row_owner(n_pad: int, mesh: Mesh) -> np.ndarray:
    """Host-side owner map for an [n_pad, ...] NODES-row-sharded table:
    ``owner[i]`` is the NODES shard holding row ``i`` (jax lays a
    row-sharded array out as contiguous blocks of ``n_pad / shards``
    rows, which is exactly what the featshard plan classifies against;
    see kernels/neighbor_agg/featshard.py)."""
    n_sh = nodes_shards(mesh)
    if n_pad % n_sh:
        raise ValueError(
            f"row_owner: n_pad={n_pad} rows must divide the {n_sh} NODES "
            f"shards (pad first)")
    return (np.arange(n_pad) // (n_pad // n_sh)).astype(np.int32)


def feats_spec(mesh: Mesh, layout: str = "replicated") -> P:
    """PartitionSpec of the gather-source feature table under a
    ``GNNConfig.feats_layout``: ``"replicated"`` is the PR-5 sharded
    kernel's layout (every shard holds the full [n, d] table),
    ``"sharded"`` rows the table over NODES — P("nodes"->mesh axes, None)
    — for the out-of-core featshard path."""
    if layout == "sharded":
        return P(nodes_axis(mesh), None)
    if layout != "replicated":
        raise ValueError(f"unknown feats_layout: {layout!r}")
    return P(None, None)


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint against the activated mesh; no-op when no
    mesh is active (smoke tests).  A spec that cannot bind to the active
    mesh raises: a constraint that silently does nothing would leave the
    layout to chance on a real mesh."""
    if _ACTIVE_MESH is None:
        return x
    return jax.lax.with_sharding_constraint(x, resolve(logical,
                                                       _ACTIVE_MESH))
