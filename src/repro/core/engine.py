"""Unified training engine: one Trainer, pluggable batch sources and
callbacks (the paper's central framing made executable: full-graph
training IS mini-batch training at the (b=n, beta=d_max) limit, so both
paradigms run through the SAME loop and differ only in their BatchSource).

Pieces
------
- ``BatchSource``     — where batches come from and how the loss is
  computed on one.  ``FullGraphSource`` (ELL layout, all train nodes),
  ``ShardedFullGraphSource`` (the same, rows laid out over the NODES
  axis of a local device mesh) and ``SampledSource`` (vectorized CSR
  sampler, optional Prefetcher with reusable host staging buffers) are
  the paper's two paradigms; ``ClusterSource`` (Cluster-GCN unions of
  BFS partitions, ``core.partition``), ``ImportanceSampledSource``
  (score-weighted targets + unbiasedness-preserving loss reweighting)
  and ``ShardedSampledSource`` (the mini-batch twin of the sharded
  full-graph source) extend the space to the related-work scenarios.
- ``TrainPlan``       — declarative run spec: optimizer name/lr/schedule
  (resolved from ``repro.optim``), iteration budget, eval cadence,
  full-loss tracking, stop targets, checkpoint cadence, and the
  throughput knobs (``donate``, ``deferred_sync``).
- ``Callback``        — composable hooks (``on_step`` / ``on_eval`` /
  ``on_stop`` / ``on_train_start`` / ``on_train_end``).  History
  recording, early stopping and checkpointing are themselves callbacks.
- ``Trainer``         — the single loop.  ``train_full_graph`` /
  ``train_minibatch`` in ``core.trainer`` are thin wrappers over it and
  reproduce the pre-engine loss sequences bit-for-bit at fixed seed
  (test-enforced against recorded goldens).

Throughput path (docs/training_api.md "Throughput knobs"):

- the jitted step DONATES ``params``/``opt_state`` (and the sampled
  batch pytree), so the optimizer update reuses their device buffers
  instead of allocating fresh ones every iteration;
- the per-step ``float(loss)`` host sync is LAGGED one iteration
  (``plan.deferred_sync``): step ``i + 1`` is dispatched while step
  ``i`` is still in flight, and record ``i`` (loss / eval accuracy /
  tracked full loss, all device scalars) is read back afterwards.
  Staging-ring slots therefore recycle one step late and the ring grows
  by one slot.  Runs with stop targets or checkpoint cadence fall back
  to the synchronous read (their semantics need the loss on host
  immediately);
- compiled steps are CACHED per graph across Trainer instances (keyed
  by source type, normalized config, optimizer spec and the static part
  of the source's constants; the constants' arrays are step arguments),
  so a ``sweep()`` grid point with the same effective shapes never
  re-traces; partial batches are padded up to
  the plan's batch size with masked-out rows so each grid point
  compiles exactly one step function;
- evaluation and full-loss tracking run through module-level jitted
  functions keyed on a normalized config, shared across all Trainers of
  a sweep.

``core.experiment`` builds the (b, beta) grid runner on top of this.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Any, Callable as TCallable, List, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GNNConfig
from repro.core import gnn as G
from repro.core import tracing
from repro.core.graph import Graph, to_ell
from repro.core.metrics import History
from repro.core.prefetch import HostStagingRing, Prefetcher
from repro.core.sampler import FanoutBatch, expand_batch, sample_batch


# ---------------------------------------------------------------------------
# Shared device-side helpers (memoized per graph)
# ---------------------------------------------------------------------------

def _resolve_max_deg(graph: Graph, max_deg: Optional[int]) -> int:
    """ELL width for an optional cap.  ``max_deg or graph.d_max`` is the
    trap this replaces: an explicit ``max_deg=0`` is falsy, so it used
    to silently fall back to the UNCAPPED d_max instead of erroring."""
    if max_deg is None:
        return graph.d_max
    if max_deg < 1:
        raise ValueError(f"max_deg must be >= 1 (or None for "
                         f"d_max={graph.d_max}), got {max_deg}")
    return int(max_deg)


def _device_base(graph: Graph):
    """``(feats, labels)`` on the device, uploaded once per graph as the
    ELL cache's max_deg-independent ``"base"`` entry: evaluation, the
    full-graph ELL and the sampled step's in-step row gather share this
    one copy of the feature table."""
    cache = getattr(graph, "_ell_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_ell_cache", cache)
    if "base" not in cache:
        cache["base"] = (jnp.asarray(graph.feats),
                         jnp.asarray(graph.labels))
    return cache["base"]


def _device_ell(graph: Graph, max_deg: Optional[int] = None):
    """Device-resident ELL layout, memoized per graph: evaluation and the
    full-loss tracker used to rebuild (re-pad + re-upload) it on every
    call.  The cache lives on the Graph instance so it dies with it.

    At most ONE ELL key is resident besides the max_deg-independent
    "base" uploads (``_device_base``): inserting a new key evicts the
    others, so a sweep over distinct ``max_deg`` values no longer
    accretes one full [n, K] upload per grid point (sources that need a
    capped ELL to outlive the cache hold their own reference via
    ``self.ell``).  Each key also keeps its count of kept edges
    (``_ell_edges``).
    """
    key = _resolve_max_deg(graph, max_deg)
    base = _device_base(graph)
    cache = graph._ell_cache
    if key not in cache:
        for stale in [k for k in cache if k != "base"]:
            del cache[stale]
        idx, w, w_self = to_ell(graph, max_deg=max_deg)
        cache[key] = ((jnp.asarray(idx), jnp.asarray(w),
                       jnp.asarray(w_self)), int(np.count_nonzero(w)))
    return cache[key][0] + base


def _ell_edges(graph: Graph, max_deg: Optional[int]) -> int:
    """Kept edges (nonzero weights) of the ELL ``_device_ell`` cached
    for this cap."""
    return graph._ell_cache[_resolve_max_deg(graph, max_deg)][1]


def _count_ell(cfg: GNNConfig, rows: int, k: int, edges: int,
               shards: int = 1) -> None:
    """Record a bound ELL's ``ell_slots`` (the (row, slot) entries one
    aggregation call reads) and ``ell_edges`` (those holding a kept
    edge).  The tiled kernel pads each shard's rows to ``agg_b_tile``
    and K to ``agg_k_slab``, and DMAs every padded slot (GAT's per-head
    calls each read the same slots); the einsum path reads
    ``[rows, K]``."""
    if cfg.use_agg_kernel:
        per_shard = -(-rows // shards)
        rows = shards * (-(-per_shard // cfg.agg_b_tile) * cfg.agg_b_tile)
        k = -(-k // cfg.agg_k_slab) * cfg.agg_k_slab
    tracing.count("ell_slots", rows * k)
    tracing.count("ell_edges", edges)


def _count_attn(cfg: GNNConfig, feat_dim: int) -> None:
    """Record GAT's ``attn_cols`` (the real columns ``H·dh`` summed over
    one forward's attention-aggregation calls) and ``attn_kernel_cols``
    (the columns those calls read: the tiled kernel pads each head's
    block as ``ops.kernel_cols`` says; the einsum path reads them as
    they are).  Other models record neither."""
    if cfg.model != "gat":
        return
    from repro.kernels.neighbor_agg.ops import kernel_cols
    agg_dt = G.agg_dtype(cfg, jnp.float32)
    for heads, dh in G.gat_head_dims(cfg, feat_dim):
        tracing.count("attn_cols", heads * dh)
        tracing.count("attn_kernel_cols", heads * (
            kernel_cols(dh, agg_dt, cfg.agg_d_tile) if cfg.use_agg_kernel
            else dh))


def _gather_hops(feats, hop_ids):
    """Each hop's feature rows ``[b, f1..fd, r]``, gathered inside the
    compiled step from the device table by the hop's node ids
    ``[b, f1..fd]``.  Staging range-checks the ids, so ``clip`` moves
    none of them."""
    return [jnp.take(feats, ids, axis=0, mode="clip") for ids in hop_ids]


def _device_nodes(graph: Graph, which: str):
    """Device copy of a node-id split (train/val/test), uploaded once per
    graph instead of per evaluation call."""
    cache = getattr(graph, "_node_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_node_cache", cache)
    if which not in cache:
        cache[which] = jnp.asarray(getattr(graph, f"{which}_nodes"))
    return cache[which]


def _static_cfg(cfg: GNNConfig) -> GNNConfig:
    """Normalize the fields that do NOT affect the traced computation
    (names, sampler geometry) so the module-level jit caches — eval,
    full loss, compiled steps — are shared across sweep grid points."""
    return dataclasses.replace(
        cfg, name="", source="", batch_size=1,
        fanout=(1,) * cfg.n_layers, max_degree=1, n_nodes=0, feat_dim=0)


@functools.partial(jax.jit, static_argnums=(1, 8))
def _eval_acc(params, cfg: GNNConfig, idx, w, w_self, feats, labels,
              nodes, mesh=None, feats_plan=None):
    # feats_plan (a FeatShardPlan pytree, or None) is an ordinary
    # argument: its index arrays are leaves, its layout static aux data
    logits = G.full_graph_forward(params, cfg, feats, idx, w, w_self,
                                  mesh=mesh, feats_plan=feats_plan)
    return G.accuracy(logits[nodes], labels[nodes])


#: layer 0's aggregation of the fixed feature table (``gnn.layer0_agg``),
#: computed once per full-graph bind
_layer0_agg = jax.jit(G.layer0_agg, static_argnums=0)


def _split_consts(consts) -> Tuple[Tuple, Tuple, TCallable]:
    """Split a source's ``loss_consts()`` into the arrays a compiled
    function takes as ARGUMENTS and the static rest (meshes; a
    featshard plan's layout is static aux data of its pytree).

    -> ``(arrays, static_key, join)`` where ``join(arrays)`` rebuilds the
    consts inside the traced function.  Arrays are never closed over:
    a closed-over device array becomes a constant of the executable (at
    papers100M scale a 2.4 GB program that no compile cache keeps), and
    a cache keyed on its identity recompiles for every fresh upload.
    """
    leaves, tdef = jax.tree.flatten(consts)
    is_arr = tuple(isinstance(x, (jax.Array, np.ndarray)) for x in leaves)
    arrays = tuple(x for x, a in zip(leaves, is_arr) if a)
    static = tuple(x for x, a in zip(leaves, is_arr) if not a)

    def join(arrs):
        it_a, it_s = iter(arrs), iter(static)
        return jax.tree.unflatten(
            tdef, [next(it_a) if a else next(it_s) for a in is_arr])

    return arrays, (tdef, is_arr, static), join


def _graph_fn_cache(graph: Graph, key, build):
    """Per-graph compiled-function cache (dies with the graph): sweeps
    re-create Trainers per grid point, and grid points with the same
    static configuration reuse ONE jitted step / full-loss function
    (jit itself keys its executables on the argument shapes).  The
    functions hold no device arrays, so an entry pins no upload; a
    FIFO bound caps the count."""
    cache = getattr(graph, "_fn_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(graph, "_fn_cache", cache)
    hit = cache.get(key)
    if hit is None:
        hit = build()
        while len(cache) >= 16:
            del cache[next(iter(cache))]
        cache[key] = hit
    return hit


def _cached_full_loss(graph: Graph, cfg: GNNConfig, ell, sel, mesh=None,
                      feats_plan=None):
    """Full-training-objective loss (params -> device scalar) over the
    device ELL.  ``mesh`` (sharded sources with the kernel on)
    partitions the kernel's aggregation over the NODES axis;
    ``feats_plan`` additionally row-shards the source table
    (feats_layout="sharded").  The ELL, ``sel`` and the plan's arrays
    are arguments of the jitted function (``_split_consts``)."""
    scfg = _static_cfg(cfg)
    arrays, static, join = _split_consts((tuple(ell), sel, mesh,
                                          feats_plan))

    def build():
        @jax.jit
        def full_loss(params, arrs):
            (idx, w, w_self, feats, labels), sel_, mesh_, plan_ = join(arrs)
            logits = G.full_graph_forward(params, scfg, feats, idx, w,
                                          w_self, mesh=mesh_,
                                          feats_plan=plan_)
            return G.gnn_loss(logits[sel_], labels[sel_], scfg.loss,
                              scfg.n_classes)

        return full_loss

    fn = _graph_fn_cache(graph, ("full_loss", scfg, static), build)
    return lambda params: fn(params, arrays)


def evaluate_full(params, cfg: GNNConfig, graph: Graph, ell, nodes,
                  mesh=None, feats_plan=None) -> float:
    """Inference uses ALL neighbors across the entire graph (§4.1).
    Jitted once per (normalized config, shapes) at module level — NOT
    per Trainer — so sweeps stop paying eval retrace at every grid
    point."""
    idx, w, w_self, feats, labels = ell
    return float(_eval_acc(params, _static_cfg(cfg), idx, w, w_self,
                           feats, labels, jnp.asarray(nodes), mesh,
                           feats_plan))


# ---------------------------------------------------------------------------
# TrainPlan
# ---------------------------------------------------------------------------

class NonFiniteStepError(RuntimeError):
    """A jitted step produced a non-finite loss or gradient and the
    plan's ``BadStepPolicy`` escalated to raise."""

    def __init__(self, it: int, loss: float, consecutive: int):
        super().__init__(
            f"non-finite loss/gradients at iteration {it} "
            f"(loss={loss}, {consecutive} consecutive bad step"
            f"{'s' if consecutive != 1 else ''})")
        self.it = it
        self.loss = loss
        self.consecutive = consecutive


@dataclasses.dataclass(frozen=True)
class BadStepPolicy:
    """What the Trainer does when the in-step ``isfinite`` guard trips
    (docs/training_api.md "Fault tolerance" has the full matrix).

    The guard itself is always in the compiled step: a bad step leaves
    params/opt_state UNCHANGED on device (a ``where`` select), so by the
    time the host learns about it — one iteration late under
    ``deferred_sync`` — the next step has already run from the last good
    params with a fresh batch.  That makes ``"skip"`` exactly
    skip-and-resample, with no pipeline stall.

    - ``on_bad="raise"``: abort with ``NonFiniteStepError`` at the first
      bad step (the default: silent NaNs are how convergence curves lie).
    - ``on_bad="skip"``: tolerate up to ``max_consecutive`` bad steps in
      a row (History records them in ``bad_steps``), then ``escalate``
      ("raise", or "rollback" when checkpointing is on).
    - ``on_bad="rollback"``: skip until ``max_consecutive`` consecutive
      bad steps, then restore params/opt_state from the newest
      checkpoint and continue with fresh batches; more than
      ``max_rollbacks`` restores aborts.  Requires ``ckpt_every > 0``
      (validated at Trainer construction).
    """

    on_bad: str = "raise"            # raise | skip | rollback
    max_consecutive: int = 3         # skip/rollback escalation threshold
    escalate: str = "raise"          # skip's escalation: raise | rollback
    max_rollbacks: int = 3

    def __post_init__(self):
        if self.on_bad not in ("raise", "skip", "rollback"):
            raise ValueError(f"BadStepPolicy.on_bad must be raise|skip|"
                             f"rollback, got {self.on_bad!r}")
        if self.escalate not in ("raise", "rollback"):
            raise ValueError(f"BadStepPolicy.escalate must be raise|"
                             f"rollback, got {self.escalate!r}")
        if self.max_consecutive < 1:
            raise ValueError("BadStepPolicy.max_consecutive must be >= 1")

    def needs_ckpt(self) -> bool:
        return (self.on_bad == "rollback"
                or (self.on_bad == "skip" and self.escalate == "rollback"))


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """Declarative spec for one training run (what used to be ~10 loose
    keyword arguments spread over two loops)."""
    lr: float = 0.3
    n_iters: int = 100
    optimizer: str = "sgd"              # name in repro.optim: sgd | adamw
    momentum: float = 0.0               # sgd only
    weight_decay: float = 0.0           # adamw only
    schedule: Optional[str] = None      # None/"constant" | "cosine"
    warmup: int = 0                     # cosine warmup iters
    lr_floor: float = 0.0               # cosine floor
    eval_every: int = 10
    track_full_loss_every: int = 0      # mini-batch: full objective cadence
    target_loss: Optional[float] = None  # stop when batch loss <= target
    target_acc: Optional[float] = None   # stop when val acc >= target
    ckpt_every: int = 0
    ckpt_dir: str = "experiments/ckpt"
    seed: int = 0
    # --- throughput knobs (docs/training_api.md) ---
    donate: bool = True                 # donate params/opt_state/batch
    deferred_sync: bool = True          # lag the float(loss) host sync
    # --- fault tolerance (docs/training_api.md "Fault tolerance") ---
    ckpt_keep_last: int = 0             # checkpoint retention (0 = all)
    bad_steps: BadStepPolicy = BadStepPolicy()

    def make_schedule(self):
        if self.schedule in (None, "constant"):
            return self.lr
        if self.schedule == "cosine":
            from repro.optim import cosine_schedule
            return cosine_schedule(self.lr, self.warmup, self.n_iters,
                                   floor=self.lr_floor)
        raise ValueError(f"unknown schedule {self.schedule!r}")

    def make_optimizer(self):
        from repro.optim import adamw, sgd
        lr = self.make_schedule()
        if self.optimizer == "sgd":
            return sgd(lr, momentum=self.momentum)
        if self.optimizer == "adamw":
            return adamw(lr, weight_decay=self.weight_decay)
        raise ValueError(f"unknown optimizer {self.optimizer!r}; "
                         "repro.optim has: sgd, adamw")


def _deferred_mode(plan: TrainPlan) -> bool:
    """Deferred loss sync needs the loss on host only one step late;
    stop targets and checkpoint cadence need it immediately."""
    return (plan.deferred_sync and plan.target_loss is None
            and plan.target_acc is None and plan.ckpt_every == 0)


def _opt_key(plan: TrainPlan) -> Tuple:
    """The subset of the plan the jitted step's optimizer depends on
    (n_iters only feeds the cosine schedule's horizon)."""
    return (plan.optimizer, plan.lr, plan.momentum, plan.weight_decay,
            plan.schedule, plan.warmup, plan.lr_floor,
            plan.n_iters if plan.schedule == "cosine" else 0)


def _guarded_update(opt, params, opt_state, loss, grads):
    """Optimizer update behind the non-finite step guard: a cheap
    ``isfinite`` reduction over loss + gradients is folded into the
    jitted step, and a bad step applies the IDENTITY update (``where``
    select keeps the old params/opt_state buffers bit-for-bit).  On a
    good step the select passes the new values through exactly, so the
    guard is value-invariant — the pre-PR-6 golden loss sequences are
    unchanged.  Returns (params, opt_state, good)."""
    good = jnp.isfinite(loss)
    for g in jax.tree.leaves(grads):
        good = good & jnp.all(jnp.isfinite(g))
    new_params, new_opt = opt.update(grads, opt_state, params)
    sel = lambda new, old: jnp.where(good, new, old)  # noqa: E731
    return (jax.tree.map(sel, new_params, params),
            jax.tree.map(sel, new_opt, opt_state), good)


def _cached_step(graph: Graph, src_cls: type, consts: Tuple,
                 cfg: GNNConfig, plan: TrainPlan):
    """Compiled train step, cached ON THE GRAPH across Trainer instances.

    -> ``(step, arrays)``: call ``step(params, opt_state, batch,
    arrays)``.  ``arrays`` are the device arrays of ``consts`` (e.g. the
    ELL tuple), passed as arguments rather than closed over
    (``_split_consts``), so the cache key is (source type, normalized
    config, optimizer spec, donation flag, the consts' static part) and
    every grid point of a ``sweep()`` with the same static
    configuration hits the same jitted step; jit compiles once per
    argument shape.
    """
    scfg = _static_cfg(cfg)
    arrays, static, join = _split_consts(consts)
    key = ("step", src_cls.__qualname__, scfg, _opt_key(plan),
           plan.donate, static)

    def build():
        opt = plan.make_optimizer()

        def step(params, opt_state, batch, arrs):
            loss, grads = jax.value_and_grad(
                lambda p: src_cls._loss_impl(p, batch, join(arrs), scfg)
            )(params)
            params, opt_state, good = _guarded_update(
                opt, params, opt_state, loss, grads)
            return params, opt_state, loss, good

        return jax.jit(step,
                       donate_argnums=(0, 1, 2) if plan.donate else ())

    return _graph_fn_cache(graph, key, build), arrays


# ---------------------------------------------------------------------------
# Batch sources
# ---------------------------------------------------------------------------

class BatchSource:
    """Where batches come from + how the training loss is computed on one.

    ``bind`` attaches graph/cfg/plan and uploads whatever is constant
    across iterations; ``batches`` yields ``(device_batch, n_nodes)``
    pairs; ``loss`` is traced inside the Trainer's single jitted step.
    ``done(batch)`` is called once the step consuming the batch has
    completed (host sync point) so sources may recycle staging buffers.
    ``close()`` is idempotent — the Trainer calls it from a ``finally``
    and early-stopping callbacks may have raced it already.

    Built-in sources additionally provide the *cacheable* loss form —
    a ``_loss_impl(params, batch, consts, cfg)`` staticmethod plus
    ``loss_consts()`` — which lets the engine reuse one compiled step
    across Trainer instances.  Custom sources only need ``loss``; they
    fall back to a per-Trainer jit.
    """

    #: the per-iteration training loss already IS the full objective
    #: (true for full-graph GD; the History callback uses this).
    loss_is_full_loss = False
    name = "source"
    #: cacheable loss form; None → per-Trainer jit fallback
    _loss_impl: Optional[TCallable] = None

    def bind(self, graph: Graph, cfg: GNNConfig, plan: TrainPlan
             ) -> "BatchSource":
        raise NotImplementedError

    def loss(self, params, batch):
        raise NotImplementedError

    def loss_consts(self) -> Tuple:
        """Constants of the cached step: arrays become step arguments,
        anything else (a mesh) is static (``_split_consts``)."""
        return ()

    def node_split(self, which: str):
        """Device array of a train/val/test node split, laid out however
        this source's forward expects (sharded sources replicate)."""
        return _device_nodes(self.graph, which)

    def place(self, tree):
        """Device placement for the params/opt_state pytrees before the
        first step.  Sharded sources replicate over their mesh so the
        step's input shardings are already final at iteration 0 —
        otherwise the first step's committed outputs silently force a
        SECOND compile at iteration 1."""
        return tree

    def batches(self):
        raise NotImplementedError

    def done(self, batch) -> None:
        pass

    def close(self) -> None:
        pass

    # -- exact-resume hooks --------------------------------------------
    def state_dict(self) -> dict:
        """JSON-serializable batch-stream position, saved inside every
        TrainerState checkpoint (sampled sources: consumed count + the
        rng bit-generator state after the last consumed draw).  Sources
        whose batches are constant across iterations have none."""
        return {}

    def load_state_dict(self, sd: dict) -> None:
        """Restore the stream position saved by ``state_dict`` (called
        between ``bind`` and ``batches`` on resume)."""
        if sd:
            raise ValueError(f"{type(self).__name__} has no stream state "
                             f"to restore, got keys {sorted(sd)}")


class FullGraphSource(BatchSource):
    """The (b=n_train, beta=d_max) limit: every iteration is GD over ALL
    training nodes on the device-resident ELL layout; the "batch" is
    empty because everything is constant across iterations.

    Where layer 0 gathers the raw feature table before it transforms it
    (GCN and GraphSAGE whose first layer does not narrow), that
    aggregation is the same in every step: ``bind`` computes it once
    (``gnn.layer0_agg``, ``[n, feat_dim]`` in the aggregation dtype) and
    holds it on the device as ``agg0``, an argument of the step."""

    loss_is_full_loss = True
    name = "fullgraph"

    def __init__(self, max_deg: Optional[int] = None):
        # ELL width cap; None takes the config's ``max_degree`` (itself
        # None = every neighbour).  A (b, beta) sweep passes its beta.
        self.max_deg = max_deg
        self.ell = None
        self.agg0 = None

    def _cap(self, cfg: GNNConfig) -> Optional[int]:
        return cfg.max_degree if self.max_deg is None else self.max_deg

    def bind(self, graph, cfg, plan):
        self.graph, self.cfg = graph, cfg
        cap = self._cap(cfg)
        self.ell = _device_ell(graph, cap)
        _count_ell(cfg, *self.ell[0].shape, _ell_edges(graph, cap))
        _count_attn(cfg, graph.feats.shape[1])
        idx, w, w_self, feats, _ = self.ell
        self.agg0 = _layer0_agg(_static_cfg(cfg), feats, idx, w, w_self)
        tracing.count("hoisted_agg_layers", int(self.agg0 is not None))
        self.train_nodes = _device_nodes(graph, "train")
        self.n_nodes = len(graph.train_nodes)
        return self

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        idx, w, w_self, feats, labels, train_nodes, agg0 = consts
        logits = G.full_graph_forward(params, cfg, feats, idx, w, w_self,
                                      agg0=agg0)
        lt = logits[train_nodes]
        return G.gnn_loss(lt, labels[train_nodes], cfg.loss,
                          cfg.n_classes)

    def loss_consts(self):
        return tuple(self.ell) + (self.train_nodes, self.agg0)

    def loss(self, params, batch):
        return type(self)._loss_impl(params, batch, self.loss_consts(),
                                     self.cfg)

    def batches(self):
        while True:
            yield None, self.n_nodes

    def close(self) -> None:
        # idempotent: drop the device ELL reference (the per-graph cache
        # keeps at most one resident key; sources release theirs here)
        self.ell = None
        self.agg0 = None


class ShardedFullGraphSource(FullGraphSource):
    """Full-graph GD with the ELL rows laid out over the ``NODES`` axis
    of a local device mesh (``NamedSharding`` row sharding), so the
    paper's (b=n, beta=d_max) limit runs data-parallel over all local
    devices — rows are padded with zero-weight entries up to a multiple
    of the mesh size, and the node splits are replicated so the same
    jitted eval/step functions serve every device.

    On a 1-device mesh this produces the exact same loss sequence as
    ``FullGraphSource`` (test-enforced); on an N-device mesh XLA GSPMD
    partitions the forward (the [n, K] gathers all-gather the layer
    activations) and all-reduces the gradients.  With
    ``cfg.use_agg_kernel`` the Pallas aggregation runs shard-locally
    over the same mesh (shard_map; ``kernels/README.md`` "Sharding") —
    bit-equal to the unsharded kernel on 1 device, einsum-equivalent on
    N.
    """

    name = "fullgraph_sharded"

    def __init__(self, max_deg: Optional[int] = None, mesh=None):
        super().__init__(max_deg)
        self.mesh = mesh

    def bind(self, graph, cfg, plan):
        from repro import sharding as sh
        self.graph, self.cfg = graph, cfg
        mesh = self.mesh if self.mesh is not None else sh.node_mesh()
        self._mesh = mesh
        n_dev = int(np.prod(list(mesh.shape.values())))
        # memoized per graph like _device_ell (same one-resident-key
        # eviction): a sweep over sharded grid points reuses ONE upload
        cap = self._cap(cfg)
        key = (tuple(d.id for d in mesh.devices.flat),
               _resolve_max_deg(graph, cap))
        cache = getattr(graph, "_sharded_ell_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(graph, "_sharded_ell_cache", cache)
        if key not in cache:
            cache.clear()
            idx, w, w_self = to_ell(graph, max_deg=cap)
            feats, labels = graph.feats, graph.labels
            pad = (-graph.n) % n_dev
            if pad:               # zero-weight rows aggregate to zero
                idx = np.pad(idx, ((0, pad), (0, 0)))
                w = np.pad(w, ((0, pad), (0, 0)))
                w_self = np.pad(w_self, (0, pad))
                feats = np.pad(feats, ((0, pad), (0, 0)))
                labels = np.pad(labels, (0, pad))
            rows2 = sh.named((sh.NODES, None), mesh)
            rows1 = sh.named((sh.NODES,), mesh)
            repl = sh.named((None,), mesh)
            ell = (jax.device_put(np.ascontiguousarray(idx), rows2),
                   jax.device_put(np.ascontiguousarray(w), rows2),
                   jax.device_put(np.ascontiguousarray(w_self), rows1),
                   jax.device_put(np.ascontiguousarray(feats), rows2),
                   jax.device_put(np.ascontiguousarray(labels), rows1))
            cache[key] = (ell, repl, {}, int(np.count_nonzero(w)))
        self.ell, self._repl, self._splits, edges = cache[key]
        _count_ell(cfg, *self.ell[0].shape, edges, shards=n_dev)
        _count_attn(cfg, graph.feats.shape[1])
        tracing.count("hoisted_agg_layers", 0)   # every layer in the step
        self.feats_plan = None
        self.featshard_stats = None
        if cfg.feats_layout == "sharded" and cfg.use_agg_kernel:
            self.feats_plan = self._bind_featshard(graph, cfg, mesh, key,
                                                   n_dev)
        self.train_nodes = self.node_split("train")
        self.n_nodes = len(graph.train_nodes)
        return self

    def _bind_featshard(self, graph, cfg, mesh, key, n_dev):
        """Build (or reuse) the static featshard plan for this
        (ELL, mesh, C) and record the bind-time accounting the ISSUE's
        acceptance asserts on: per-device table bytes n·d/S + C·d and
        remote-gather bytes per aggregation call."""
        from repro.kernels.neighbor_agg.ops import build_featshard_plan
        pkey = key + (cfg.feat_cache_rows,)
        pcache = getattr(graph, "_featshard_plan_cache", None)
        if pcache is None:
            pcache = {}
            object.__setattr__(graph, "_featshard_plan_cache", pcache)
        if pkey not in pcache:
            # one-resident-key eviction like the ELL cache
            pcache.clear()
            idx_h, w_h, _ = to_ell(graph, max_deg=self._cap(cfg))
            pad = (-graph.n) % n_dev
            if pad:
                idx_h = np.pad(idx_h, ((0, pad), (0, 0)))
                w_h = np.pad(w_h, ((0, pad), (0, 0)))
            pcache[pkey] = build_featshard_plan(
                idx_h, w_h, graph.degrees, mesh,
                cache_rows=cfg.feat_cache_rows)
        fsplan = pcache[pkey]
        d = graph.feats.shape[1]
        item = np.dtype(G.agg_dtype(cfg, graph.feats.dtype)).itemsize
        st = dict(fsplan.stats)
        st["feat_table_bytes_per_device"] = \
            fsplan.table_bytes_per_device(d, item)
        st["feat_remote_gather_bytes"] = fsplan.remote_bytes_per_call(
            d, item)
        self.featshard_stats = st
        return fsplan

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        idx, w, w_self, feats, labels, train_nodes, mesh, fsplan = consts
        logits = G.full_graph_forward(params, cfg, feats, idx, w, w_self,
                                      mesh=mesh, feats_plan=fsplan)
        lt = logits[train_nodes]
        return G.gnn_loss(lt, labels[train_nodes], cfg.loss,
                          cfg.n_classes)

    def loss_consts(self):
        # the mesh (static) and the featshard plan (a pytree: arrays as
        # step arguments, layout static) ride along so the forward can
        # shard_map the kernel path over the NODES axis
        return tuple(self.ell) + (self.train_nodes, self._mesh,
                                  self.feats_plan)

    def node_split(self, which: str):
        if which not in self._splits:
            self._splits[which] = jax.device_put(
                getattr(self.graph, f"{which}_nodes"), self._repl)
        return self._splits[which]

    def place(self, tree):
        from repro import sharding as sh
        repl = sh.named((), self._mesh)          # P(): any-rank replicate
        return jax.tree.map(lambda a: jax.device_put(a, repl), tree)


class SampledSource(BatchSource):
    """The paper's mini-batch paradigm: per-iteration (b, beta) fan-out
    trees from the vectorized CSR sampler, optionally produced ahead of
    the device step by a background ``Prefetcher`` thread.

    A batch carries each hop's node ids, not its feature rows: the
    compiled step gathers the rows from the device-resident feature
    table (``_device_base``, the copy evaluation already holds), which
    it takes as an argument.  Staging range-checks the ids (a device
    gather would clip an out-of-range id silently) and counts them as
    ``device_gather_rows``.

    Device uploads go through a ``HostStagingRing``: host staging buffers
    are allocated ONCE per shape and recycled across batches (the ring
    slot is released in ``done`` once the consuming step has synced; with
    the engine's deferred loss sync that release lags one extra step, so
    the ring grows by one slot).  Ids (cast to int32), masks (bool->f32),
    weights and labels are copied straight into the slot's buffers, so
    the plain path's fresh per-batch allocations disappear; with
    ``prefetch`` that staging work runs on the Prefetcher's worker
    thread, off the device step's critical path.  The whole batch then
    ships as a single ``jax.device_put`` pytree transfer instead of
    ~4·n_layers separate ``jnp.asarray`` uploads.

    When the graph has fewer training nodes than the configured batch
    size, every batch is PADDED up to ``batch_size`` with masked-out
    rows (zero weights, zero labels, a validity column), so the grid
    point still compiles exactly one step function; the masked loss
    matches the unpadded mean up to float summation order."""

    name = "minibatch"

    def __init__(self, batch_size: Optional[int] = None,
                 fanouts: Optional[Sequence[int]] = None,
                 prefetch: bool = True, depth: int = 2,
                 reuse_buffers: bool = True):
        self.batch_size = batch_size
        self.fanouts = tuple(fanouts) if fanouts is not None else None
        self.prefetch = prefetch
        self.depth = depth
        self.reuse_buffers = reuse_buffers
        self._pf: Optional[Prefetcher] = None
        self._ring: Optional[HostStagingRing] = None
        self._inflight: List[int] = []   # staging slots awaiting done()
        self._consumed = 0               # batches delivered so far
        self._last_rng_state = None      # rng state after last delivery
        self._resume_rng_state = None    # restored position (resume)

    def bind(self, graph, cfg, plan):
        self.graph, self.cfg = graph, cfg
        self._consumed = 0
        self._last_rng_state = None
        self._resume_rng_state = None
        n_train = len(graph.train_nodes)
        if n_train == 0:
            raise ValueError(
                f"{type(self).__name__}: graph has no training nodes "
                f"(train_mask selects 0 of {graph.n}) — nothing to sample")
        # b_request is what the sampler draws; b is the fixed compiled
        # width every batch pads up to (subclasses may round b up, e.g.
        # to a mesh-size multiple, without over-sampling targets)
        self.b_request = self.b = self.batch_size or cfg.batch_size
        if self.b < 1:
            raise ValueError(f"{type(self).__name__}: batch_size must be "
                             f">= 1, got {self.b}")
        self.fanouts = self.fanouts or tuple(cfg.fanout)
        assert len(self.fanouts) == cfg.n_layers
        self.n_iters = plan.n_iters
        self.seed = plan.seed
        self.pad = max(0, self.b - n_train)
        self._inflight = []
        if self.reuse_buffers:
            # slots outnumber in-flight batches: queue depth + the batch
            # on the device + the one being staged on the worker (+ one
            # more when the engine recycles a step late under deferred
            # loss sync)
            extra = 1 if _deferred_mode(plan) else 0
            self._ring = HostStagingRing(self.depth + 2 + extra)
        self.feats = _device_base(graph)[0]
        return self

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        (feats,) = consts
        if len(batch) == 6:              # padded batch: masked mean
            ids, masks, weights, self_w, labels, valid = batch
        else:
            ids, masks, weights, self_w, labels = batch
            valid = None
        logits = G.minibatch_forward(params, cfg, _gather_hops(feats, ids),
                                     masks, weights, self_w)
        return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes,
                          valid=valid)

    def loss_consts(self):
        # the device feature table the step gathers hop rows from
        return (self.feats,)

    def loss(self, params, batch):
        return type(self)._loss_impl(params, batch, self.loss_consts(),
                                     self.cfg)

    # -- host-side batch assembly --------------------------------------
    def _pad_batch(self, fb: FanoutBatch) -> FanoutBatch:
        """Pad the target-node axis up to ``self.b`` with masked-out rows
        so every batch of this grid point has ONE compiled shape."""
        p = self.b - fb.batch_size
        if p <= 0:
            return fb

        def padrow(a):
            return np.pad(a, [(0, p)] + [(0, 0)] * (a.ndim - 1))

        return FanoutBatch(
            nodes=[padrow(x) for x in fb.nodes],
            masks=[padrow(m) for m in fb.masks],
            weights=[padrow(w) for w in fb.weights],
            self_w=[padrow(s) for s in fb.self_w],
            labels=padrow(fb.labels),
            target_w=(padrow(fb.target_w)
                      if fb.target_w is not None else None))

    # -- subclass hooks ------------------------------------------------
    def _sample(self, rng, graph, batch_size, fanouts) -> FanoutBatch:
        """How one batch is drawn (Prefetcher-compatible signature).
        Subclasses override for non-uniform target selection."""
        return sample_batch(rng, graph, batch_size, fanouts)

    def _extra_cols(self, fb: FanoutBatch, valid_n: int) -> Tuple:
        """Columns appended after ``labels`` in the host batch tuple
        (``_loss_impl`` must unpack in the same order)."""
        if not self.pad:
            return ()
        valid = np.zeros(self.b, np.float32)
        valid[:valid_n] = 1.0
        return (valid,)

    def _host_batch(self, graph, fb):
        """Host tuple for one batch.  Returns ``(slot, host_tree)`` —
        slot is -1 on the plain (no-ring) path.  Runs on the Prefetcher
        worker thread when prefetching."""
        valid_n = fb.batch_size
        fb = self._pad_batch(fb)
        extra: Tuple = tuple(self._extra_cols(fb, valid_n))
        for ids in fb.nodes:
            # the step's gather clips an out-of-range id to a real row,
            # so it has to fail here, as a host gather of the rows did
            if ids.size and (ids.min() < 0 or ids.max() >= graph.n):
                raise IndexError(
                    f"sampled node ids outside [0, {graph.n}): "
                    f"{ids.min()}..{ids.max()}")
        # the lists (ids, masks, weights, self_w) and the single arrays
        # (labels, extra columns), each with the dtype it ships in
        lists = [(fb.nodes, np.int32), (fb.masks, np.float32),
                 (fb.weights, None), (fb.self_w, None)]
        singles = [fb.labels, *extra]
        if self._ring is None:
            host = (tuple([a.astype(dt or a.dtype, copy=False)
                           for a in arrs] for arrs, dt in lists)
                    + tuple(singles))
            slot = -1
        else:
            specs = ([(a.shape, dt or a.dtype)
                      for arrs, dt in lists for a in arrs]
                     + [(a.shape, a.dtype) for a in singles])
            with tracing.span("ring_wait"):
                slot = self._ring.acquire()
            try:
                bufs = iter(self._ring.buffers(slot, specs))

                def fill(a):          # copy (and cast) into the slot
                    buf = next(bufs)
                    np.copyto(buf, a, casting="same_kind")
                    return buf

                host = (tuple([fill(a) for a in arrs] for arrs, _ in lists)
                        + tuple(fill(a) for a in singles))
            except BaseException:
                # a worker dying mid-batch must not strand its staging
                # slot: the consuming step never runs, so done() would
                # never release it and the ring would leak one slot per
                # failure
                self._ring.release(slot)
                raise
        tracing.count("device_gather_rows",
                      sum(ids.size for ids in fb.nodes))
        return slot, host

    def _to_device(self, payload):
        """One device_put for the whole batch; the ring slot joins an
        in-flight FIFO (batches complete in order) and is recycled by
        ``done`` once the consuming step has synced."""
        slot, host = payload
        if slot >= 0:
            self._inflight.append(slot)
        return jax.device_put(host)

    def state_dict(self):
        return {"consumed": self._consumed,
                "rng_state": self._last_rng_state}

    def load_state_dict(self, sd):
        if not sd:
            return
        self._consumed = int(sd["consumed"])
        self._resume_rng_state = sd.get("rng_state")
        if self._consumed and self._resume_rng_state is None:
            raise ValueError(
                f"{type(self).__name__}: checkpoint records "
                f"{self._consumed} consumed batches but no rng state — "
                f"cannot resume the stream exactly")

    def batches(self):
        # resume-aware: a restored stream starts at batch `_consumed`
        # with the rng fast-forwarded to the checkpointed state, so the
        # sequence continues bit-for-bit where the checkpoint left off.
        # A batch's id in the tracing spans is its index in the stream,
        # the iteration that consumes it.
        remaining = self.n_iters - self._consumed
        if self.prefetch:
            self._pf = Prefetcher(self.graph, self.b_request, self.fanouts,
                                  seed=self.seed, depth=self.depth,
                                  n_batches=remaining,
                                  payload_fn=self._host_batch,
                                  sample_fn=self._sample,
                                  rng_state=self._resume_rng_state,
                                  first_batch=self._consumed)
            try:
                for _ in range(remaining):
                    fb, payload = self._pf.next()
                    self._last_rng_state = self._pf.last_rng_state
                    with tracing.span("device_put", self._consumed):
                        batch = self._to_device(payload)
                    self._consumed += 1
                    yield batch, fb.batch_size
            finally:
                self.close()
        else:
            rng = np.random.default_rng(self.seed)
            if self._resume_rng_state is not None:
                rng.bit_generator.state = self._resume_rng_state
            for _ in range(remaining):
                i = self._consumed
                with tracing.span("sample", i):
                    fb = self._sample(rng, self.graph, self.b_request,
                                      self.fanouts)
                self._last_rng_state = rng.bit_generator.state
                with tracing.span("stage", i):
                    payload = self._host_batch(self.graph, fb)
                with tracing.span("device_put", i):
                    batch = self._to_device(payload)
                self._consumed += 1
                yield batch, fb.batch_size

    def done(self, batch) -> None:
        if self._ring is not None and self._inflight:
            self._ring.release(self._inflight.pop(0))

    def close(self) -> None:
        # idempotent: an early-stopping callback and the Trainer's
        # finally may both land here without racing the worker thread
        if self._ring is not None:
            self._ring.close()     # wakes a worker blocked in acquire()
        if self._pf is not None:
            pf, self._pf = self._pf, None
            pf.close()


class ImportanceSampledSource(SampledSource):
    """Mini-batch SGD with NON-uniform target selection: targets are
    drawn WITH replacement from the training split with probability
    p_j ∝ score_j, and every sampled row carries the loss weight
    w_j = 1 / (n_train · p_j), so the weighted batch mean stays an
    UNBIASED estimator of the full training objective
    (E[1/b Σ w_j ℓ_j] = 1/n Σ ℓ_i) no matter how skewed — or how far
    from summing to one — the scores are.

    ``scores`` selects the proposal ("The Case for Sampling", Serafini
    & Guan 2021 — sampling design changes both convergence and cost):

    - ``"degree"`` (default): (deg + 1) ** alpha — high-degree nodes,
      whose fan-out trees are the expensive ones, are visited more
      often but down-weighted accordingly;
    - ``"grad"``: per-node gradient norm ‖∂ℓ_i/∂logits_i‖ at the
      plan-seed init params (one full-graph forward at bind time) — a
      cheap static proxy for gradient-norm importance sampling;
    - an array of per-node (length n) or per-train-node (length
      n_train) non-negative scores — e.g. gradient norms refreshed from
      a pilot run.  Zero scores are floored to a tiny positive value:
      a node with p_j = 0 would never be sampled and the estimator
      would silently drop its loss term.

    Sampling WITH replacement means any ``batch_size`` is valid —
    b > n_train never pads, it just revisits nodes (weights keep the
    estimator honest).  Everything else (Prefetcher, HostStagingRing,
    pad/donate/deferred-sync fast path) is inherited from
    ``SampledSource``.
    """

    name = "importance"

    def __init__(self, batch_size: Optional[int] = None,
                 fanouts: Optional[Sequence[int]] = None,
                 scores="degree", alpha: float = 1.0, **kw):
        super().__init__(batch_size, fanouts, **kw)
        self.scores = scores
        self.alpha = alpha

    def bind(self, graph, cfg, plan):
        super().bind(graph, cfg, plan)
        train = graph.train_nodes
        if isinstance(self.scores, str):
            if self.scores == "degree":
                s = (graph.degrees[train] + 1.0) ** self.alpha
            elif self.scores == "uniform":
                s = np.ones(len(train), np.float64)
            elif self.scores == "grad":
                s = self._grad_norm_scores(graph, cfg, plan)
            else:
                raise ValueError(
                    f"ImportanceSampledSource: unknown scores mode "
                    f"{self.scores!r} (have: degree, uniform, grad, or an "
                    f"array)")
        else:
            s = np.asarray(self.scores, np.float64).reshape(-1)
            if s.shape[0] == graph.n:
                s = s[train]
            if s.shape[0] != len(train):
                raise ValueError(
                    f"ImportanceSampledSource: scores must have length "
                    f"n={graph.n} or n_train={len(train)}, got "
                    f"{s.shape[0]}")
        if not np.all(np.isfinite(s)) or (s < 0).any() or s.sum() <= 0:
            raise ValueError(
                "ImportanceSampledSource: scores must be finite, "
                "non-negative, with a positive sum")
        if (s == 0).any():              # p_j = 0 would bias the estimator
            s = np.where(s > 0, s, s[s > 0].min() * 1e-6)
        p = s / s.sum()
        self._p = p
        self._train = train
        # E_p[w] = Σ p_j / (n p_j) = 1: uniform scores give weight 1.0
        self._w = (1.0 / (len(train) * p)).astype(np.float32)
        # replacement always fills b_request rows, so padding exists
        # only when a subclass rounds the compiled width up (the valid
        # column below masks those rows)
        self.pad = self.b - self.b_request
        return self

    def _grad_norm_scores(self, graph, cfg, plan):
        """‖∂ℓ_i/∂logits_i‖ per train node at the plan-seed init params
        (softmax(z) − onehot for CE, z − onehot for MSE)."""
        idx, w, w_self, feats, labels = _device_ell(graph)
        params = G.init_gnn(jax.random.key(plan.seed), cfg,
                            graph.feats.shape[1])
        logits = np.asarray(G.full_graph_forward(
            params, _static_cfg(cfg), feats, idx, w, w_self))
        tr = graph.train_nodes
        lt = logits[tr].astype(np.float64)
        onehot = np.zeros_like(lt)
        onehot[np.arange(len(tr)), graph.labels[tr]] = 1.0
        if cfg.loss == "mse":
            g = lt - onehot
        else:
            e = np.exp(lt - lt.max(axis=1, keepdims=True))
            g = e / e.sum(axis=1, keepdims=True) - onehot
        return np.linalg.norm(g, axis=1)

    def _sample(self, rng, graph, batch_size, fanouts):
        # batch_size is b_request per the hook contract — a subclass
        # that rounds self.b up must not over-sample targets
        sel = rng.choice(len(self._train), size=batch_size, replace=True,
                         p=self._p)
        fb = expand_batch(rng, graph,
                          self._train[sel].astype(np.int32), fanouts)
        fb.target_w = self._w[sel]
        return fb

    def _extra_cols(self, fb, valid_n):
        valid = np.zeros(self.b, np.float32)
        valid[:valid_n] = 1.0
        return (valid, fb.target_w)

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        (feats,) = consts
        ids, masks, weights, self_w, labels, valid, row_w = batch
        logits = G.minibatch_forward(params, cfg, _gather_hops(feats, ids),
                                     masks, weights, self_w)
        return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes,
                          valid=valid, weight=row_w)


class ShardedSampledSource(SampledSource):
    """Data-parallel mini-batches: the sampled batch's target axis is
    laid out over the ``NODES`` axis of a local device mesh — the
    mini-batch twin of ``ShardedFullGraphSource``.  The host side is
    inherited unchanged (CSR sampler, Prefetcher, per-shape
    ``HostStagingRing``); only the upload differs: every leaf of the
    batch pytree is ``device_put`` with a NODES-sharded leading axis
    (``sharding.row_sharding``), so XLA GSPMD partitions the fan-out
    tree forward per device shard and all-reduces the gradients.  The
    feature table the step gathers hop rows from is replicated over the
    mesh, so each device gathers the rows of its own ids.

    ``b`` is rounded UP to a multiple of the mesh size; the surplus
    rows ride the engine's existing masked-row padding (the valid
    column keeps the loss equal to the unpadded mean).  On a 1-device
    mesh the host batches, the compiled step, and therefore the loss
    sequence are identical to ``SampledSource`` (test-enforced
    bit-for-bit).  With ``cfg.use_agg_kernel`` each shard runs the
    tiled Pallas kernel on its local rows of the fan-out tree
    (collective-free — the gather table derives from the row-sharded
    batch).
    """

    name = "minibatch_sharded"

    def __init__(self, batch_size: Optional[int] = None,
                 fanouts: Optional[Sequence[int]] = None, mesh=None, **kw):
        super().__init__(batch_size, fanouts, **kw)
        self.mesh = mesh

    def bind(self, graph, cfg, plan):
        from repro import sharding as sh
        super().bind(graph, cfg, plan)
        mesh = self.mesh if self.mesh is not None else sh.node_mesh()
        self._mesh = mesh
        n_dev = int(np.prod(list(mesh.shape.values())))
        if self.b % n_dev:               # surplus rows are masked out
            self.b += (-self.b) % n_dev
        self.pad = max(0, self.b - min(self.b_request,
                                       len(graph.train_nodes)))
        self._repl = sh.named((None,), mesh)
        self.feats = jax.device_put(self.feats, self._repl)
        self._row_shardings: dict = {}
        self._repl_splits: dict = {}
        # feats_layout="sharded": sampled fan-outs change every step, so
        # the hot set is the LRU variant — a host-side cache model over
        # the per-batch source-node ids (counted on the Prefetcher
        # worker, surfaced through History.counters / bench columns)
        self.feat_cache = None
        if cfg.feats_layout == "sharded":
            from repro.core.featcache import (LRURowCache,
                                              resolve_cache_rows)
            self.feat_cache = LRURowCache(
                resolve_cache_rows(cfg.feat_cache_rows, graph.n),
                row_bytes=graph.feats.shape[1]
                * graph.feats.dtype.itemsize)
        return self

    def _host_batch(self, graph, fb):
        if self.feat_cache is not None:
            # single-threaded by construction: one Prefetcher worker (or
            # inline when prefetch is off) stages every batch in order
            for ids in fb.nodes:
                self.feat_cache.lookup(ids.reshape(-1))
        return super()._host_batch(graph, fb)

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        feats, mesh = consts
        if len(batch) == 6:              # padded batch: masked mean
            ids, masks, weights, self_w, labels, valid = batch
        else:
            ids, masks, weights, self_w, labels = batch
            valid = None
        # a replicated table gathered by row-sharded ids: each device
        # gathers its own rows, no collective
        logits = G.minibatch_forward(params, cfg, _gather_hops(feats, ids),
                                     masks, weights, self_w, mesh=mesh)
        return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes,
                          valid=valid)

    def loss_consts(self):
        # the replicated table, and the static mesh for the shard_map'd
        # kernel path
        return (self.feats, self._mesh)

    def _row_sharding(self, ndim: int):
        from repro import sharding as sh
        s = self._row_shardings.get(ndim)
        if s is None:
            s = sh.row_sharding(self._mesh, ndim)
            self._row_shardings[ndim] = s
        return s

    def _to_device(self, payload):
        slot, host = payload
        if slot >= 0:
            self._inflight.append(slot)
        return jax.device_put(
            host, jax.tree.map(lambda a: self._row_sharding(a.ndim), host))

    def node_split(self, which: str):
        # replicated over the mesh so eval mixes cleanly with the
        # mesh-committed params the sharded step produces
        if which not in self._repl_splits:
            self._repl_splits[which] = jax.device_put(
                getattr(self.graph, f"{which}_nodes"), self._repl)
        return self._repl_splits[which]

    def place(self, tree):
        from repro import sharding as sh
        repl = sh.named((), self._mesh)          # P(): any-rank replicate
        return jax.tree.map(lambda a: jax.device_put(a, repl), tree)


class ClusterSource(BatchSource):
    """Cluster-GCN style batching: partition once (greedy BFS,
    ``core.partition`` — no METIS dependency), then every iteration
    trains on the induced subgraph of a union of k clusters.  Against
    node-wise (b, β) fan-out sampling this trades neighbor explosion
    for a bounded, reusable batch structure: each cluster's induced ELL
    block is built ONCE at bind and batches assemble block-diagonally
    (cross-cluster edges are dropped — vanilla Cluster-GCN's documented
    approximation).

    The batch is a fixed-shape padded ELL ([m_max, K] with m_max = the
    k largest clusters stacked, K = the widest induced block), so every
    grid point compiles exactly ONE step like the other sources, and
    donation/deferred-sync apply unchanged.  The loss runs the
    FULL-GRAPH forward on the batch-local ELL and masks to the batch's
    training rows (padding and non-train rows carry zero ``valid``).
    Batches with zero training rows are rejection-resampled (bind
    fails fast if NO cluster contains a training node).
    """

    name = "cluster"

    def __init__(self, batch_size: Optional[int] = None,
                 clusters_per_batch: int = 2,
                 n_parts: Optional[int] = None, partition_seed: int = 0):
        if clusters_per_batch < 1:
            raise ValueError(f"ClusterSource: clusters_per_batch must be "
                             f">= 1, got {clusters_per_batch}")
        if n_parts is not None and n_parts < 1:
            raise ValueError(f"ClusterSource: n_parts must be >= 1, got "
                             f"{n_parts}")
        self.batch_size = batch_size
        self.clusters_per_batch = clusters_per_batch
        self.n_parts = n_parts
        self.partition_seed = partition_seed
        self._pf: Optional[Prefetcher] = None

    def bind(self, graph, cfg, plan):
        from repro.core.partition import bfs_partition, cluster_ell_blocks
        self.graph, self.cfg = graph, cfg
        self.b = self.batch_size or cfg.batch_size
        k = self.clusters_per_batch
        if self.n_parts is None:
            # expected union size ≈ b: n/P nodes per cluster, k per batch
            n_parts = int(round(graph.n * k / max(self.b, 1)))
        else:
            n_parts = self.n_parts
        n_parts = min(max(n_parts, k), graph.n)
        part = bfs_partition(graph, n_parts, seed=self.partition_seed)
        blocks = cluster_ell_blocks(graph, part)
        self.blocks = blocks
        self.n_parts_ = len(blocks.clusters)
        self.k = min(k, self.n_parts_)
        self._train_valid = [graph.train_mask[c].astype(np.float32)
                             for c in blocks.clusters]
        self._has_train = np.array([v.sum() > 0 for v in self._train_valid])
        if not self._has_train.any():
            raise ValueError(
                "ClusterSource: no cluster contains a training node "
                f"(n_train={len(graph.train_nodes)}) — nothing to train on")
        sizes = blocks.sizes
        self.m_max = int(np.sort(sizes)[::-1][:self.k].sum())
        self.K = blocks.max_width
        self._feats = [graph.feats[c] for c in blocks.clusters]
        self._labels = [graph.labels[c].astype(np.int32)
                        for c in blocks.clusters]
        self.n_iters = plan.n_iters
        self.seed = plan.seed
        self._consumed = 0
        self._last_rng_state = None
        self._resume_rng_state = None
        return self

    @staticmethod
    def _loss_impl(params, batch, consts, cfg: GNNConfig):
        idx, w, w_self, feats, labels, valid = batch
        logits = G.full_graph_forward(params, cfg, feats, idx, w, w_self)
        return G.gnn_loss(logits, labels, cfg.loss, cfg.n_classes,
                          valid=valid)

    def loss(self, params, batch):
        return type(self)._loss_impl(params, batch, self.loss_consts(),
                                     self.cfg)

    def _assemble(self, chosen):
        """Block-diagonal union of the chosen clusters, padded to the
        fixed (m_max, K) compile shape."""
        fd = self.graph.feats.shape[1]
        idx = np.zeros((self.m_max, self.K), np.int32)
        w = np.zeros((self.m_max, self.K), np.float32)
        w_self = np.zeros(self.m_max, np.float32)
        feats = np.zeros((self.m_max, fd), self.graph.feats.dtype)
        labels = np.zeros(self.m_max, np.int32)
        valid = np.zeros(self.m_max, np.float32)
        off = 0
        for ci in chosen:
            bi, bw = self.blocks.idx[ci], self.blocks.w[ci]
            mc, kc = bi.shape
            # local ids -> batch-local ids; padded entries (weight 0)
            # offset too, staying in-range for the gather
            idx[off:off + mc, :kc] = bi + off
            w[off:off + mc, :kc] = bw
            w_self[off:off + mc] = self.blocks.w_self[ci]
            feats[off:off + mc] = self._feats[ci]
            labels[off:off + mc] = self._labels[ci]
            valid[off:off + mc] = self._train_valid[ci]
            off += mc
        return (idx, w, w_self, feats, labels, valid), int(valid.sum())

    def _sample_union(self, rng, graph, batch_size, fanouts):
        """One assembled host batch (Prefetcher ``sample_fn`` signature:
        assembly runs on the worker thread, off the step's critical
        path, from the single ordered rng stream)."""
        train_cluster = int(np.nonzero(self._has_train)[0][0])
        for _ in range(64):          # a batch needs >= 1 training row
            chosen = rng.choice(self.n_parts_, size=self.k,
                                replace=False)
            if self._has_train[chosen].any():
                break
        else:                        # pathological split: force one in
            chosen[0] = train_cluster
        return self._assemble(chosen)

    def state_dict(self):
        return {"consumed": self._consumed,
                "rng_state": self._last_rng_state}

    def load_state_dict(self, sd):
        if not sd:
            return
        self._consumed = int(sd["consumed"])
        self._resume_rng_state = sd.get("rng_state")
        if self._consumed and self._resume_rng_state is None:
            raise ValueError(
                "ClusterSource: checkpoint records "
                f"{self._consumed} consumed batches but no rng state — "
                "cannot resume the stream exactly")

    def batches(self):
        remaining = self.n_iters - self._consumed
        self._pf = Prefetcher(self.graph, self.k, (), seed=self.seed,
                              depth=2, n_batches=remaining,
                              payload_fn=lambda g, batch: None,
                              sample_fn=self._sample_union,
                              rng_state=self._resume_rng_state,
                              first_batch=self._consumed)
        try:
            for _ in range(remaining):
                (host, n_valid), _ = self._pf.next()
                self._last_rng_state = self._pf.last_rng_state
                with tracing.span("device_put", self._consumed):
                    batch = jax.device_put(host)
                self._consumed += 1
                yield batch, n_valid
        finally:
            self.close()

    def close(self) -> None:
        # idempotent: Trainer's finally and the batches() finally both
        # land here
        pf, self._pf = getattr(self, "_pf", None), None
        if pf is not None:
            pf.close()


# ---------------------------------------------------------------------------
# Callbacks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainState:
    """Mutable loop state handed to every callback hook."""
    graph: Graph
    cfg: GNNConfig
    plan: TrainPlan
    source: BatchSource
    history: History
    it: int = -1                      # current iteration (0-based)
    params: Any = None
    opt_state: Any = None
    loss: float = float("nan")        # this iteration's training loss
    val_acc: Optional[float] = None   # this iteration's eval (None = none)
    full_loss: Optional[float] = None  # precomputed tracked full loss
    n_nodes: int = 0                  # target nodes in this batch
    full_loss_fn: Optional[TCallable] = None   # params -> full objective
    stop: bool = False
    stop_reason: Optional[str] = None
    step_bad: bool = False            # this step tripped the NaN guard
    rollback_pending: bool = False    # BadStepPolicy requested a restore

    def request_stop(self, reason: str) -> None:
        if not self.stop:
            self.stop, self.stop_reason = True, reason


class Callback:
    """Hooks fire in list order; ``on_eval`` only on eval iterations,
    ``on_stop`` once when any callback requested a stop.

    Reading ``state.params`` inside a hook is always safe; a hook that
    RETAINS the arrays past its return must copy them first
    (``jax.tree.map(jnp.copy, state.params)``) — with the default
    ``plan.donate`` the next step donates those buffers (see
    docs/training_api.md "Throughput knobs")."""

    def on_train_start(self, state: TrainState) -> None: ...

    def on_step(self, state: TrainState) -> None: ...

    def on_eval(self, state: TrainState) -> None: ...

    def on_stop(self, state: TrainState) -> None: ...

    def on_train_end(self, state: TrainState) -> None: ...


class HistoryCallback(Callback):
    """Absorbs the loops' metric recording: per-iteration History rows
    plus full-objective tracking (every iteration for full-graph GD,
    every ``track_full_loss_every`` iterations for mini-batch; the
    Trainer pre-dispatches the tracked value on those iterations so the
    deferred-sync pipeline stays unbroken — ``state.full_loss``)."""

    def on_train_start(self, state):
        state.history.start()

    def on_step(self, state):
        state.history.record(state.loss, state.val_acc,
                             nodes=state.n_nodes)
        if state.step_bad:
            state.history.bad_steps.append(state.it + 1)
        if state.source.loss_is_full_loss:
            # full-graph training: the per-iteration loss IS the full loss
            state.history.full_losses.append(state.loss)
            state.history.full_loss_iters.append(state.it + 1)
        elif (state.plan.track_full_loss_every
              and state.it % state.plan.track_full_loss_every == 0):
            fl = (state.full_loss if state.full_loss is not None
                  else float(state.full_loss_fn(state.params)))
            state.history.full_losses.append(fl)
            state.history.full_loss_iters.append(state.it + 1)

    def on_train_end(self, state):
        # feature-shard / hot-cache accounting: bind-time plan stats
        # (full-graph) or the host LRU's run totals (sampled) land as
        # run-level counters next to the per-iteration series
        st = getattr(state.source, "featshard_stats", None)
        if st:
            state.history.counters.update(st)
        fc = getattr(state.source, "feat_cache", None)
        if fc is not None:
            state.history.counters.update(fc.stats())


class EarlyStop(Callback):
    """The loops' stop rules: batch loss <= target_loss (checked every
    step, AFTER recording — the crossing iteration stays in History) and
    val acc >= target_acc (checked on eval iterations)."""

    def on_step(self, state):
        tl = state.plan.target_loss
        if tl is not None and state.loss <= tl:
            state.request_stop(f"target_loss<={tl}")

    def on_eval(self, state):
        ta = state.plan.target_acc
        if ta is not None and state.val_acc is not None \
                and state.val_acc >= ta:
            state.request_stop(f"target_acc>={ta}")


def save_trainer_state(state: TrainState, final: bool = False) -> str:
    """One exact-resume snapshot: params + opt_state in the npz, the
    engine state (iteration, source stream position/rng, History) in the
    step's metadata JSON.  ``Trainer.run(resume_from=...)`` restores all
    of it and continues bit-for-bit identical to an uninterrupted run
    (test-enforced goldens)."""
    from repro.checkpoint import save_checkpoint
    meta = {
        "loss": state.loss, "it": state.it, "source": state.source.name,
        "engine_state": {
            "format": 1,
            "it": state.it,
            "seed": state.plan.seed,
            "source": state.source.name,
            "source_state": state.source.state_dict(),
            "history": state.history.to_dict(),
        },
    }
    if final:
        meta["final"] = True
    return save_checkpoint(
        state.plan.ckpt_dir, state.it,
        {"params": state.params, "opt_state": state.opt_state},
        meta, keep_last=state.plan.ckpt_keep_last or None)


class CheckpointCallback(Callback):
    """Periodic TrainerState checkpointing via ``repro.checkpoint``
    (same cadence semantics as launch/train.py's LM loop: skips step 0).
    Each save is a full exact-resume snapshot — params AND opt_state,
    source rng/stream position, History, iteration — not just params,
    so a restored run is the run the convergence curves describe."""

    def on_step(self, state):
        every = state.plan.ckpt_every
        if every and state.it and state.it % every == 0:
            save_trainer_state(state)

    def on_train_end(self, state):
        if state.plan.ckpt_every:
            save_trainer_state(state, final=True)


def default_callbacks(plan: TrainPlan) -> List[Callback]:
    cbs: List[Callback] = [HistoryCallback(), EarlyStop()]
    if plan.ckpt_every:
        cbs.append(CheckpointCallback())
    return cbs


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainResult:
    params: list
    history: History
    final_test_acc: float
    stop_reason: Optional[str] = None


class Trainer:
    """The single training engine both paradigms run through.

    Per iteration: jitted step (value_and_grad over the source's loss +
    optimizer update, params/opt_state/batch donated) -> periodic
    full-neighborhood eval -> ``on_step`` callbacks (History /
    early-stop / checkpoint) -> ``on_eval`` on eval iterations -> break
    when any callback requested a stop.  With ``plan.deferred_sync``
    the host-side readback of a record lags one iteration so the next
    step dispatches while the previous one is still in flight.
    """

    def __init__(self, graph: Graph, cfg: GNNConfig, plan: TrainPlan,
                 source: Optional[BatchSource] = None,
                 callbacks: Optional[Sequence[Callback]] = None,
                 extra_callbacks: Sequence[Callback] = ()):
        self.graph, self.cfg, self.plan = graph, cfg, plan
        tracing.clear()                  # the log holds this run alone
        self.source = (source or SampledSource()).bind(graph, cfg, plan)
        self.callbacks = (list(callbacks) if callbacks is not None
                          else default_callbacks(plan))
        self.callbacks += list(extra_callbacks)
        if plan.bad_steps.needs_ckpt() and not plan.ckpt_every:
            raise ValueError(
                "BadStepPolicy escalates to rollback but plan.ckpt_every "
                "is 0 — there would never be a checkpoint to roll back "
                "to; set ckpt_every (and ckpt_dir) or use "
                "on_bad='skip'/'raise'")
        self._consec_bad = 0             # consecutive guard-tripped steps
        self._n_rollbacks = 0
        self.opt = plan.make_optimizer()
        self._scfg = _static_cfg(cfg)
        # evaluation + full-loss tracking reuse the source's ELL when it
        # has one (FullGraphSource with max_deg: eval on the SAME capped
        # adjacency, and no second full-width upload); otherwise the
        # config's ELL cap (a power-law graph's uncapped ELL is [n, d_max]
        # with d_max in the tens of thousands)
        self._ell = (getattr(self.source, "ell", None)
                     or _device_ell(graph, cfg.max_degree))
        # sharded sources + kernel: eval/full-loss partition the Pallas
        # aggregation over the source's mesh too (the kernel cannot be
        # GSPMD-partitioned; einsum-path runs keep mesh=None so their
        # module-level jit cache entries stay shared with plain sources)
        self._agg_mesh = (getattr(self.source, "_mesh", None)
                          if cfg.use_agg_kernel else None)
        # featshard sources: eval/full-loss reuse the bind-time plan so
        # they run on the same NODES-sharded table as the step
        self._feats_plan = getattr(self.source, "feats_plan", None)

        if type(self.source)._loss_impl is not None:
            # built-in sources: one compiled step per (source type,
            # normalized cfg, optimizer spec, consts) PER GRAPH — shared
            # across every Trainer a sweep creates
            self._step, self._step_arrays = _cached_step(
                graph, type(self.source), self.source.loss_consts(), cfg,
                plan)
        else:
            # custom source: per-Trainer jit over the instance loss
            src, opt = self.source, self.opt
            self._step_arrays = ()

            def step(params, opt_state, batch, arrs):
                loss, grads = jax.value_and_grad(
                    lambda p: src.loss(p, batch))(params)
                params, opt_state, good = _guarded_update(
                    opt, params, opt_state, loss, grads)
                return params, opt_state, loss, good

            self._step = jax.jit(
                step, donate_argnums=(0, 1) if plan.donate else ())

    # ------------------------------------------------------------------
    def _eval_dev(self, params, nodes):
        idx, w, w_self, feats, labels = self._ell
        return _eval_acc(params, self._scfg, idx, w, w_self, feats,
                         labels, nodes, self._agg_mesh, self._feats_plan)

    def _full_loss_dev(self, params):
        return _cached_full_loss(self.graph, self.cfg, self._ell,
                                 self.source.node_split("train"),
                                 mesh=self._agg_mesh,
                                 feats_plan=self._feats_plan)(params)

    def evaluate(self, params, nodes) -> float:
        return float(self._eval_dev(params, jnp.asarray(nodes)))

    def full_train_loss(self, params) -> float:
        return float(self._full_loss_dev(params))

    def close(self) -> None:
        """Release device references held by this Trainer (the per-graph
        ELL cache keeps at most one resident entry; sweeps call this
        between grid points)."""
        self._ell = None
        self._step_arrays = ()
        self.source.close()

    def _fire(self, hook: str, state: TrainState) -> None:
        for cb in self.callbacks:
            getattr(cb, hook)(state)

    # ------------------------------------------------------------------
    def _consume(self, rec, state: TrainState) -> None:
        """Read one step record back to host and fire its callbacks."""
        it, loss, val, fl, n_nodes, batch, good = rec
        state.it = it
        state.loss = float(loss)           # host sync: step finished
        state.step_bad = not bool(good)
        if state.step_bad:
            self._consec_bad += 1
        else:
            self._consec_bad = 0
        state.val_acc = float(val) if val is not None else None
        state.full_loss = float(fl) if fl is not None else None
        state.n_nodes = n_nodes
        self.source.done(batch)            # staging slot recyclable
        self._fire("on_step", state)
        if state.val_acc is not None:
            self._fire("on_eval", state)
        if state.step_bad:
            self._apply_bad_step_policy(state)

    def _apply_bad_step_policy(self, state: TrainState) -> None:
        """A guard-tripped step reached the host: decide what to do.

        The in-jaxpr guard already made the bad step an identity update,
        so under ``skip`` there is nothing to undo — the next step (which
        under ``deferred_sync`` has ALREADY dispatched from the kept
        params) simply resamples.  ``rollback`` restores the latest
        checkpoint once ``max_consecutive`` bad steps pile up."""
        pol = self.plan.bad_steps
        if pol.on_bad == "raise":
            raise NonFiniteStepError(state.it, state.loss,
                                     self._consec_bad)
        if self._consec_bad < pol.max_consecutive:
            return                         # plain skip-and-resample
        escalation = (pol.escalate if pol.on_bad == "skip"
                      else "rollback")
        if escalation == "rollback":
            state.rollback_pending = True
            return
        raise NonFiniteStepError(state.it, state.loss, self._consec_bad)

    def _rollback(self, state: TrainState):
        """Restore params/opt_state from the latest checkpoint after
        ``max_consecutive`` bad steps (bounded by ``max_rollbacks``)."""
        from repro.checkpoint import latest_step, restore_checkpoint
        pol = self.plan.bad_steps
        self._n_rollbacks += 1
        if self._n_rollbacks > pol.max_rollbacks:
            raise NonFiniteStepError(state.it, state.loss,
                                     self._consec_bad)
        step = latest_step(self.plan.ckpt_dir)
        if step is None:
            # bad steps piled up before the first checkpoint cadence —
            # there is nothing to restore, surface the divergence
            raise NonFiniteStepError(state.it, state.loss,
                                     self._consec_bad)
        warnings.warn(
            f"rolling back to checkpoint step {step} after "
            f"{self._consec_bad} consecutive non-finite steps "
            f"(rollback {self._n_rollbacks}/{pol.max_rollbacks})",
            RuntimeWarning, stacklevel=2)
        tree = restore_checkpoint(
            self.plan.ckpt_dir,
            {"params": state.params, "opt_state": state.opt_state},
            step=step)
        self._consec_bad = 0
        return (self.source.place(tree["params"]),
                self.source.place(tree["opt_state"]))

    def _restore_run_state(self, directory: str, params_like,
                           opt_like):
        """Load the latest TrainerState checkpoint for exact resume."""
        from repro.checkpoint import (latest_step, load_metadata,
                                      restore_checkpoint)
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"resume_from={directory!r}: no completed checkpoints")
        meta = load_metadata(directory, step) or {}
        es = meta.get("engine_state")
        if not es:
            raise ValueError(
                f"checkpoint step {step} in {directory!r} has no "
                f"engine_state — it was not written by the engine's "
                f"CheckpointCallback (params-only checkpoints cannot "
                f"be resumed exactly)")
        if es.get("seed") != self.plan.seed:
            warnings.warn(
                f"resuming a run recorded with seed={es.get('seed')} "
                f"under plan.seed={self.plan.seed}; the continued "
                f"batch stream follows the CHECKPOINT's stream state, "
                f"not the new seed", RuntimeWarning, stacklevel=2)
        tree = restore_checkpoint(
            directory, {"params": params_like, "opt_state": opt_like},
            step=step)
        self.source.load_state_dict(es.get("source_state", {}))
        history = History.from_dict(es.get("history", {}))
        return (self.source.place(tree["params"]),
                self.source.place(tree["opt_state"]),
                int(es["it"]) + 1, history)

    def run(self, resume_from: Optional[str] = None) -> TrainResult:
        graph, cfg, plan = self.graph, self.cfg, self.plan
        key = jax.random.key(plan.seed)
        params = self.source.place(G.init_gnn(key, cfg,
                                              graph.feats.shape[1]))
        opt_state = self.source.place(self.opt.init(params))
        history, start_it = History(), 0
        if resume_from is not None:
            params, opt_state, start_it, history = \
                self._restore_run_state(resume_from, params, opt_state)

        state = TrainState(graph=graph, cfg=cfg, plan=plan,
                           source=self.source, history=history,
                           params=params, opt_state=opt_state,
                           it=start_it - 1,     # last completed iteration
                           full_loss_fn=self._full_loss_dev)
        if history.losses:
            state.loss = history.losses[-1]
        self._fire("on_train_start", state)
        deferred = _deferred_mode(plan)
        track = plan.track_full_loss_every
        track_full = track and not self.source.loss_is_full_loss
        pending = None
        try:
            val_sel = self.source.node_split("val")
            stream = self.source.batches()
            for it in range(start_it, plan.n_iters):
                batch, n_nodes = next(stream)
                # tracing happens on the first call; the donated batch
                # pytree has no batch-shaped output to alias into, so
                # XLA reports it "not usable" — expected, suppressed
                # ONLY around the tracing call so real params/opt_state
                # donation misses stay visible
                with contextlib.ExitStack() as stack:
                    if it == start_it:
                        stack.enter_context(warnings.catch_warnings())
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                    params, opt_state, loss, good = self._step(
                        params, opt_state, batch, self._step_arrays)
                # eval / tracked full loss are DISPATCHED here (device
                # scalars); the floats are read in _consume
                val = (self._eval_dev(params, val_sel)
                       if it % plan.eval_every == 0 else None)
                fl = (self._full_loss_dev(params)
                      if track_full and it % track == 0 else None)
                rec = (it, loss, val, fl, n_nodes, batch, good)
                state.params, state.opt_state = params, opt_state
                if deferred:
                    # lagged sync: read record i-1 while step i flies
                    prev, pending = pending, rec
                    if prev is not None:
                        self._consume(prev, state)
                else:
                    self._consume(rec, state)
                if state.rollback_pending:
                    # rollback policies require ckpt_every>0, which
                    # forces sync mode — params here are the guard-kept
                    # (pre-divergence) values being replaced
                    params, opt_state = self._rollback(state)
                    state.params, state.opt_state = params, opt_state
                    state.rollback_pending = False
                if state.stop:
                    # the last batch drawn when the stop came (one past
                    # the stopping record under deferred sync)
                    tracing.note_stop(it)
                    break
            if pending is not None:
                # drain the lagged record so History stays aligned with
                # the params actually returned
                self._consume(pending, state)
            if state.stop:
                tracing.note_stop(state.it)
                self._fire("on_stop", state)
            acc = self.evaluate(params, self.source.node_split("test"))
            state.params = params
            self._fire("on_train_end", state)
        finally:
            self.source.close()
        return TrainResult(params, state.history, acc, state.stop_reason)
