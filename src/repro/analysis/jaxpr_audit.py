"""Jaxpr auditor: trace the REAL engine-bound step/eval/inference
functions for every committed sweep variant and walk the jaxprs for
hazard classes the bench suite cannot see.

The variants reuse the engine's own plumbing — sources are constructed
and ``bind``-ed exactly like ``experiment.make_source`` does (minus
worker threads: sampled sources run with ``prefetch=False,
reuse_buffers=False`` and the cluster batch is drawn through
``_sample_union`` directly), and the step comes out of
``engine._cached_step`` with the source's own ``loss_consts()`` (their
arrays as step arguments), so the audited jaxpr IS the jaxpr a sweep
compiles, not a lookalike.

Hazard classes (ISSUE 9):

* **f64 widening** — any equation producing a float64/complex64+
  output.  The repo is an f32/bf16 codebase; a float64 aval means a
  host constant or ``enable_x64`` leak doubled the hot path's bytes.
* **convert churn** — ``convert_element_type`` applied directly to the
  output of another ``convert_element_type``: a round-trip (A->B->A)
  is a wasted pass over the array (warning); other double-converts
  collapse to one and are reported as info.
* **constant capture** — host ``np.ndarray`` or device ``jax.Array``
  constants above a size threshold folded into the jaxpr.  Either bakes
  into the executable: a captured ELL or feature table makes a program
  of the table's size (2.4 GB at papers100M scale) that compiles slowly
  and that no compile cache keeps.  The engine passes its arrays as
  step arguments (``engine._split_consts``).
* **collectives outside shard_map** — psum/all_gather/... equations
  not nested under a ``shard_map`` body run under GSPMD semantics
  where they are almost always a tracing bug in this codebase.
* **donation feasibility** — donated params/opt_state leaves whose
  (shape, dtype) cannot alias any step output would silently disable
  buffer reuse (error); donated batch leaves are donated for early
  deallocation only and are tallied, not flagged.
* **retrace stability** — a fresh source instance bound to the same
  graph must (a) hit ``_cached_step``'s cache (same function object
  back) and (b) retrace to a byte-identical canonical
  jaxpr.  Either failing means a ``sweep()`` recompiles per grid
  point and every bench number downstream is measuring the compiler.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from .findings import Finding

#: collective primitives that must only appear under shard_map
COLLECTIVES = frozenset({
    "psum", "psum2", "all_gather", "all_to_all", "ppermute", "pbroadcast",
    "psum_scatter", "reduce_scatter", "pmin", "pmax", "pgather",
})

#: primitives that introduce a shard_map scope for everything below
_SPMD_SCOPES = frozenset({"shard_map"})

#: constants this large baked into a jaxpr are an executable-size hazard
HOST_CONST_BYTES = 4096

F64 = frozenset({"float64", "complex128"})


# ---------------------------------------------------------------------------
# variant cube (the committed sweep axes)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Variant:
    paradigm: str           # experiment.PARADIGMS name
    kernel: bool            # cfg.use_agg_kernel
    featshard: bool = False  # cfg.feats_layout == "sharded"
    model: str = "graphsage"

    @property
    def name(self) -> str:
        tags = [self.paradigm, "kernel" if self.kernel else "einsum"]
        if self.featshard:
            tags.append("featshard")
        if self.model != "graphsage":
            tags.append(self.model)
        return "+".join(tags)


def sweep_variants() -> List[Variant]:
    """Every committed sweep variant: paradigm x {einsum, kernel}, plus
    the featshard layout (only reachable on fullgraph_sharded x kernel),
    one gcn point covering the kernel's fused self-row epilogue and one
    gat point covering the per-head attention calls."""
    from repro.core.experiment import PARADIGMS
    vs = [Variant(p, k) for p in PARADIGMS for k in (False, True)]
    vs.append(Variant("fullgraph_sharded", True, featshard=True))
    vs.append(Variant("fullgraph", True, model="gcn"))
    vs.append(Variant("fullgraph", True, model="gat"))
    return vs


def audit_graph(n: int = 192, seed: int = 0):
    """Small synthetic graph with the presets' structure; tracing cost
    is shape-driven, so a small n keeps the full cube under CI budget
    while exercising identical code paths."""
    from repro.data.synth import make_preset
    return make_preset("arxiv-like", n=n, seed=seed)


def variant_cfg(graph, v: Variant):
    from repro.configs.base import GNNConfig
    return GNNConfig(
        name="analyze", model=v.model, n_nodes=graph.n,
        feat_dim=graph.feats.shape[1], hidden=16,
        n_classes=graph.n_classes, n_layers=2, fanout=(4, 3),
        batch_size=32, loss="ce", use_agg_kernel=v.kernel,
        agg_b_tile=8, agg_d_tile=16, agg_k_slab=2,
        feats_layout="sharded" if v.featshard else "replicated")


def _make_source(v: Variant, cfg):
    """Thread-free twin of ``experiment.make_source``: sampled sources
    take the plain (no Prefetcher / no staging ring) path so an audit
    never spawns a worker; the traced jaxpr is identical either way
    (prefetch only changes WHERE host staging runs)."""
    from repro.core import engine as E
    b, fo = cfg.batch_size, tuple(cfg.fanout)
    kw = dict(prefetch=False, reuse_buffers=False)
    if v.paradigm == "fullgraph":
        return E.FullGraphSource()
    if v.paradigm == "fullgraph_sharded":
        return E.ShardedFullGraphSource()
    if v.paradigm == "minibatch":
        return E.SampledSource(batch_size=b, fanouts=fo, **kw)
    if v.paradigm == "minibatch_sharded":
        return E.ShardedSampledSource(batch_size=b, fanouts=fo, **kw)
    if v.paradigm == "cluster":
        return E.ClusterSource(batch_size=b)
    if v.paradigm == "importance":
        return E.ImportanceSampledSource(batch_size=b, fanouts=fo, **kw)
    raise ValueError(f"unknown paradigm {v.paradigm!r}")


def _draw_batch(src, graph):
    """One device batch without starting any source thread."""
    import jax
    from repro.core import engine as E
    rng = np.random.default_rng(0)
    if isinstance(src, E.ClusterSource):
        host, _n_valid = src._sample_union(rng, graph, src.k, ())
        return jax.device_put(host)
    if isinstance(src, E.SampledSource):
        fb = src._sample(rng, graph, src.b_request, src.fanouts)
        return src._to_device(src._host_batch(graph, fb))
    return None                              # full-graph: batch is None


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _subjaxprs(params: Dict) -> Iterable[Tuple[Any, bool]]:
    """-> (sub-closed/open jaxpr, introduces_shard_map_scope)."""
    import jax.extend.core as jcore
    for val in params.values():
        stack = [val]
        while stack:
            x = stack.pop()
            if isinstance(x, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                yield x
            elif isinstance(x, (tuple, list)):
                stack.extend(x)


def _iter_eqns(jaxpr, in_spmd: bool = False):
    """Depth-first (eqn, inside_shard_map) over a (Closed)Jaxpr."""
    import jax.extend.core as jcore
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, in_spmd
        sub_spmd = in_spmd or eqn.primitive.name in _SPMD_SCOPES
        for sub in _subjaxprs(eqn.params):
            yield from _iter_eqns(sub, sub_spmd)


def _walk_hazards(closed, site: str) -> List[Finding]:
    """The per-jaxpr hazard walks shared by step/eval/inference."""
    import jax
    import jax.extend.core as jcore
    out: List[Finding] = []

    f64_counts: Dict[str, int] = {}
    f64_first: Dict[str, str] = {}
    churn_round = 0
    churn_other = 0
    stray_coll: Dict[str, int] = {}
    producers: Dict[Any, Any] = {}

    for eqn, in_spmd in _iter_eqns(closed):
        name = eqn.primitive.name
        for ov in eqn.outvars:
            dt = getattr(getattr(ov, "aval", None), "dtype", None)
            if dt is not None and str(dt) in F64:
                f64_counts[str(dt)] = f64_counts.get(str(dt), 0) + 1
                f64_first.setdefault(str(dt), name)
            producers[ov] = eqn
        if name in COLLECTIVES and not in_spmd:
            stray_coll[name] = stray_coll.get(name, 0) + 1
        if name == "convert_element_type":
            iv = eqn.invars[0]
            if isinstance(iv, jcore.Literal):
                continue
            prev = producers.get(iv)
            if prev is not None \
                    and prev.primitive.name == "convert_element_type":
                src_dt = prev.invars[0].aval.dtype \
                    if not isinstance(prev.invars[0], jcore.Literal) \
                    else prev.invars[0].aval.dtype
                if eqn.outvars[0].aval.dtype == src_dt:
                    churn_round += 1
                else:
                    churn_other += 1

    for dt, cnt in sorted(f64_counts.items()):
        out.append(Finding(
            "jaxpr", "error", site,
            f"{cnt} equation(s) produce {dt} (first: "
            f"{f64_first[dt]}) — implicit widening; the hot path is "
            f"f32/bf16 by design"))
    if churn_round:
        out.append(Finding(
            "jaxpr", "warning", site,
            f"{churn_round} convert_element_type round-trip(s) "
            "(A->B->A on the direct producer) — each one is a wasted "
            "full pass over the array"))
    if churn_other:
        out.append(Finding(
            "jaxpr", "info", site,
            f"{churn_other} chained convert_element_type pair(s) "
            "(A->B->C) that could collapse to one convert"))
    for name, cnt in sorted(stray_coll.items()):
        out.append(Finding(
            "jaxpr", "error", site,
            f"collective '{name}' appears {cnt}x OUTSIDE any shard_map "
            "scope — under plain GSPMD tracing this is a replicated "
            "all-reduce bug, not a partitioning hint"))

    # -- constants folded into the jaxpr --------------------------------
    for c in getattr(closed, "consts", ()):
        kind = "device jax.Array"
        if not isinstance(c, jax.Array):
            # a host array (jax wraps captured numpy tables in its own
            # typed-literal class; np.asarray unwraps either form)
            c, kind = np.asarray(c), "host np.ndarray"
        nbytes = int(getattr(c, "nbytes", 0))
        if nbytes >= HOST_CONST_BYTES:
            out.append(Finding(
                "jaxpr", "error", site,
                f"{kind} constant {tuple(c.shape)} {c.dtype} ({nbytes} B) "
                "folded into the jaxpr — it bakes into the executable "
                "(closure-captured table? pass it as an argument)"))
    return out


def _canonical_hash(closed) -> str:
    return hashlib.sha256(str(closed.jaxpr).encode()).hexdigest()[:16]


def _donation_findings(closed, site: str, n_batch_leaves: int,
                       n_trailing: int = 0) -> Tuple[List[Finding], Dict]:
    """Check that donated params/opt leaves can actually alias an
    output buffer; donated batch leaves are early-free only (tallied).
    ``n_trailing`` undonated inputs (the step's const arrays) follow
    the batch leaves."""
    out: List[Finding] = []
    eqns = closed.jaxpr.eqns
    rec = {"donated": 0, "donated_unaliasable_batch": 0}
    pjit = next((e for e in eqns if e.primitive.name == "pjit"), None)
    if pjit is None:
        return out, rec
    donated = pjit.params.get("donated_invars")
    if donated is None:
        return out, rec
    out_avals = [v.aval for v in pjit.outvars]
    pool: Dict[Tuple, int] = {}
    for a in out_avals:
        k = (a.shape, str(a.dtype))
        pool[k] = pool.get(k, 0) + 1
    invars = pjit.invars
    n_in = len(invars)
    for i, (v, d) in enumerate(zip(invars, donated)):
        if not d:
            continue
        rec["donated"] += 1
        a = v.aval
        k = (a.shape, str(a.dtype))
        is_batch = (n_batch_leaves
                    and i >= n_in - n_trailing - n_batch_leaves)
        if pool.get(k, 0) > 0:
            pool[k] -= 1
        elif is_batch:
            # donated purely so the host batch frees early — expected
            rec["donated_unaliasable_batch"] += 1
        else:
            out.append(Finding(
                "jaxpr", "error", site,
                f"donated params/opt leaf {a.shape} {a.dtype} cannot "
                "alias any step output — donation is silently dropped "
                "and the step double-buffers this array"))
    return out, rec


# ---------------------------------------------------------------------------
# per-variant audit
# ---------------------------------------------------------------------------

def audit_variant(graph, v: Variant, plan=None
                  ) -> Tuple[List[Finding], Dict]:
    """Trace one sweep variant's cached step twice (fresh source each
    time) and run every hazard walk.  -> (findings, record)."""
    import jax
    from repro.core import engine as E
    from repro.core import gnn as G

    if plan is None:
        plan = E.TrainPlan(lr=0.1, n_iters=4, eval_every=0)
    cfg = variant_cfg(graph, v)
    site = f"variant:{v.name}"
    findings: List[Finding] = []
    rec: Dict[str, Any] = {"variant": v.name}

    def trace_once():
        src = _make_source(v, cfg).bind(graph, cfg, plan)
        try:
            consts = src.loss_consts()
            step, arrays = E._cached_step(graph, type(src), consts, cfg,
                                          plan)
            params = src.place(
                G.init_gnn(jax.random.key(0), cfg,
                           graph.feats.shape[1]))
            opt_state = src.place(plan.make_optimizer().init(params))
            batch = _draw_batch(src, graph)
            closed = jax.make_jaxpr(step)(params, opt_state, batch, arrays)
            n_batch = len(jax.tree.leaves(batch))
            return step, closed, n_batch, len(arrays)
        finally:
            src.close()

    step1, closed1, n_batch, n_arrays = trace_once()
    step2, closed2, _, _ = trace_once()

    findings += _walk_hazards(closed1, site)
    don, drec = _donation_findings(closed1, site, n_batch, n_arrays)
    findings += don
    rec.update(drec)

    h1, h2 = _canonical_hash(closed1), _canonical_hash(closed2)
    rec["jaxpr_hash"] = h1
    rec["n_eqns"] = sum(1 for _ in _iter_eqns(closed1))
    rec["step_cache_hit"] = step1 is step2
    if step1 is not step2:
        findings.append(Finding(
            "jaxpr", "error", site,
            "_cached_step returned a DIFFERENT function for a fresh "
            "source bound to the same graph — the step cache key is "
            "unstable and every sweep grid point recompiles"))
    if h1 != h2:
        findings.append(Finding(
            "jaxpr", "error", site,
            f"re-trace produced a different canonical jaxpr "
            f"({h1} != {h2}) — sweep() would silently retrace/"
            "recompile this variant per grid point"))
    return findings, rec


def _audit_eval(graph, v: Variant) -> Tuple[List[Finding], Dict]:
    """Trace the module-level jitted eval (full-graph accuracy) the
    Trainer calls at eval_every; only full-graph paradigms own an ELL."""
    import jax
    from repro.core import engine as E
    from repro.core import gnn as G
    cfg = variant_cfg(graph, v)
    plan = E.TrainPlan(lr=0.1, n_iters=4, eval_every=0)
    site = f"eval:{v.name}"
    src = _make_source(v, cfg).bind(graph, cfg, plan)
    try:
        idx, w, w_self, feats, labels = src.ell
        params = src.place(
            G.init_gnn(jax.random.key(0), cfg, graph.feats.shape[1]))
        mesh = getattr(src, "_mesh", None)
        fsplan = getattr(src, "feats_plan", None)
        closed = jax.make_jaxpr(
            E._eval_acc, static_argnums=(1, 8))(
                params, E._static_cfg(cfg), idx, w, w_self, feats,
                labels, src.node_split("val"), mesh, fsplan)
    finally:
        src.close()
    return _walk_hazards(closed, site), \
        {"variant": site, "jaxpr_hash": _canonical_hash(closed),
         "n_eqns": sum(1 for _ in _iter_eqns(closed))}


def _audit_inference(graph) -> Tuple[List[Finding], List[Dict]]:
    """Trace the layer-wise inference chunk function (einsum + kernel)
    — the serving tier's hot path (`core.inference`)."""
    import jax
    from repro.core import engine as E
    from repro.core import gnn as G
    from repro.core import inference as I
    findings: List[Finding] = []
    recs: List[Dict] = []
    for kernel in (False, True):
        v = Variant("fullgraph", kernel)
        cfg = variant_cfg(graph, v)
        scfg = E._static_cfg(cfg)
        params = G.init_gnn(jax.random.key(0), cfg,
                            graph.feats.shape[1])
        ell = E._device_ell(graph)
        idx, w, w_self, feats, labels = ell
        c = 64
        site = f"inference:chunk+{'kernel' if kernel else 'einsum'}"
        import jax.numpy as jnp
        rows = jnp.arange(c, dtype=jnp.int32)
        src = G.layer_source(scfg, params[0], feats)
        closed = jax.make_jaxpr(
            I._chunk_apply, static_argnums=(0, 1, 2))(
                scfg, False, None, params[0], feats, src, rows,
                idx[:c], w[:c], w_self[:c])
        findings += _walk_hazards(closed, site)
        recs.append({"variant": site,
                     "jaxpr_hash": _canonical_hash(closed),
                     "n_eqns": sum(1 for _ in _iter_eqns(closed))})
    return findings, recs


def audit_jaxprs(n: int = 192) -> Tuple[List[Finding], List[Dict]]:
    """The full jaxpr audit: every sweep variant's step, the shared
    eval function, and the inference chunk path."""
    graph = audit_graph(n=n)
    findings: List[Finding] = []
    records: List[Dict] = []
    for v in sweep_variants():
        f, r = audit_variant(graph, v)
        findings += f
        records.append(r)
    # eval: one replicated + one sharded(+featshard) trace covers the
    # (mesh, feats_plan) static dispatch of the single jitted _eval_acc
    for v in (Variant("fullgraph", True),
              Variant("fullgraph_sharded", True, featshard=True)):
        f, r = _audit_eval(graph, v)
        findings += f
        records.append(r)
    f, rs = _audit_inference(graph)
    findings += f
    records += rs
    return findings, records
