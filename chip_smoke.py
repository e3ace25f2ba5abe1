"""Smoke run of the papers100M-width GNN trainer and server on a TPU.

Drives the main path once through the entry points a user calls, with
random weights made from ``--seed``, and checks what comes out against
plain float32 references::

    python chip_smoke.py               # one chip (every phase below)
    python chip_smoke.py --chips 4     # the four-chip sharded path only

The graph is a papers100M-regime SBM (172 classes, 128-wide features,
average degree 29, homophily 0.6, power-law degrees) built from
``--seed``, cut from the config's 16,777,216 nodes to ``--nodes``.  The
model is ``configs/gnn_papers100m.full_config()`` with only ``n_nodes``
replaced (bf16 aggregation, hidden 256, fan-out (15, 10), b=8192, ELL
max_degree 32, the Pallas aggregation kernel on).

One chip, all in this process:

* ``kernel``: the tiled Pallas gather at the full-graph layer-1 shapes
  against ``kernels/neighbor_agg/ref.py``;
* ``train_sampled`` / ``train_full``: ``Trainer`` with ``SampledSource``
  / ``FullGraphSource``; the first two losses against the float32
  einsum path: the loss at the same params on the same batch, and the
  loss after one optimizer step from there (which checks the backward
  pass and the update);
* ``serve``: ``EmbeddingStore.build`` and a ``GNNServer`` answering
  queries, checked against the argmax of the full-graph forward.

``--chips 4``: ``ShardedFullGraphSource`` with the feature table
row-sharded over all four chips (``feats_layout="sharded"``), its first
two losses against the float32 einsum reference, and the peak bytes of
every device.

Each phase prints one JSON line, and a ``progress`` line to stderr as
it starts.  Its times are smoke timings of one process, not benchmark
numbers.  The last line is ``{"ok": true,
"device": {...}}``; a failed check exits 1 before it, and a host without
a TPU exits 2: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import sharding as sh  # noqa: E402
from repro.configs.gnn_papers100m import full_config  # noqa: E402
from repro.core import gnn as G  # noqa: E402
from repro.core.embedding_store import EmbeddingStore  # noqa: E402
from repro.core.engine import (  # noqa: E402
    Callback, FullGraphSource, SampledSource, ShardedFullGraphSource,
    Trainer, TrainPlan)
from repro.core.graph import to_ell  # noqa: E402
from repro.core import inference as I  # noqa: E402
from repro.core.serving import GNNServer  # noqa: E402
from repro.data.synth import make_sbm_graph  # noqa: E402
from repro.kernels.neighbor_agg.ops import neighbor_agg  # noqa: E402
from repro.kernels.neighbor_agg.ref import neighbor_agg_ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

PAPERS_NODES = 16_777_216

# Tolerances, each against a float32 reference computed under
# jax.default_matmul_precision("highest") from the same bf16-rounded
# inputs.
# * kernel, f32 output: both sides multiply the same f32 values and
#   accumulate in f32; only the order of the K=32 additions differs,
#   which moves a sum by a few f32 roundoffs (2^-24 each).
KERNEL_RTOL = 1e-5
# * kernel, bf16 output: the f32 accumulator is rounded once to bf16
#   (8 significant bits: at most 2^-9 relative) on top of the above.
BF16_OUT_RTOL = 2.0 ** -8
# * first- and second-step loss: the timed path rounds its aggregation
#   tables to bf16 and the TPU's default matmul precision rounds matmul
#   operands to bf16.  The first-step losses read 1.1e-5 relative on a
#   v5e; the limit is about 100 times that, still small enough that a
#   dropped neighbour set or a wrong gradient moves a loss past it.
LOSS_RTOL = 1e-3
# most node rows per chunk of the full-graph float32 reference
REF_CHUNK = 1 << 11
# * serving: answers must be the full-graph forward's argmax except where
#   its top two logits are within two bf16 ulps (a tie that a different
#   summation order may break either way).
TIE_RTOL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A check of this script failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def progress(what: str) -> None:
    """A line on stderr as a phase starts, so a run cut short still says
    where it was."""
    print(f"chip_smoke: {time.strftime('%H:%M:%S')} {what}",
          file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events), read per phase."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += duration

    def lap(self) -> float:
        secs, self.secs = self.secs, 0.0
        return secs


def peak_bytes():
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def make_graph(args):
    progress(f"graph: {args.nodes} nodes")
    t0 = time.perf_counter()
    graph = make_sbm_graph(n=args.nodes, n_classes=172, avg_degree=29.0,
                           homophily=0.6, feat_dim=128, power_law=True,
                           seed=args.seed)
    emit("graph", nodes=graph.n, edges=int(graph.indices.size),
         d_max=int(graph.d_max), seconds=time.perf_counter() - t0,
         cut=f"n_nodes {PAPERS_NODES} -> {graph.n}; every width as "
             f"configs/gnn_papers100m.py")
    return graph


def papers_cfg(graph, **kw):
    return dataclasses.replace(full_config(), n_nodes=graph.n, **kw)


def ref_cfg(cfg):
    """The float32 einsum configuration the timed path is compared with."""
    return dataclasses.replace(cfg, use_agg_kernel=False, dtype="float32")


class StepClock(Callback):
    """``Trainer`` callback: host time at each synced step."""

    def __init__(self):
        self.t = []

    def on_step(self, state):
        self.t.append(time.perf_counter())

    def steady(self) -> float:
        """Median time of the steps after the first (which compiles)."""
        return statistics.median(np.diff(self.t).tolist())


def train(graph, cfg, source, n_iters, seed, clock):
    # synced steps, so the callback's clock brackets each step; one eval
    # (iteration 0) besides the Trainer's final test evaluation
    plan = TrainPlan(n_iters=n_iters, eval_every=n_iters + 1,
                     deferred_sync=False, seed=seed)
    steps = StepClock()
    t0 = time.perf_counter()
    trainer = Trainer(graph, cfg, plan, source=source,
                      extra_callbacks=[steps])
    bind_s = time.perf_counter() - t0
    res = trainer.run()
    losses = [float(x) for x in res.history.losses]
    check(len(losses) == n_iters and all(np.isfinite(losses)),
          f"{source.name}: losses {losses}")
    timing = dict(bind_s=bind_s, compile_s=clock.lap(),
                  steady_step_s=steps.steady(),
                  run_s=time.perf_counter() - t0 - bind_s)
    return res, losses, plan, timing


def init_params(cfg, plan):
    """The params ``Trainer.run`` starts from."""
    return G.init_gnn(jax.random.key(plan.seed), cfg, cfg.feat_dim)


def two_step_losses(loss_fn, plan, params, batch0, batch1):
    """``loss_fn`` at ``params`` on ``batch0``, and at the params one
    optimizer step later (the Trainer's optimizer, from its initial
    state) on ``batch1``."""
    opt = plan.make_optimizer()

    @jax.jit
    def run(p0, b0, b1):
        l0, grads = jax.value_and_grad(loss_fn)(p0, b0)
        p1, _ = opt.update(grads, opt.init(p0), p0)
        return l0, loss_fn(p1, b1)

    with jax.default_matmul_precision("highest"):
        return [float(x) for x in run(params, batch0, batch1)]


def fullgraph_ref_losses(graph, cfg, params, plan):
    """First two full-graph training losses on the float32 einsum path:
    the loss at ``params`` and after one optimizer step from there.

    The layers run in node chunks through the layer-wise inference body
    (``inference._chunk_apply``), and the backward pass goes chunk by
    chunk too: each chunk's VJP is taken over its own gathered rows and
    scatter-added into per-layer gradient tables, so the device holds a
    few [n, d] tables and never an [n, K, d] gather (autodiff through a
    chunk loop keeps a dense [n, d] cotangent per chunk and does not fit
    one chip at 2^21 nodes).  The last layer runs for the training
    nodes only.  The ELL and features are the device copies
    ``FullGraphSource`` trains on (capped at ``cfg.max_degree``)."""
    args = FullGraphSource().bind(graph, cfg, plan).ell + (
        jnp.asarray(graph.train_nodes, jnp.int32),)
    step, loss = fullgraph_ref_program(ref_cfg(cfg), plan, len(params))
    with jax.default_matmul_precision("highest"):
        l0, p1 = step(params, *args)
        return [float(l0), float(loss(p1, *args))]


def fullgraph_ref_program(rcfg, plan, n_layers):
    """The two jitted programs behind ``fullgraph_ref_losses``, each
    taking ``(params, idx, w, w_self, feats, labels, train)``:
    ``step`` -> (loss, params after one optimizer step) and ``loss``.
    Two programs, so the second forward's tables are not live beside
    the backward's."""
    opt = plan.make_optimizer()

    def program(p0, idx, w, w_self, feats, labels, tr, *, take_step):
        all_rows = jnp.arange(feats.shape[0], dtype=jnp.int32)

        def chunk_fn(last, r):
            """Output rows ``r`` of a layer from the chunk's own rows:
            ``f(p, h[r], src[idx[r]] as [c*K, d])``."""
            c, k = r.shape[0], idx.shape[1]
            local = jnp.arange(c * k, dtype=jnp.int32).reshape(c, k)

            def f(p, h_rows, src_rows):
                return I._chunk_apply(
                    rcfg, last, None, p, h_rows, src_rows,
                    jnp.arange(c, dtype=jnp.int32), local, w[r], w_self[r])
            return f

        def gathered(src, r):
            return src[idx[r]].reshape(-1, src.shape[1])

        def chunks(rows):
            c = math.gcd(rows.shape[0], REF_CHUNK)
            return rows.reshape(-1, c)

        def forward(params):
            hs = [feats]                     # each layer's input table
            for li, p in enumerate(params):
                last = li == n_layers - 1
                h = hs[-1]
                src = G.layer_source(rcfg, p, h)
                out = jax.lax.map(
                    lambda r: chunk_fn(last, r)(p, h[r], gathered(src, r)),
                    chunks(tr if last else all_rows))
                hs.append(out.reshape(-1, out.shape[-1]))
            return hs

        def loss_of(logits):
            return G.gnn_loss(logits, labels[tr], rcfg.loss, rcfg.n_classes)

        def layer_vjp(p, h, last, rows, g_out, need_dh):
            """(dp, dh) of a layer from the cotangent of its output
            rows; dh is None unless ``need_dh``."""
            src, pre_vjp = jax.vjp(
                lambda p, h: G.layer_source(rcfg, p, h), p, h)
            rc = chunks(rows)
            gc = g_out.reshape(rc.shape + g_out.shape[1:])

            def body(i, acc):
                r = rc[i]
                f = chunk_fn(last, r)
                if not need_dh:
                    _, vjp = jax.vjp(lambda p: f(p, h[r], gathered(src, r)),
                                     p)
                    return jax.tree.map(jnp.add, acc, vjp(gc[i])[0])
                dp, dh, dsrc = acc
                _, vjp = jax.vjp(f, p, h[r], gathered(src, r))
                gp, gh, gs = vjp(gc[i])
                ii = idx[r]
                return (jax.tree.map(jnp.add, dp, gp), dh.at[r].add(gh),
                        dsrc.at[ii].add(gs.reshape(ii.shape + gs.shape[1:])))

            zeros = jax.tree.map(jnp.zeros_like,
                                 (p, h, src) if need_dh else p)
            acc = jax.lax.fori_loop(0, rc.shape[0], body, zeros)
            if not need_dh:              # the input layer: no dh needed
                return acc, None
            dp, dh, dsrc = acc
            gp, gh = pre_vjp(dsrc)
            return jax.tree.map(jnp.add, dp, gp), dh + gh

        def loss_and_grads(params):
            hs = forward(params)
            loss, g = jax.value_and_grad(loss_of)(hs[-1])
            grads = [None] * n_layers
            for li in reversed(range(n_layers)):
                last = li == n_layers - 1
                grads[li], g = layer_vjp(params[li], hs[li], last,
                                         tr if last else all_rows, g,
                                         need_dh=li > 0)
            return loss, grads

        if not take_step:
            return loss_of(forward(p0)[-1])
        l0, grads = loss_and_grads(p0)
        return l0, opt.update(grads, opt.init(p0), p0)[0]

    return (jax.jit(functools.partial(program, take_step=True)),
            jax.jit(functools.partial(program, take_step=False)))


def loss_check(name, losses, refs):
    rels = [abs(x - r) / abs(r) for x, r in zip(losses, refs)]
    for i, rel in enumerate(rels):
        check(rel <= LOSS_RTOL, f"{name}: step-{i} loss {losses[i]} vs "
              f"float32 einsum {refs[i]} (rel {rel:.3g} > {LOSS_RTOL})")
    return rels


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_kernel(graph, cfg, ell, clock):
    """The tiled gather at the full-graph layer-1 shapes ([n, 128]
    table, ELL K=32): an f32 table, a bf16 table, and a bf16 table with
    the fused self term, each against the float32 reference of
    ``ref.py`` on the same bf16-rounded values, in row blocks."""
    progress("kernel")
    bf, f32 = jnp.bfloat16, jnp.float32
    idx = jnp.asarray(ell[0])
    w_b = jnp.asarray(ell[1]).astype(bf)
    ws_b = jnp.asarray(ell[2]).astype(bf)
    tab_b = jnp.asarray(graph.feats).astype(bf)
    tab32, w32, ws32 = tab_b.astype(f32), w_b.astype(f32), ws_b.astype(f32)
    zero_ws = jnp.zeros_like(ws32)
    kw = dict(use_kernel=True, kernel="tiled", b_tile=cfg.agg_b_tile,
              d_tile=cfg.agg_d_tile, k_slab=cfg.agg_k_slab)

    @jax.jit
    def block_err(out, tab, i, w, ws, start):
        """Over one row block: max |out - ref|, max |ref| and the largest
        error beyond bf16 output rounding, max(|out - ref| - 2^-8 |ref|).
        The self rows of block rows are the table rows at ``start``."""
        self_rows = jax.lax.dynamic_slice_in_dim(tab, start, out.shape[0])
        ref = neighbor_agg_ref(tab, i, w) + ws[:, None] * self_rows
        err = jnp.abs(out.astype(f32) - ref)
        return (jnp.max(err), jnp.max(jnp.abs(ref)),
                jnp.max(err - BF16_OUT_RTOL * jnp.abs(ref)))

    n, k = idx.shape
    blk = 1 << 15
    fields = {}
    for name, table, w, ws in (("f32", tab32, w32, None),
                               ("bf16", tab_b, w_b, None),
                               ("bf16_self", tab_b, w_b, ws_b)):
        args = (table, idx, w) + ((table, ws) if ws is not None else ())
        jax.block_until_ready(neighbor_agg(*args, **kw))
        compile_s = clock.lap()
        t0 = time.perf_counter()
        out = jax.block_until_ready(neighbor_agg(*args, **kw))
        call_s = time.perf_counter() - t0
        check(out.shape == (n, 128) and out.dtype == table.dtype,
              f"kernel {name}: {out.shape} {out.dtype}")
        ws_ref = zero_ws if ws is None else ws32
        err = scale = excess = 0.0
        with jax.default_matmul_precision("highest"):
            for s in range(0, n, blk):
                e, r, x = block_err(out[s:s + blk], tab32, idx[s:s + blk],
                                    w32[s:s + blk], ws_ref[s:s + blk], s)
                err, scale = max(err, float(e)), max(scale, float(r))
                excess = max(excess, float(x))
        if table.dtype == f32:
            check(err <= KERNEL_RTOL * scale, f"kernel {name}: max err "
                  f"{err} > {KERNEL_RTOL} * max |ref| {scale}")
        else:
            check(excess <= KERNEL_RTOL * scale, f"kernel {name}: error "
                  f"beyond bf16 rounding {excess} > {KERNEL_RTOL} * "
                  f"max |ref| {scale}")
        fields[name] = dict(compile_s=compile_s, call_s=call_s,
                            max_abs_err=err, max_abs_ref=scale,
                            err_beyond_bf16_rounding=excess)
        del out
    clock.lap()
    emit("kernel", n=n, K=k, d=128, **fields,
         tolerance=dict(f32=f"max err <= {KERNEL_RTOL} max|ref|",
                        bf16=f"|err| <= {BF16_OUT_RTOL}|ref| + "
                             f"{KERNEL_RTOL} max|ref|"),
         timing="smoke", peak_bytes_in_use=peak_bytes())


def phase_train_sampled(graph, cfg, iters, seed, clock):
    progress("train_sampled")
    res, losses, plan, timing = train(graph, cfg, SampledSource(), iters,
                                      seed, clock)
    # the same first two batches (same seed, same sampler) on the einsum
    # path
    ref_src = SampledSource(prefetch=False, reuse_buffers=False)
    ref_src.bind(graph, cfg, plan)
    stream = ref_src.batches()
    (b0, _), (b1, _) = next(stream), next(stream)
    rcfg, consts = ref_cfg(cfg), ref_src.loss_consts()
    refs = two_step_losses(
        lambda p, b: SampledSource._loss_impl(p, b, consts, rcfg), plan,
        init_params(cfg, plan), b0, b1)
    ref_src.close()
    rels = loss_check("train_sampled", losses, refs)
    clock.lap()
    emit("train_sampled", steps=iters, losses=losses, ref_losses=refs,
         rel_err_losses=rels, tolerance=LOSS_RTOL,
         test_acc=res.final_test_acc, timing="smoke", **timing,
         peak_bytes_in_use=peak_bytes())


def phase_train_full(graph, cfg, ell, iters, seed, clock):
    progress("train_full")
    res, losses, plan, timing = train(graph, cfg, FullGraphSource(), iters,
                                      seed, clock)
    progress("train_full: float32 reference")
    refs = fullgraph_ref_losses(graph, cfg, init_params(cfg, plan), plan)
    rels = loss_check("train_full", losses, refs)
    clock.lap()
    emit("train_full", steps=iters, losses=losses, ref_losses=refs,
         rel_err_losses=rels, tolerance=LOSS_RTOL,
         test_acc=res.final_test_acc, timing="smoke", **timing,
         peak_bytes_in_use=peak_bytes())
    return res.params


def phase_serve(graph, cfg, params, ell, args, clock):
    progress("serve")
    t0 = time.perf_counter()
    store = EmbeddingStore(params, cfg, graph, chunk_size=args.chunk)
    run = store.build()
    build_s = time.perf_counter() - t0
    build_compile_s = clock.lap()

    rng = np.random.default_rng(args.seed + 1)
    queries = [rng.integers(0, graph.n, size=rng.integers(1, 9))
               for _ in range(args.queries)]
    server = GNNServer(store, max_batch=64, max_wait_ms=2.0)
    t0 = time.perf_counter()
    try:
        with ThreadPoolExecutor(max_workers=8) as pool:
            answers = list(pool.map(
                lambda q: server.classify(q, timeout=120.0), queries))
    finally:
        server.close()
    serve_s = time.perf_counter() - t0
    st = server.stats()

    fwd = jax.jit(lambda p, f, i, w, ws: G.full_graph_forward(
        p, cfg, f, i, w, ws))
    logits = np.asarray(fwd(params, jnp.asarray(graph.feats),
                            *(jnp.asarray(a) for a in ell)))
    fwd_compile_s = clock.lap()
    q_all = np.concatenate(queries)
    got = np.concatenate(answers)
    want = logits[q_all].argmax(-1)
    top = logits[q_all, want]
    tie = logits[q_all, got] >= top - TIE_RTOL * np.maximum(1.0,
                                                           np.abs(top))
    check(bool(np.all((got == want) | tie)),
          f"serve: {int(np.sum(got != want))} answers differ from the "
          f"full-graph argmax beyond a tie")
    store_err = float(np.max(np.abs(store.query_logits(q_all)
                                    - logits[q_all])))
    emit("serve", nodes=graph.n, chunk=store.chunk_size,
         chunks_per_layer=run.stats["n_chunks"], build_s=build_s,
         build_compile_s=build_compile_s, per_layer_s=run.stats[
             "per_layer_s"], queries=len(queries), answered=int(got.size),
         exact_argmax=int(np.sum(got == want)), serve_s=serve_s,
         p50_ms=st["p50_ms"], p99_ms=st["p99_ms"],
         max_abs_err_store_vs_forward=store_err,
         forward_compile_s=fwd_compile_s, timing="smoke",
         peak_bytes_in_use=peak_bytes())


def run_one_chip(args, clock):
    graph = make_graph(args)
    cfg = papers_cfg(graph)
    check(cfg.use_agg_kernel and cfg.dtype == "bfloat16",
          "papers config lost its kernel/bf16 settings")
    ell = to_ell(graph, max_deg=cfg.max_degree)
    clock.lap()
    phase_kernel(graph, cfg, ell, clock)
    phase_train_sampled(graph, cfg, args.sampled_steps, args.seed,
                        clock)
    params = phase_train_full(graph, cfg, ell, args.full_steps,
                              args.seed, clock)
    phase_serve(graph, cfg, params, ell, args, clock)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(args, clock):
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    graph = make_graph(args)
    cfg = papers_cfg(graph, feats_layout="sharded")
    mesh = sh.node_mesh()
    check(sorted(d.id for d in mesh.devices.flat)
          == sorted(d.id for d in devs),
          f"node mesh {mesh} does not span all four devices")
    src = ShardedFullGraphSource(mesh=mesh)
    progress("train_featshard_4chips")
    res, losses, plan, timing = train(
        graph, cfg, src, args.full_steps, args.seed, clock)
    peaks = peak_bytes()
    st = src.featshard_stats or {}
    check(st, "feats_layout='sharded' bound no featshard plan")
    check(max(peaks) <= 1.5 * min(peaks),
          f"per-device peak bytes unbalanced: {peaks}")
    progress("train_featshard_4chips: float32 reference")
    refs = fullgraph_ref_losses(graph, cfg, init_params(cfg, plan), plan)
    rels = loss_check("train_featshard_4chips", losses, refs)
    clock.lap()
    emit("train_featshard_4chips", mesh=dict(mesh.shape), steps=len(losses),
         losses=losses, ref_losses=refs, rel_err_losses=rels,
         tolerance=LOSS_RTOL, test_acc=res.final_test_acc,
         feat_table_bytes_per_device=st.get("feat_table_bytes_per_device"),
         feat_remote_gather_bytes=st.get("feat_remote_gather_bytes"),
         timing="smoke", **timing, peak_bytes_in_use_per_device=peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--nodes", type=int, default=1 << 21)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sampled-steps", type=int, default=5)
    ap.add_argument("--full-steps", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=1 << 17,
                    help="EmbeddingStore build chunk (rows per dispatch)")
    ap.add_argument("--queries", type=int, default=48)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    clock = CompileClock()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), compile_cache=cache)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args, clock)
        else:
            run_one_chip(args, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
