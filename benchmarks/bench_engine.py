"""Engine throughput bench: steady-state steps/s and time-to-first-step
for BOTH training paradigms, toggling the device-resident fast path —
Pallas aggregation kernel on/off, params/opt_state donation + deferred
loss sync on/off, the scenario sources, and (``--devices N``) the
NODES-sharded sources on a multi-device mesh.  An ``inference`` variant
family benchmarks the serving tier: layer-wise embedding build
(ms/node, chunk steps/s) and micro-batched query throughput per
aggregation path, ``@Ndev``-keyed like the training rows.

``--devices N`` reruns the SHARDED variant set (fullgraph_sharded /
minibatch_sharded, einsum + shard_map'd kernel cells) in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the flag
must be set before jax initializes, so the parent process cannot host
them.  Multi-device rows are keyed by a ``@Ndev`` variant suffix, so
they land BESIDE the 1-device baseline rows instead of on top of them.

Writes ``BENCH_engine.json`` at the REPO ROOT so every subsequent PR has
a perf trajectory to regress against.  ``--check`` (CI mode) compares
fresh numbers to the committed baseline and fails with a readable
per-variant diff when steady-state steps/s regresses more than
``BENCH_TOL`` (default 25%); in that mode the baseline is only replaced
when ``--promote`` is given AND the gate passes (atomic tmp+rename via
``BENCH_engine.json.new``) — otherwise the side file is deleted before
exit, so repeated local runs cannot ratchet the bar down and CI leaves
the tree clean (``make bench-promote`` wraps the refresh).
Interpret-mode kernel cells and ``inference`` rows are recorded but
excluded from the gate (their few-iteration CPU wall-clock is noise —
a smoke embedding build is ~8 sub-ms chunk dispatches); a baseline
recorded at a
different size class (smoke vs full) is skipped as incomparable.

    python benchmarks/bench_engine.py --smoke --check --devices 4  # CI gate
    python benchmarks/bench_engine.py --smoke --devices 4  # refresh baseline
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

import jax

from benchmarks.common import gnn_cfg, print_rows
from repro.core.engine import Trainer, TrainPlan
from repro.core.experiment import make_source
from repro.data import make_preset

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(REPO_ROOT, "BENCH_engine.json")


def _source(paradigm: str, cfg):
    """Engine's paradigm dispatch, parameterized from the bench cfg."""
    return make_source(paradigm, b=cfg.batch_size, fanouts=cfg.fanout)


def run_variant(graph, cfg, paradigm: str, iters: int, fast: bool,
                seed: int = 0, repeats: int = 1) -> Dict:
    """One (paradigm, kernel, fast-path) cell: time-to-first-step is the
    History timestamp of iteration 0 of the FIRST run (compile + first
    dispatch + sync); steady-state steps/s is the BEST of ``repeats``
    runs — later runs reuse the cached compiled step, and taking the
    least-loaded measurement keeps the CI gate from firing on transient
    host contention."""
    plan = TrainPlan(lr=0.3, n_iters=iters, eval_every=10 ** 9, seed=seed,
                     donate=fast, deferred_sync=fast)
    ttfs, steady, res = 0.0, 0.0, None
    for rep in range(max(repeats, 1)):
        trainer = Trainer(graph, cfg, plan, source=_source(paradigm, cfg))
        try:
            res = trainer.run()
        finally:
            trainer.close()
        times = res.history.times
        if rep == 0:
            ttfs = times[0]
        steady = max(steady,
                     (len(times) - 1) / (times[-1] - times[0])
                     if len(times) > 1 and times[-1] > times[0] else 0.0)
    n_dev = len(jax.devices())
    featshard = cfg.feats_layout == "sharded"
    row = {
        # multi-device runs key their variants by device count, so a
        # 4-device row diffs against the 4-device baseline row — never
        # against (or over) the 1-device one
        "variant": f"{paradigm}"
                   f"{'+kernel' if cfg.use_agg_kernel else ''}"
                   f"{'+featshard' if featshard else ''}"
                   f"{'+fast' if fast else ''}"
                   f"{f'@{n_dev}dev' if n_dev > 1 else ''}",
        "paradigm": paradigm,
        "kernel": int(cfg.use_agg_kernel),
        "fast_path": int(fast),          # donation + deferred loss sync
        "devices": len(jax.devices()),
        "iters": iters,
        "time_to_first_step_s": round(ttfs, 4),
        "steady_steps_per_s": round(steady, 2),
        "final_loss": round(res.history.losses[-1], 6),
    }
    if featshard:
        # the hot-cache accounting the sources surface at train end:
        # full-graph plans report bind-time classification, sampled
        # sources report the host LRU — either way the same keys
        c = res.history.counters
        row["cache_hit_rate"] = round(c.get("feat_cache_hit_rate", 0.0), 4)
        row["remote_gather_bytes"] = int(c.get("feat_remote_gather_bytes",
                                               0))
        row["table_bytes_per_device"] = int(
            c.get("feat_table_bytes_per_device", 0))
    return row


def run_inference_variant(graph, cfg, seed: int = 0, repeats: int = 2,
                          mesh=None, chunk_size: int = 128,
                          serve_requests: int = 128) -> Dict:
    """One inference-tier cell: layer-wise embedding build (ms/node;
    "steps" are chunk dispatches, so ``steady_steps_per_s`` keeps the
    gate's shared row schema) plus micro-batched serve throughput
    (queries/s through ``GNNServer``).  ``time_to_first_step_s`` is the
    FIRST build (compile included); steady-state comes from the best of
    the warm rebuilds."""
    import numpy as np

    from repro.core import gnn as G
    from repro.core.embedding_store import EmbeddingStore
    from repro.core.serving import GNNServer

    params = G.init_gnn(jax.random.key(seed), cfg, graph.feats.shape[1])
    ttfs, steady, store, stats = 0.0, 0.0, None, None
    for rep in range(max(repeats, 1)):
        s = EmbeddingStore(params, cfg, graph, chunk_size=chunk_size,
                           mesh=mesh)
        run = s.build()
        rate = run.stats["chunk_steps"] / max(run.stats["total_s"], 1e-9)
        if rep == 0:
            ttfs = run.stats["total_s"]
        if rep > 0 or repeats == 1:
            steady = max(steady, rate)
        store, stats = s, run.stats
    rng = np.random.default_rng(seed)
    server = GNNServer(store, max_batch=32, max_wait_ms=0.5)
    try:
        futs = [server.submit(rng.integers(0, graph.n, size=8))
                for _ in range(serve_requests)]
        for f in futs:
            f.result(timeout=120.0)
    finally:
        server.close()
    st = server.stats()
    n_dev = len(jax.devices())
    return {
        "variant": f"inference"
                   f"{'+kernel' if cfg.use_agg_kernel else ''}"
                   f"{f'@{n_dev}dev' if n_dev > 1 else ''}",
        "paradigm": "inference",
        "kernel": int(cfg.use_agg_kernel),
        "fast_path": 1,
        "devices": n_dev,
        "iters": stats["chunk_steps"],
        "time_to_first_step_s": round(ttfs, 4),
        "steady_steps_per_s": round(steady, 2),
        "ms_per_node": round(stats["ms_per_node"], 5),
        "serve_q_per_s": round(st["qps"], 1),
        "serve_p99_ms": round(st["p99_ms"], 4),
    }


def run_serve_writes_variant(graph, cfg, seed: int = 0,
                             serve_requests: int = 128,
                             n_updates: int = 24,
                             chunk_size: int = 128) -> Dict:
    """Serving under write load (PR 10): a background writer streams
    feature updates through the WAL while query clients hammer the
    server; the row records answered queries/s, p99 latency, the max
    served staleness and the refresh-budget accounting (scheduler vs
    SLO-forced refreshes).  ``paradigm="inference"`` keeps the row
    recorded-but-not-gated, like the other inference cells — wall-clock
    under a concurrent writer is even noisier than the build loop."""
    import threading
    import time as _time

    import numpy as np

    from repro.core import gnn as G
    from repro.core.embedding_store import EmbeddingStore
    from repro.core.serving import GNNServer

    params = G.init_gnn(jax.random.key(seed), cfg, graph.feats.shape[1])
    store = EmbeddingStore(params, cfg, graph, chunk_size=chunk_size)
    run = store.build()
    rng = np.random.default_rng(seed)
    server = GNNServer(store, max_batch=32, max_wait_ms=0.5,
                       max_staleness_s=0.25, refresh_every_updates=4,
                       refresh_budget_ms=50.0)
    t0 = _time.monotonic()
    try:
        def writer():
            for _ in range(n_updates):
                nodes = rng.choice(graph.n, size=4, replace=False)
                store.update_features(
                    nodes, rng.normal(size=(4, graph.feats.shape[1]))
                    .astype(np.float32))
                _time.sleep(0.002)

        wt = threading.Thread(target=writer)
        wt.start()
        futs = [server.submit(rng.integers(0, graph.n, size=8))
                for _ in range(serve_requests)]
        for f in futs:
            f.result(timeout=120.0)
        wt.join(timeout=60.0)
    finally:
        server.close()
    total_s = _time.monotonic() - t0
    st = server.stats()
    rs = store.refresh_stats()
    n_dev = len(jax.devices())
    return {
        "variant": f"serve+writes"
                   f"{'+kernel' if cfg.use_agg_kernel else ''}"
                   f"{f'@{n_dev}dev' if n_dev > 1 else ''}",
        "paradigm": "inference",
        "kernel": int(cfg.use_agg_kernel),
        "fast_path": 1,
        "devices": n_dev,
        "iters": serve_requests,
        "time_to_first_step_s": round(run.stats["total_s"], 4),
        "steady_steps_per_s": round(serve_requests / max(total_s, 1e-9),
                                    2),
        "serve_q_per_s": round(st["qps"], 1),
        "serve_p99_ms": round(st["p99_ms"], 4),
        "staleness_max_s": round(st["staleness_max_s"], 4),
        "snapshot_version": int(st["snapshot_version"]),
        "n_updates": n_updates,
        "sched_refreshes": int(rs["sched_refreshes"]),
        "forced_refreshes": int(st["n_forced_refresh"]),
    }


def _bench_setup(smoke: bool, seed: int):
    """Shared sizes/graph/configs for the main and sharded variant sets
    (identical sizes keep 1-device and @Ndev rows comparable)."""
    # gated cells need a measurement window big enough to ride out
    # scheduler jitter on throttled CI hosts (~0.5 s per run, x3 runs)
    n, iters, kernel_iters = (400, 96, 6) if smoke else (2000, 200, 12)
    graph = make_preset("arxiv-like", n=n, seed=seed)
    cfg = gnn_cfg(graph, model="graphsage", n_layers=2, fanout=(5, 3),
                  batch=64, hidden=32)
    kcfg = dataclasses.replace(cfg, model="gcn", use_agg_kernel=True,
                               agg_b_tile=8,
                               agg_d_tile=128, agg_k_slab=4)
    return graph, cfg, kcfg, iters, kernel_iters


def run(smoke: bool = True, seed: int = 0) -> List[Dict]:
    graph, cfg, kcfg, iters, kernel_iters = _bench_setup(smoke, seed)
    rows = []
    for paradigm in ("fullgraph", "minibatch"):
        for fast in (False, True):
            # gated cells: best-of-3 to smooth host-load noise
            rows.append(run_variant(graph, cfg, paradigm, iters, fast,
                                    seed=seed, repeats=3))
        # kernel-on cell (interpret mode on CPU: correctness + dispatch
        # shape, NOT a TPU wall-time — few iters keep it cheap, and the
        # gate skips it)
        rows.append(run_variant(graph, kcfg, paradigm, kernel_iters,
                                True, seed=seed))
    # scenario sources (one fast-path cell each): cluster unions,
    # importance-weighted targets, NODES-sharded mini-batches.
    for paradigm in ("cluster", "importance", "minibatch_sharded"):
        rows.append(run_variant(graph, cfg, paradigm, iters, True,
                                seed=seed, repeats=3))
    if len(jax.devices()) > 1:
        rows.append(run_variant(graph, cfg, "fullgraph_sharded", iters,
                                True, seed=seed, repeats=3))
    # inference tier: layer-wise embed + serve throughput, einsum
    # (gated once baselined) and Pallas-kernel (record-only) cells
    rows.append(run_inference_variant(graph, cfg, seed=seed, repeats=3))
    rows.append(run_inference_variant(graph, kcfg, seed=seed, repeats=1,
                                      serve_requests=32))
    # serving under a concurrent write stream (qps/p99/staleness —
    # recorded, not gated, like the other inference cells)
    rows.append(run_serve_writes_variant(graph, cfg, seed=seed))
    return rows


def run_sharded(smoke: bool = True, seed: int = 0) -> List[Dict]:
    """The NODES-sharded variant set — einsum fast-path cells (gated)
    plus shard_map'd Pallas kernel cells (interpret mode, record-only)
    for both sharded sources.  Meant to run under
    ``--xla_force_host_platform_device_count=N`` via ``--devices``."""
    graph, cfg, kcfg, iters, kernel_iters = _bench_setup(smoke, seed)
    # NODES-sharded feature table + degree-ordered hot cache: kernel=1
    # keeps these cells record-only (interpret mode), but their
    # cache_hit_rate / remote_gather_bytes columns ARE the bench's
    # feature-traffic trajectory
    fscfg = dataclasses.replace(kcfg, feats_layout="sharded",
                                feat_cache_rows=-1)
    rows = []
    for paradigm in ("fullgraph_sharded", "minibatch_sharded"):
        rows.append(run_variant(graph, cfg, paradigm, iters, True,
                                seed=seed, repeats=3))
        rows.append(run_variant(graph, kcfg, paradigm, kernel_iters,
                                True, seed=seed))
        rows.append(run_variant(graph, fscfg, paradigm, kernel_iters,
                                True, seed=seed))
    # layer-wise inference through the NODES-sharded kernel path
    # (record-only: kernel rows are excluded from the gate)
    from repro import sharding as sh
    rows.append(run_inference_variant(graph, kcfg, seed=seed, repeats=1,
                                      mesh=sh.node_mesh(),
                                      serve_requests=32))
    return rows


def _sharded_subprocess(n_dev: int, smoke: bool) -> List[Dict]:
    """Run ``run_sharded`` under N virtual CPU devices (the XLA flag
    must be set before jax initializes, hence the subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_dev}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO_ROOT, "src"), REPO_ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    with tempfile.NamedTemporaryFile("r", suffix=".json") as tf:
        cmd = [sys.executable, os.path.abspath(__file__), "--sharded-only",
               "--rows-out", tf.name] + (["--smoke"] if smoke else [])
        subprocess.run(cmd, env=env, check=True, timeout=3600)
        return json.load(open(tf.name))


# ---------------------------------------------------------------------------
# Baseline check
# ---------------------------------------------------------------------------

def check_regression(rows: List[Dict], baseline_path: str = BENCH_PATH,
                     tol: Optional[float] = None,
                     smoke: Optional[bool] = None) -> List[str]:
    """Readable per-variant diff vs the committed baseline; returns the
    list of failures (> tol relative steps/s regression).  A baseline
    recorded at a different size class (smoke vs full) is incomparable
    and skipped rather than silently passed."""
    tol = float(os.environ.get("BENCH_TOL", "0.25")) if tol is None else tol
    if not os.path.exists(baseline_path):
        print(f"bench_engine: no baseline at {baseline_path}, skipping "
              "regression check")
        return []
    with open(baseline_path) as f:
        payload = json.load(f)
    if smoke is not None and payload.get("smoke") != smoke:
        print(f"bench_engine: baseline at {baseline_path} was recorded "
              f"with smoke={payload.get('smoke')}, current run is "
              f"smoke={smoke} — sizes are incomparable, skipping "
              "regression check")
        return []
    n_dev = len(jax.devices())
    if payload.get("devices", n_dev) != n_dev:
        print(f"bench_engine: baseline recorded on "
              f"{payload.get('devices')} device(s), current run sees "
              f"{n_dev} — incomparable, skipping regression check")
        return []
    base = {r["variant"]: r for r in payload["rows"]}
    failures = []
    for r in rows:
        if r.get("kernel"):
            # interpret-mode kernel cells exist for correctness /
            # dispatch shape; their few-iteration CPU wall-clock is too
            # noisy to gate on
            continue
        if r.get("paradigm") == "inference":
            # a smoke embedding build is ~8 sub-ms chunk dispatches —
            # its chunk-steps/s swings >40% run to run on a shared CPU,
            # so inference rows are recorded for the perf trajectory
            # but not gated (same rationale as the kernel cells)
            print(f"  {r['variant']:32s} steps/s "
                  f"{r['steady_steps_per_s']:>10.2f} (inference row — "
                  f"recorded, not gated)")
            continue
        b = base.get(r["variant"])
        if b is None:
            # a variant the baseline predates (e.g. a source added in
            # this PR): record-only until the baseline is refreshed —
            # the first PR after a new source must not trip the gate
            print(f"  {r['variant']:32s} steps/s "
                  f"{r['steady_steps_per_s']:>10.2f} (new variant, not "
                  f"in baseline — not gated)")
            continue
        if not b["steady_steps_per_s"]:
            continue
        old, new = b["steady_steps_per_s"], r["steady_steps_per_s"]
        rel = (new - old) / old
        line = (f"  {r['variant']:32s} steps/s {old:10.2f} -> {new:10.2f} "
                f"({rel:+.1%})")
        print(line)
        if rel < -tol:
            failures.append(line)
    if failures:
        print(f"bench_engine: steady-state steps/s regressed more than "
              f"{tol:.0%} vs {baseline_path}:")
        for line in failures:
            print("FAIL" + line)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for per-PR CI")
    ap.add_argument("--check", action="store_true",
                    help="fail on >BENCH_TOL steps/s regression vs the "
                         "committed BENCH_engine.json")
    ap.add_argument("--promote", action="store_true",
                    help="with --check: when the gate passes, atomically "
                         "replace the committed baseline with the fresh "
                         "numbers (tmp file + rename); without this flag "
                         "--check never touches the baseline")
    ap.add_argument("--devices", type=int, default=0,
                    help="additionally run the sharded variant set in a "
                         "subprocess with N virtual CPU devices "
                         "(rows keyed @Ndev beside the 1-device ones)")
    ap.add_argument("--sharded-only", action="store_true",
                    help=argparse.SUPPRESS)    # the --devices subprocess
    ap.add_argument("--rows-out", default="", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=BENCH_PATH,
                    help="output path (default: repo-root "
                         "BENCH_engine.json)")
    args = ap.parse_args(argv)

    if args.sharded_only:
        rows = run_sharded(smoke=args.smoke)
        print_rows("engine-sharded", rows)
        if args.rows_out:
            with open(args.rows_out, "w") as f:
                json.dump(rows, f, indent=1)
        return 0

    rows = run(smoke=args.smoke)
    if args.devices > 1 and len(jax.devices()) == 1:
        # only from a 1-device parent: a multi-device parent already
        # recorded in-process sharded rows under the same @Ndev keys,
        # and a forced-CPU subprocess duplicate would silently win the
        # per-variant dict in the gate/baseline
        rows += _sharded_subprocess(args.devices, args.smoke)
    elif args.devices:
        print(f"bench_engine: --devices {args.devices} skipped "
              f"(parent already sees {len(jax.devices())} device(s); "
              "sharded rows come from the in-process run)")
    print_rows("engine", rows)
    payload = {"bench": "engine", "smoke": bool(args.smoke),
               "devices": len(jax.devices()), "rows": rows}
    if args.check:
        # gate mode never silently rewrites the baseline (no ratchet):
        # fresh numbers go to a side file, which either gets PROMOTED
        # over the baseline via an atomic same-directory rename
        # (--promote, gate green) or is deleted before exit — CI and
        # repeated local runs leave the tree clean either way
        failures = check_regression(rows, baseline_path=args.out,
                                    smoke=bool(args.smoke))
        side = args.out + ".new"
        try:
            with open(side, "w") as f:
                json.dump(payload, f, indent=1)
                f.write("\n")
            if args.promote and not failures:
                os.replace(side, args.out)   # atomic: tmp + rename
                print(f"bench_engine: gate passed — promoted fresh "
                      f"numbers to {args.out}")
            elif args.promote:
                print(f"bench_engine: gate FAILED — baseline {args.out} "
                      "left untouched despite --promote")
            else:
                print(f"bench_engine: baseline {args.out} untouched in "
                      "--check mode (pass --promote to refresh it on a "
                      "green gate)")
        finally:
            if os.path.exists(side):
                os.remove(side)
        return 1 if failures else 0
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"bench_engine: wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
