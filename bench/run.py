"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration and a traffic mix, found by name from
``BENCHMARK.json``.  The run builds (or loads from ``bench/.cache``) the
configuration's graph, trains through the program's ``Trainer`` with the
mix's batch source, and measures a window of whole steps after the
warm-up:

* ``--trace 0``: the end-to-end metrics (targets per second over the
  window, the 95th percentile step, the set-up time);
* ``--trace 1``: a profiler trace of a short steady window, reduced to
  the per-layer metrics (``bench/metrics``) and a breakdown of device
  time and idle gaps.

Either way the first three steps are then compared with the float32
reference (``bench/check.py``), and each number compared is printed
beside its limit, last on stderr and last in the result line.  A host
without the TPU chips the cell asks for exits 3 with no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, the repository root takes this directory's place, so
# that bench modules are imported as ``bench.<name>`` and never shadow
# the standard library
if sys.path[0] == HERE:
    sys.path[0] = ROOT
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(1, _p)
# the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, "bench", ".cache", "tpu_logs"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check  # noqa: E402
from bench import counts  # noqa: E402
from bench import graph as bgraph  # noqa: E402
from bench import program_spans  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import trace as btrace  # noqa: E402
from bench.registry import Registry  # noqa: E402


#: the program's spans whose means over the window's batches every run
#: prints on an ``info`` line (``bench/program_spans.py``)
PROGRAM_SPANS = ("sample", "stage", "queue_wait", "device_put")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chip(chips: int):
    """The TPU devices the cell needs; there is no CPU fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs


def use_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at ``path`` (a fixed directory
    in the checkout), unless ``JAX_COMPILATION_CACHE_DIR`` names one;
    every program is kept, however fast it compiled."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def info(**fields):
    print(json.dumps({"info": fields}), flush=True)


class CompileCounter:
    """XLA compiles (``/jax/core/compile/backend_compile_duration``
    events) and seconds spent tracing, lowering and compiling, by
    event."""

    def __init__(self):
        self.compiles = 0
        self.seconds = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            key = event.rsplit("/", 1)[-1]
            self.seconds[key] = self.seconds.get(key, 0.0) + duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1


class Span:
    """A host span in the profiler's trace, opened and closed by hand."""

    def __init__(self, name: str):
        self._a = jax.profiler.TraceAnnotation(name)
        self._a.__enter__()

    def close(self):
        if self._a is not None:
            self._a.__exit__(None, None, None)
            self._a = None


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


class Probe:
    """The benchmark's hooks into one ``Trainer.run``: a ``Callback``
    that stamps the host clock at every ``on_step`` and stops the run
    when the window is full, a wrapper round the source's batch stream
    (``batch_wait`` spans, the params the output check needs) and, for
    sampled sources, round its sampler (the first batches' node ids)."""

    def __init__(self, warmup: int, seconds: float, trace_steps, trace_dir,
                 compiles: CompileCounter, setup_span=None):
        self.warmup, self.seconds = warmup, seconds
        self.trace_steps, self.trace_dir = trace_steps, trace_dir
        self.compiles, self.setup_span = compiles, setup_span
        self.state = None
        self.open = False
        self.t_start = None
        self.stamps, self.targets, self.bad = [], 0, 0
        self.losses, self.snaps = [], {}
        self.wait_s, self.batch_bytes = 0.0, 0
        self.sampled, self.slot_counts = [], []
        self.compiles_at_open = None
        self.compiles_in_window = None
        self.window_span = self.step_span = self.stop_span = None

    # -- Callback ------------------------------------------------------
    def on_train_start(self, state):
        self.state = state

    def on_step(self, state):
        t = time.perf_counter()
        if state.it < 3:
            self.losses.append(state.loss)
        if state.it == self.warmup - 2 and self.trace_dir is not None:
            # a step ahead of the window: an operation already running
            # when the trace starts is not recorded
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        if state.it == self.warmup - 1:
            self._open(t)
        elif self.open:
            self.stamps.append(t)
            self.targets += state.n_nodes
            self.bad += int(state.step_bad)
            full = (t - self.t_start >= self.seconds
                    or (self.trace_dir is not None
                        and len(self.stamps) >= self.trace_steps))
            if full:
                self._close()
                state.request_stop("benchmark window full")

    def on_eval(self, state):
        pass

    def on_stop(self, state):
        self.stop_span = Span("eval")

    def on_train_end(self, state):
        if self.stop_span is not None:
            self.stop_span.close()

    def _open(self, t):
        if self.setup_span is not None:
            self.setup_span.close()
        if self.trace_dir is not None:
            self.window_span = Span("window")
            t = time.perf_counter()
        self.t_start = t
        self.compiles_at_open = self.compiles.compiles
        self.open = True

    def _close(self):
        self.open = False
        self.compiles_in_window = self.compiles.compiles - self.compiles_at_open
        if self.step_span is not None:
            self.step_span.close()
        if self.window_span is not None:
            self.window_span.close()
            jax.profiler.stop_trace()

    # -- the source ----------------------------------------------------
    def attach(self, source, capture_batches: int = 3):
        orig_batches = source.batches

        def batches():
            stream = orig_batches()
            i = 0
            try:
                while True:
                    if i in (0, 1, 3):     # params before steps 0, 1 and 3
                        self.snaps[i] = jax.device_get(
                            (self.state.params, self.state.opt_state))
                    if self.step_span is not None:
                        self.step_span.close()
                    span = Span("batch_wait")
                    t0 = time.perf_counter()
                    item = next(stream)
                    dt = time.perf_counter() - t0
                    span.close()
                    if self.open:
                        self.wait_s += dt
                    if item[0] is not None and not self.batch_bytes:
                        self.batch_bytes = _tree_bytes(item[0])
                    self.step_span = Span("step")
                    i += 1
                    yield item
            finally:
                if self.step_span is not None:
                    self.step_span.close()
                stream.close()

        source.batches = batches
        sample = getattr(source, "_sample", None)
        if sample is None:
            return

        def sample_and_keep(*a):
            fb = sample(*a)
            if len(self.sampled) < capture_batches:
                self.sampled.append([np.array(x) for x in fb.nodes])
            self.slot_counts.append([int(m.sum()) for m in fb.masks])
            return fb

        source._sample = sample_and_keep

    # -- results -------------------------------------------------------
    def window(self) -> dict:
        ts = np.asarray([self.t_start] + self.stamps)
        gaps = np.diff(ts)
        return dict(steps=len(self.stamps), seconds=float(ts[-1] - ts[0]),
                    targets=self.targets, gaps=gaps)

    def program_readings(self, b1: float) -> dict:
        (p0, _), (p1, s1), (p3, _) = (self.snaps[i] for i in (0, 1, 3))
        g0 = jax.tree.map(lambda m: m / (1.0 - b1), s1["mu"])
        return {"losses": self.losses[:3], "p0": p0, "g0": g0, "p3": p3}


def build_source(engine, traffic: dict):
    args = dict(traffic.get("args", {}))
    if "fanouts" in args:
        args["fanouts"] = tuple(args["fanouts"])
    return getattr(engine, traffic["source"])(**args)


class Cell:
    """One cell, set up once: its entries, the chips, the compile cache,
    the graph and the program's configuration.  ``train`` drives one
    ``Trainer.run``; ``reference`` runs the float32 reference."""

    def __init__(self, workload: str, root: str = ROOT):
        self.reg = Registry(root)
        self.root, self.name = root, workload
        self.spec = self.reg.workload(workload)
        self.conf = self.reg.config(self.spec["config"])
        self.traffic = self.reg.traffic(self.spec["traffic"])
        self.model = self.reg.model(self.conf["gnn"]["model"])
        self.devs = require_chip(self.spec["chips"])
        self.cache_dir = os.path.join(root, "bench", ".cache")
        use_compile_cache(os.path.join(self.cache_dir, "jax"))
        from repro.configs.base import GNNConfig
        self.compiles = CompileCounter()
        self.arrays, self.build_s = bgraph.load(
            self.spec["config"], self.conf["data"], self.graph_cache)
        self.n = self.arrays["labels"].size
        gnn = dict(self.conf["gnn"], fanout=tuple(self.conf["gnn"]["fanout"]))
        self.cfg = GNNConfig(**gnn, n_nodes=self.n)
        self.cfg.validate()
        if self.conf["plan"]["optimizer"] != "adamw":
            raise ValueError("the output check reads Adam's first moment; "
                             f"{self.conf['plan']['optimizer']!r} has none")
        if self.traffic["warmup_steps"] < 3:
            raise ValueError("the output check reads the first three "
                             "steps: warm up at least 3")
        deg = np.diff(self.arrays["indptr"])
        self.kept_edges = int(np.minimum(deg, self.cfg.max_degree).sum())
        self.edges = int(deg.sum())

    @property
    def graph_cache(self) -> str:
        return os.path.join(self.cache_dir, "graphs")

    def train(self, seed: int, seconds: float, trace_dir=None,
              setup_span=None, keep_graph=False) -> "Probe":
        """One ``Trainer.run`` through the program's normal path, stopped
        once the window is full.  The program's state is freed before this
        returns; with ``keep_graph`` its graph object, and the device
        arrays the program cached on it, stay for the next ``train``."""
        from repro.core import engine
        from repro.core.graph import Graph
        plan_conf = self.conf["plan"]
        plan = engine.TrainPlan(n_iters=1 << 40, eval_every=1 << 40,
                                seed=seed, optimizer="adamw",
                                lr=plan_conf["lr"],
                                weight_decay=plan_conf["weight_decay"])
        source = build_source(engine, self.traffic)
        probe = Probe(self.traffic["warmup_steps"], seconds,
                      self.traffic.get("trace_steps"), trace_dir,
                      self.compiles, setup_span)
        probe.attach(source)
        g = getattr(self, "_graph", None) or Graph(
            n=self.n, **{f: self.arrays[f] for f in bgraph.FIELDS})
        self._graph = g if keep_graph else None
        trainer = engine.Trainer(g, self.cfg, plan, source=source,
                                 extra_callbacks=[probe])
        trainer.run()
        probe.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devs[:self.spec["chips"]])
        probe.readings = probe.program_readings(plan_conf["b1"])
        del trainer, source, g
        probe.state = probe.snaps = None
        gc.collect()
        jax.clear_caches()
        return probe

    def reference(self, seed: int, nodes=None, lowp=None,
                  half_batch=False, precision="highest") -> dict:
        ell = None
        if self.traffic["source"] == "FullGraphSource":
            k = self.cfg.max_degree
            ell = bgraph.derived(
                self.spec["config"], self.conf["data"], self.graph_cache,
                f"ell{k}", ("idx", "kept"),
                lambda: R.capped_ell(self.arrays["indptr"],
                                     self.arrays["indices"], k))
        return check.reference_run(self.conf, self.traffic, self.arrays,
                                   seed, nodes=nodes, ell=ell, lowp=lowp,
                                   half_batch=half_batch,
                                   precision=precision, model=self.model)

    def step_counts(self, probe: "Probe") -> dict:
        if self.traffic["source"] == "FullGraphSource":
            return counts.fullgraph(self.conf["gnn"], self.n,
                                    self.kept_edges, self.model)
        args = self.traffic["args"]
        return counts.sampled(self.conf["gnn"], args["batch_size"],
                              args["fanouts"],
                              np.mean(probe.slot_counts, axis=0), self.model)


def main(argv=None, root: str = ROOT) -> int:
    args = parse(argv)
    setup_span = Span("setup")
    cell = Cell(args.workload, root)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(cell.cache_dir, "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    probe = cell.train(args.seed, args.seconds, trace_dir, setup_span)
    win = probe.window()
    gaps = win["gaps"]
    info(graph_build_s=cell.build_s, compile_s=dict(cell.compiles.seconds),
         compiles_in_window=probe.compiles_in_window,
         window_steps=win["steps"], window_s=win["seconds"],
         step_median_max_s=([float(np.median(gaps)), float(gaps.max())]
                            if gaps.size else None),
         step_p90_s=float(np.percentile(gaps, 90)) if gaps.size else None,
         span_ms=program_spans.span_means(win["steps"], PROGRAM_SPANS),
         bad_steps=probe.bad, memory_peak_bytes=probe.memory_peak,
         ell_kept_edge_share=cell.kept_edges / max(cell.edges, 1))

    devs = cell.devs
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": probe.memory_peak}
    result = {"correct": False, "attempted": win["steps"],
              "failed": probe.bad}
    if args.trace:
        peaks = counts.peaks(os.path.join(root, "bench", "peaks.json"),
                             devs[0].device_kind)
        step_counts = cell.step_counts(probe)
        reduced = btrace.reduce(btrace.load(trace_dir),
                                n_chips=cell.spec["chips"])
        record = dict(trace=reduced, counts=step_counts, peaks=peaks,
                      chips=cell.spec["chips"], window=win,
                      least_agg_s=counts.least_seconds(
                          step_counts["agg_calls"], peaks),
                      batch_wait_s=probe.wait_s,
                      batch_bytes=probe.batch_bytes)
        metrics = {}
        for m in cell.reg.metrics("per_layer", args.workload):
            v = cell.reg.reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        # an end-to-end metric is one of these, named with an optional
        # ``.<kind>`` suffix where cells of different kinds take it apart
        values = {"setup_s": probe.t_start - T0,
                  "train_targets_per_s": win["targets"] / win["seconds"],
                  "step_p95_ms": float(np.percentile(win["gaps"], 95)) * 1e3}
        metrics = {m["name"]: {"value": values[m["name"].split(".")[0]],
                               "unit": m["unit"]}
                   for m in cell.reg.metrics("end_to_end", args.workload)}

    t_ref = time.perf_counter()
    values = check.numbers(probe.readings,
                           cell.reference(args.seed, nodes=probe.sampled))
    info(reference_s=time.perf_counter() - t_ref,
         compile_s=dict(cell.compiles.seconds), readings=values)
    ok, checks = check.judge(values, cell.reg.limits(args.workload))
    ok = ok and win["steps"] > 0 and probe.bad == 0
    result.update(correct=ok, metrics=metrics, device=device, checks=checks)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
