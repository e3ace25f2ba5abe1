"""Host spans and counters of the training pipeline, in one log.

``span(name, batch)`` times a piece of host work for one batch (the
iteration that consumes it) and ``count(name, value)`` adds to a named
counter.  Both land in one in-process log that ``snapshot()`` reads
back; ``Trainer.__init__`` clears it, so after a run it holds that
run's batches.

Each span is also opened as ``jax.profiler.TraceAnnotation(name,
batch=...)``: under a profiler session (``jax.profiler.trace``) it sits
on the device trace's clock, on its own thread's host line, under its
bare name.  With no session the annotation records nothing, and a span
costs two clock reads and an append under the log's lock.

Spans nest per thread: a span opened inside another records it as its
``parent``, and one opened with ``batch=None`` takes its parent's batch.
A span with neither is left out of the log (it still annotates the
profiler's trace).  The log keeps the last ``MAX_BATCHES`` batches.

Span names: ``sample``, ``stage`` and its child ``ring_wait``,
``queue_wait`` and ``device_put`` (``core/prefetch.py``,
``core/engine.py``).  Counters: ``ell_slots`` and ``ell_edges``, the
ELL entries one aggregation call reads and those that hold an edge
(``FullGraphSource.bind``); ``hoisted_agg_layers``, 1 where that bind
computed layer 0's aggregation of the fixed feature table once for every
step (``gnn.layer0_agg``), else 0; ``attn_cols`` and ``attn_kernel_cols``,
GAT's real columns summed over one forward's attention-aggregation calls
and the columns those calls read as the kernel pads each head (the same
bind); ``device_gather_rows``, the node ids of every staged sampled
batch, whose feature rows the step gathers on the device
(``SampledSource._host_batch``).

GAT's edge logits and softmax run under ``jax.named_scope
("gat_attention")`` (``core/gnn.py``), which names their device
operations in a profile.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax

#: batches the log keeps; older ones are dropped as new ones arrive
MAX_BATCHES = 1024


class Span(NamedTuple):
    name: str
    batch: int
    thread: str
    start_ns: int                 # time.perf_counter_ns()
    end_ns: int
    parent: Optional[str]         # enclosing span on the same thread


class Log:
    """The spans of the last ``MAX_BATCHES`` batches, the counters, and
    the last batch drawn when the run was first asked to stop.  Every
    write takes the log's lock, so worker and main threads may record at
    once."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open = threading.local()       # per-thread stack of spans
        self._spans: "collections.OrderedDict[int, List[Span]]" = \
            collections.OrderedDict()
        self._counters: Dict[str, float] = {}
        self._stop_batch: Optional[int] = None

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()
            self._stop_batch = None

    @contextlib.contextmanager
    def span(self, name: str, batch: Optional[int] = None) -> Iterator[None]:
        stack = self._open.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        if batch is None and parent is not None:
            batch = parent[1]
        stack.append((name, batch))
        try:
            with jax.profiler.TraceAnnotation(name, batch=batch):
                t0 = time.perf_counter_ns()
                try:
                    yield
                finally:
                    # a span that raised is kept: a batch replayed after
                    # a transient fault counts both attempts
                    if batch is not None:
                        self._add(Span(
                            name, batch, threading.current_thread().name,
                            t0, time.perf_counter_ns(),
                            parent[0] if parent else None))
        finally:
            stack.pop()

    def _add(self, span: Span) -> None:
        with self._lock:
            spans = self._spans.get(span.batch)
            if spans is None:
                spans = self._spans[span.batch] = []
                while len(self._spans) > MAX_BATCHES:
                    self._spans.popitem(last=False)
            spans.append(span)

    def count(self, name: str, value: float) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def note_stop(self, batch: int) -> None:
        """The last batch the training loop had drawn when it was first
        asked to stop; later notes are ignored."""
        with self._lock:
            if self._stop_batch is None:
                self._stop_batch = batch

    def snapshot(self) -> dict:
        """``spans`` (by batch, in order of arrival), ``counters`` and
        ``stop_batch`` (None until a stop was asked for), copied."""
        with self._lock:
            return {"spans": [s for spans in self._spans.values()
                              for s in spans],
                    "counters": dict(self._counters),
                    "stop_batch": self._stop_batch}


#: the process's log, which the pipeline records into
LOG = Log()
span = LOG.span
count = LOG.count
note_stop = LOG.note_stop
snapshot = LOG.snapshot
clear = LOG.clear
