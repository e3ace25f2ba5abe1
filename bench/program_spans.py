"""Readings of the program's own spans and counters (``repro.core.tracing``)
after a run, for the per-layer metrics that read them.

The program's log holds one run's spans, each tagged with the batch it
worked on (the iteration that consumes it), and the last batch the
training loop had drawn when the benchmark asked it to stop.  The
window's batches are the ``record["window"]["steps"]`` batches that end
there: the ones whose ``next()`` fell inside the benchmark's window, so
that they are the batches its ``batch_wait`` span covers.  Under the
engine's deferred loss sync they run one ahead of the window's steps.

A program without that log (``ImportError``), a traced window with no
device time (a CPU run: its host split is not a chip's) or a window batch
without the span gives ``None``: the metric stays out of the line.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def snapshot() -> Optional[dict]:
    """The program's log, or None where the program has none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.snapshot()


def window_batches(record: dict, snap: dict) -> Optional[List[int]]:
    """The ids of the traced window's batches."""
    steps, stop = record["window"]["steps"], snap["stop_batch"]
    if not steps or stop is None:
        return None
    return list(range(stop - steps + 1, stop + 1))


def mean_span_ms(record: dict, name: str) -> Optional[float]:
    """Mean over the window's batches of each batch's summed self time
    in spans called ``name`` (ms): a span's length less that of its child
    spans.  Every attempt at a batch counts."""
    if record["trace"]["busy_s"] <= 0:
        return None
    snap = snapshot()
    if snap is None:
        return None
    ids = window_batches(record, snap)
    if ids is None:
        return None
    return _mean_self_ms(snap, ids, name)


def span_means(steps: int, names) -> Dict[str, Optional[float]]:
    """``mean_span_ms`` of each of ``names`` over a window of ``steps``
    steps, with no trace to ask: for a run's ``info`` line, never a
    metric.  Empty where the program keeps no log."""
    snap = snapshot()
    if snap is None:
        return {}
    ids = window_batches({"window": {"steps": steps}}, snap)
    if ids is None:
        return {}
    return {name: _mean_self_ms(snap, ids, name) for name in names}


def _mean_self_ms(snap: dict, ids: List[int], name: str) -> Optional[float]:
    own = {i: 0 for i in ids}
    seen = set()
    for s in snap["spans"]:
        if s.batch not in own:
            continue
        if s.name == name:
            own[s.batch] += s.end_ns - s.start_ns
            seen.add(s.batch)
        elif s.parent == name:
            own[s.batch] -= s.end_ns - s.start_ns
    if len(seen) != len(ids):
        return None
    return sum(own.values()) / len(ids) / 1e6


def pad_share() -> Optional[float]:
    """100 × (1 − ``ell_edges`` / ``ell_slots``): the share of the ELL
    entries an aggregation call reads that hold no edge (%)."""
    snap = snapshot()
    if snap is None:
        return None
    c = snap["counters"]
    if not c.get("ell_slots"):
        return None
    return 100.0 * (1.0 - c["ell_edges"] / c["ell_slots"])
