"""Reduction of a JAX profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` a traced run wrote into plain event
lists: device operations (from each TPU plane's ``XLA Ops`` line) and
host spans (every host line's events, the benchmark's own
``TraceAnnotation`` spans among them), all on the trace's one clock.
``reduce`` works on those lists only, so the tests can hand it a small
recorded trace.

The window is the host span named ``window``; everything is clipped to
it.  Busy time is the union of a chip's operation intervals, averaged
over the chips; the idle gaps are the rest of the window, each named by
the innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

#: the Pallas aggregation kernel's ``name``; its device events carry it
KERNEL = "neighbor_agg_tiled"
HOST_SPANS = ("window", "setup", "batch_wait", "step", "eval")


def load(trace_dir: str) -> dict:
    """Device ops ``[chip, name, start_ns, end_ns, is_kernel]`` and host
    spans ``[name, start_ns, end_ns]`` of the one trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {trace_dir}, "
                                f"found {paths}")
    data = ProfileData.from_file(paths[0])
    device, host = [], []
    chips = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            chip = int(plane.name[12:])
            chips = max(chips, chip + 1)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    label = op_label(e.name)
                    name = label.split(" ")[0]
                    device.append([chip, label, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   name.partition(".")[0] == KERNEL])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, e.start_ns,
                                     e.start_ns + e.duration_ns])
    return {"device": device, "host": host, "chips": chips}


def op_label(text: str) -> str:
    """``"<name> <opcode>"`` of an ``XLA Ops`` event, whose name is the
    op's HLO text (``%fusion.85 = f32[...]{...} fusion(...), ...``)."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name
    if rest.startswith("("):                 # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    return f"{name} {rest.split('(')[0]}"


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(t: float, spans) -> str:
    inner = None
    for name, s, e in spans:
        if name != "window" and s <= t < e and (inner is None
                                                or e - s < inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0] if inner else "other"


def reduce(events: dict, n_chips: int, top: int = 10) -> Dict[str, object]:
    """-> ``window_s``, ``busy_s`` (per chip, averaged), ``kernel_s`` and
    ``kernel_calls`` (all chips), and ``breakdown``: the ``top`` device
    operations by total seconds and the ``top`` longest idle gaps of
    chip 0, named by the host span they fell in."""
    wins = [(s, e) for name, s, e in events["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span, found {len(wins)}")
    w0, w1 = wins[0]
    per_chip: Dict[int, list] = {c: [] for c in range(n_chips)}
    by_name: Dict[str, float] = {}
    kernel_ns, kernel_calls = 0.0, 0
    for chip, name, s, e, is_kernel in events["device"]:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        per_chip.setdefault(chip, []).append((s, e))
        by_name[name] = by_name.get(name, 0.0) + (e - s)
        if is_kernel:
            kernel_ns += e - s
            kernel_calls += 1
    busy = {c: _union(iv) for c, iv in per_chip.items()}
    busy_ns = sum(sum(e - s for s, e in u) for u in busy.values()) / n_chips
    gaps, t = [], w0
    for s, e in busy.get(0, []) + [(w1, w1)]:
        if s > t:
            gaps.append((s - t, _label((s + t) / 2, events["host"])))
        t = max(t, e)
    gaps.sort(reverse=True)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / 1e9,
            "kernel_s": kernel_ns / 1e9, "kernel_calls": kernel_calls,
            "breakdown": {
                "device_ops": [[name, ns / 1e9] for name, ns in ops],
                "idle_gaps": [[label, ns / 1e9] for ns, label in gaps[:top]]}}
