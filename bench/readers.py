"""Readers of the per-layer metrics that full-graph and sampled cells
share, each over the record of one traced run.  A metric's file in
``bench/metrics`` names one of these (or holds a reader of its own); a
reader that finds nothing to read returns None, and the metric stays out
of the result line."""


def device_idle_share(record):
    """The share of the traced window (%) in which no operation ran on
    the chip, from the profiler's device trace (busy time is the union of
    the operations' intervals, averaged over the chips)."""
    t = record["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def agg_fwd_roofline(record):
    """The tiled aggregation kernel's share of its roofline (%).  The
    least time of a call is the larger of its FLOPs over the chip's peak
    FLOP/s and its bytes over the peak bandwidth, both counted from shapes
    by ``bench/counts.py`` (the work of the weighted neighbour sum, not
    what the kernel moves); the kernel's time is the sum of its device
    events in the traced window.  A step's calls are averaged, so
    ``least_agg_s / calls_per_step`` is the least time of one call."""
    t = record["trace"]
    calls = len(record["counts"]["agg_calls"])
    if not t["kernel_calls"] or t["kernel_s"] <= 0 or not calls:
        return None
    least = record["least_agg_s"] / calls * t["kernel_calls"]
    return 100.0 * least / t["kernel_s"]


def step_mfu(record):
    """The whole training step's model FLOPs per second over the chips'
    peak (%).  FLOPs per step come from shapes (``bench/counts.py``: dense
    transforms and aggregation, backward as twice the forward, no
    recomputation); steps per second are the traced window's steps over
    the window's length on the trace's clock."""
    t, w = record["trace"], record["window"]
    if not w["steps"] or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    rate = record["counts"]["model_flops"] * w["steps"] / t["window_s"]
    return 100.0 * rate / (record["peaks"]["flops_per_s"] * record["chips"])
