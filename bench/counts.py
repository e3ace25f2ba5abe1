"""Operations and bytes of a training step, from shapes; each model's
module (``bench/models``) gives its layers' aggregation calls and dense
FLOPs.  They count the work the algorithm needs, not what an
implementation moves: a later PR that changes the tiling, pads less or
skips padded slots is judged against the same numbers.

Aggregation (one call of the weighted neighbour sum): per real
(nonzero-weight) edge, one neighbour row at the table's dtype plus its
int32 id and its weight at the table's dtype; one written row per output
row; with a fused self term, one self row and its weight per output row.
Two operations per edge and column (multiply, add), two per output
element for the self term.  ELL padding and masked fan-out slots are
not work.

Model FLOPs of a step: the dense transforms and the aggregations of the
forward, with the backward counted as twice the forward; nothing for
recomputation.  A full-graph forward computes every row of every layer,
as the method is defined.
"""
from __future__ import annotations

import json
from typing import Dict, Sequence

from bench import models


def agg_call(out_rows: int, edges: int, d: int, itemsize: int,
             fused_self: bool) -> Dict[str, float]:
    """FLOPs and bytes of one weighted-neighbour-sum call."""
    nbytes = edges * (d * itemsize + 4 + itemsize) + out_rows * d * itemsize
    flops = 2.0 * edges * d
    if fused_self:
        nbytes += out_rows * (d * itemsize + itemsize)
        flops += 2.0 * out_rows * d
    return {"flops": float(flops), "bytes": float(nbytes)}


def fullgraph(gnn: dict, n: int, edges: int, model=None) -> Dict[str, object]:
    """Counts of one full-graph step over ``n`` nodes whose capped ELL
    holds ``edges`` real edges.  Aggregation tables are in the
    configuration's ``dtype``; the model says whether a layer transforms
    first.  ``model`` is the model's module, by default the one
    ``gnn["model"]`` names."""
    m = model or models.load(gnn["model"])
    item = 2 if gnn["dtype"] == "bfloat16" else 4
    dims = m.layer_dims(gnn)
    calls, fwd = [], 0.0
    for li, (d_in, d_out) in enumerate(dims):
        lc, dense = m.layer_counts(gnn, n, edges, d_in, d_out, item,
                                   m.transforms_first(d_in, d_out),
                                   li == len(dims) - 1)
        calls.extend(lc)
        fwd += sum(c["flops"] for c in lc) + dense
    return {"agg_calls": calls, "model_flops": 3.0 * fwd}


def sampled(gnn: dict, batch: int, fanouts: Sequence[int],
            edges: Sequence[float], model=None) -> Dict[str, object]:
    """Counts of one sampled step: ``batch`` targets, ``edges[d]`` real
    slots from hop ``d`` to hop ``d + 1`` (averaged over the batches).
    Hop rows are float32 and arrive untransformed; layer ``l`` aggregates
    hops ``d < L - l``.  ``model`` as in ``fullgraph``."""
    m = model or models.load(gnn["model"])
    n_layers = gnn["n_layers"]
    rows = [batch]
    for f in fanouts:
        rows.append(rows[-1] * f)
    dims = m.layer_dims(gnn)
    calls, fwd = [], 0.0
    for li, (d_in, d_out) in enumerate(dims):
        for d in range(n_layers - li):
            lc, dense = m.layer_counts(gnn, rows[d], int(round(edges[d])),
                                       d_in, d_out, 4, False,
                                       li == len(dims) - 1)
            calls.extend(lc)
            fwd += sum(c["flops"] for c in lc) + dense
    return {"agg_calls": calls, "model_flops": 3.0 * fwd}


def least_seconds(calls: Sequence[Dict[str, float]], peaks: dict) -> float:
    """The least time the chip needs for ``calls``: for each, the larger of
    its FLOPs over peak FLOP/s and its bytes over peak bandwidth."""
    return sum(max(c["flops"] / peaks["flops_per_s"],
                   c["bytes"] / peaks["bytes_per_s"]) for c in calls)


def peaks(path: str, device_kind: str) -> dict:
    """The chip's peaks from the table at ``path``; a device that is not
    in the table is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
