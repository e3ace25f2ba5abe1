"""What the benchmark knows of each GNN model, one module per model.

A configuration's ``gnn.model`` names its module: ``bench/models/<model>.py``
under the checkout's root.  The float32 reference (``bench/reference.py``)
and the step counts (``bench/counts.py``) hold what no model owns (Adam,
the loss, the capped adjacency, the sampled batch tensors, the full-graph
and mini-batch drivers, the aggregation call's bytes) and ask the module
for the rest.  A new architecture is a new module, a configuration that
names it, its cells' limits and, where it needs one, a traffic file; no
existing file changes.

A module defines, with ``gnn`` the configuration's ``gnn`` group:

``layer_dims(gnn) -> [(d_in, d_out), ...]``
    Each layer's input and output width, as the tables between layers
    hold them (``bench.reference.layer_dims`` where they are the shared
    ones: features, hidden on every inner layer, classes last).

``init_layer(key, d_in, d_out, last, gnn) -> dict``
    One layer's float32 parameters from its own key (the run's seed with
    the layer's index folded in).  Leaves may have any shape: GAT's are
    per-head weights ``[d_in, heads, d_head]`` and attention vectors
    ``[heads, d_head]``, and its last layer's ``d_head`` is ``d_out``.

``transforms_first(d_in, d_out) -> bool``
    Whether a full-graph layer transforms its input table before the
    gather, so that the gather moves rows of the transformed width.

``transform(p, table, lowp)``
    That transform of a whole table, its operands rounded by
    ``bench.reference._round`` / ``_mm``: ``[n, ...]`` rows.

``SELF_FROM_SOURCE: bool``
    Whether a layer's self rows are the transformed source table (GCN's
    fused self term, GAT's self edge) rather than the untransformed input
    table; the full-graph backward then adds the self rows' gradient to
    the source's.

``layer(p, last, self_rows, nb_rows, w, mask, w_self, pre, lowp)``
    One layer over a block of output rows before the activation: their
    self rows ``[..., d]``, gathered neighbour rows ``[..., K, d]``, the
    symmetric-normalised edge weights ``w`` and the real-slot ``mask``
    ``[..., K]``, the self weights ``w_self`` ``[...]``.  ``pre`` says
    the rows arrive transformed.  Returns ``[..., d_out]`` rows; a last
    layer with several heads averages them here.  The drivers apply ReLU
    to every layer but the last.

``layer_counts(gnn, rows, edges, d_in, d_out, item, pre, last)``
    ``-> (calls, dense_flops)``: one forward layer's aggregation calls
    (``bench.counts.agg_call`` dicts, or the module's own ``flops`` and
    ``bytes`` where its aggregation moves other things) over ``rows``
    output rows and ``edges`` real edges with tables of ``item`` bytes an
    element, and the FLOPs of its dense products.  ``pre`` as above.
"""
from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = None):
    """The module of model ``name``: ``<root>/bench/models/<name>.py``, or
    this directory's where ``root`` is None.  A model without a module is
    a ``ValueError`` that names it."""
    where = HERE if root is None else os.path.join(root, "bench", "models")
    path = os.path.join(where, f"{name}.py")
    if not re.fullmatch(r"[A-Za-z0-9_]+", name) or not os.path.isfile(path):
        raise ValueError(f"no benchmark module for model {name!r} "
                         f"(looked for {path})")
    spec = importlib.util.spec_from_file_location("bench_model_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
