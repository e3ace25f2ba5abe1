"""GCN (Kipf and Welling 2017, arXiv:1609.02907), as the repository's
``core/gnn.py`` computes it: ``(sum_k w_k h_k + w_self h_self) @ w`` with
symmetric-normalised weights, the self term fused into the aggregation.
The interface is ``bench/models/__init__.py``'s."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.counts import agg_call
from bench.reference import F32, _mm, layer_dims  # noqa: F401

#: the self term aggregates the same (transformed) table as the neighbours
SELF_FROM_SOURCE = True


def init_layer(key, d_in, d_out, last, gnn):
    """Normal(0, 1/d_in) ``w`` from the layer's key."""
    sc = 1.0 / math.sqrt(d_in)
    return {"w": sc * jax.random.normal(key, (d_in, d_out), F32)}


def transforms_first(d_in, d_out):
    """A layer that narrows gathers ``h @ w``."""
    return d_out < d_in


def transform(p, table, lowp):
    return _mm(table, p["w"], lowp)


def layer(p, last, self_rows, nb_rows, w, mask, w_self, pre, lowp):
    agg = (jnp.einsum("...k,...kd->...d", w, nb_rows)
           + w_self[..., None] * self_rows)
    return agg if pre else _mm(agg, p["w"], lowp)


def layer_counts(gnn, rows, edges, d_in, d_out, item, pre, last):
    """One weighted sum of the gathered width with the self term fused;
    one dense product."""
    call = agg_call(rows, edges, d_out if pre else d_in, item, True)
    return [call], 2.0 * rows * d_in * d_out
