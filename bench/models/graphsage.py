"""GraphSAGE with the mean aggregator (Hamilton et al. 2017,
arXiv:1706.02216), as the repository's ``core/gnn.py`` computes it:
``h_self @ w_self + mean_k(h_nb) @ w_neigh``.  The interface is
``bench/models/__init__.py``'s."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.counts import agg_call
from bench.reference import F32, _mm, layer_dims  # noqa: F401

#: the self rows are the layer's input table, not the transformed source
SELF_FROM_SOURCE = False


def init_layer(key, d_in, d_out, last, gnn):
    """Normal(0, 1/d_in) ``w_self`` and ``w_neigh`` from the key's split."""
    sc = 1.0 / math.sqrt(d_in)
    k1, k2 = jax.random.split(key)
    return {"w_self": sc * jax.random.normal(k1, (d_in, d_out), F32),
            "w_neigh": sc * jax.random.normal(k2, (d_in, d_out), F32)}


def transforms_first(d_in, d_out):
    """A layer that narrows gathers ``h @ w_neigh``."""
    return d_out < d_in


def transform(p, table, lowp):
    return _mm(table, p["w_neigh"], lowp)


def layer(p, last, self_rows, nb_rows, w, mask, w_self, pre, lowp):
    cnt = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
    mean = jnp.einsum("...k,...kd->...d", mask, nb_rows) / cnt
    return (_mm(self_rows, p["w_self"], lowp)
            + (mean if pre else _mm(mean, p["w_neigh"], lowp)))


def layer_counts(gnn, rows, edges, d_in, d_out, item, pre, last):
    """One mean aggregation of the gathered width, no self term in it;
    two dense products."""
    call = agg_call(rows, edges, d_out if pre else d_in, item, False)
    return [call], 2 * (2.0 * rows * d_in * d_out)
