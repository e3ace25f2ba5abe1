"""GAT (Veličković et al. 2018, arXiv:1710.10903), as the repository's
``core/gnn.py`` computes it: per head ``z_j = W h_j``, edge logits
``e_ij = LeakyReLU_0.2(a_src·z_i + a_dst·z_j)``, ``alpha_ij`` the softmax of
``e_ij`` over the row's real neighbours and its self edge, ``out_i =
sum_j alpha_ij z_j``; heads concatenated on hidden layers and averaged on
the last.  The normalised edge weights play no part.  The interface is
``bench/models/__init__.py``'s.

The reference gathers untransformed rows and applies ``W`` to every slot
(``transforms_first`` is False), which is plainly the definition; the
program transforms once per node and gathers ``z``.  ``layer_counts``
counts the latter, the algorithm's work.  A configuration states
``gat_heads``, and ``hidden`` splits evenly over them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.counts import agg_call
from bench.reference import F32, _round

#: the self edge attends over the same rows as the neighbours
SELF_FROM_SOURCE = True


def heads_of(gnn) -> int:
    """The configuration's ``gat_heads``; a ``ValueError`` where it is not
    stated or ``hidden`` does not split over it."""
    heads = gnn.get("gat_heads")
    if not isinstance(heads, int) or heads < 1:
        raise ValueError(f"a GAT configuration states gat_heads >= 1, "
                         f"got {heads!r}")
    if gnn["hidden"] % heads:
        raise ValueError(f"hidden {gnn['hidden']} does not split over "
                         f"{heads} heads")
    return heads


def head_dims(gnn, d_out, last):
    """``(heads, d_head)`` of a layer: hidden layers split ``d_out`` over
    the heads, the last layer gives each head all ``d_out`` classes."""
    heads = heads_of(gnn)
    return heads, d_out if last else d_out // heads


def layer_dims(gnn):
    heads_of(gnn)
    return R.layer_dims(gnn)


def init_layer(key, d_in, d_out, last, gnn):
    """Normal(0, 1/d_in) ``w [d_in, heads, d_head]``, Normal(0, 0.01)
    ``a_src`` and ``a_dst`` ``[heads, d_head]``, from the key's three-way
    split."""
    heads, dh = head_dims(gnn, d_out, last)
    sc = 1.0 / math.sqrt(d_in)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w": sc * jax.random.normal(k1, (d_in, heads, dh), F32),
            "a_src": 0.1 * jax.random.normal(k2, (heads, dh), F32),
            "a_dst": 0.1 * jax.random.normal(k3, (heads, dh), F32)}


def transforms_first(d_in, d_out):
    """Never: the layer applies ``W`` itself, to every gathered row."""
    return False


def transform(p, table, lowp):
    """``W`` on every row of a table: ``[n, heads, d_head]``."""
    return jnp.einsum("nd,dhe->nhe", _round(table, lowp), _round(p["w"], lowp))


def _proj(x, w, lowp):
    return jnp.einsum("...d,dhe->...he", _round(x, lowp), _round(w, lowp))


def _score(z, a, lowp):
    return jnp.einsum("...he,he->...h", _round(z, lowp), _round(a, lowp))


def layer(p, last, self_rows, nb_rows, w, mask, w_self, pre, lowp):
    z_self = _proj(self_rows, p["w"], lowp)                  # [..., H, dh]
    z_nb = _proj(nb_rows, p["w"], lowp)                      # [..., K, H, dh]
    e_self = jax.nn.leaky_relu(_score(z_self, p["a_src"], lowp)
                               + _score(z_self, p["a_dst"], lowp), 0.2)
    e_nb = jax.nn.leaky_relu(_score(z_self, p["a_src"], lowp)[..., None, :]
                             + _score(z_nb, p["a_dst"], lowp), 0.2)
    e_nb = jnp.where(mask[..., None] > 0, e_nb, -1e30)
    alpha = jax.nn.softmax(jnp.concatenate([e_nb, e_self[..., None, :]], -2),
                           axis=-2)                          # [..., K+1, H]
    z = jnp.concatenate([z_nb, z_self[..., None, :, :]], -3)
    out = jnp.einsum("...kh,...khe->...he", alpha, _round(z, lowp))
    return out.mean(-2) if last else out.reshape(out.shape[:-2] + (-1,))


def layer_counts(gnn, rows, edges, d_in, d_out, item, pre, last):
    """The algorithm whatever ``pre`` says: ``W`` once per row and the two
    scores per row and head as dense products; per head, one weighted sum
    of ``d_head`` columns over the real edges, the self edge fused (the
    logits and softmax are elementwise and not counted)."""
    heads, dh = head_dims(gnn, d_out, last)
    calls = [agg_call(rows, edges, dh, item, True) for _ in range(heads)]
    return calls, 2.0 * rows * d_in * heads * dh + 2 * (2.0 * rows * heads * dh)
