"""Plain float32 references of the benchmark's training cells.

Nothing here imports the program: the initial weights, the capped
adjacency, the mini-batch tensors, the forwards, the loss and the
optimizer are written out again from their published descriptions (the
repository's ``core/gnn.py``, ``core/graph.py``, ``core/sampler.py`` and
``optim/optimizers.py`` implement the same equations), and every matrix
product runs at ``highest`` precision.  What belongs to one model (its
weights, its layer, whether it transforms before the gather) is in its
module under ``bench/models``; the drivers here name no model.  A run
compares what its timed path produced with these, in ``bench/check.py``.

``lowp`` (a dtype or None) rounds every aggregation table and both
operands of every matrix product to that dtype: the reference computed
in float8, one precision below the configuration's bfloat16, is the
benchmark's control.  ``half_batch`` drops every second loss row and
takes the mean over the rest: a planted fault.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import models

F32 = jnp.float32
#: output rows per chunk of the full-graph reference
CHUNK = 2048


# ---------------------------------------------------------------------------
# weights and optimizer
# ---------------------------------------------------------------------------

def layer_dims(gnn: dict):
    dims, d_in = [], gnn["feat_dim"]
    for li in range(gnn["n_layers"]):
        d_out = gnn["n_classes"] if li == gnn["n_layers"] - 1 else gnn["hidden"]
        dims.append((d_in, d_out))
        d_in = d_out
    return dims


def init_params(gnn: dict, seed: int, model=None):
    """Each layer's weights from ``jax.random.key(seed)`` with the layer's
    index folded in, by the model's ``init_layer``.  ``model`` is the
    model's module (``bench/models``), by default the one ``gnn["model"]``
    names."""
    m = model or models.load(gnn["model"])
    key = jax.random.key(seed)
    dims = m.layer_dims(gnn)
    return [m.init_layer(jax.random.fold_in(key, li), d_in, d_out,
                         li == len(dims) - 1, gnn)
            for li, (d_in, d_out) in enumerate(dims)]


def adam(plan: dict):
    """Adam with bias correction, gradients first clipped to global norm
    ``clip_norm``, decoupled weight decay.  -> (init, update)."""
    lr, b1, b2 = plan["lr"], plan["b1"], plan["b2"]
    eps, wd, clip = plan["eps"], plan["weight_decay"], plan["clip_norm"]

    def init(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return {"mu": z, "nu": z, "t": jnp.zeros((), F32)}

    def update(grads, state, params):
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / (norm + 1e-9)),
                             grads)
        t = state["t"] + 1.0
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                          grads)

        def step(p, m, v):
            d = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return p - lr * (d + wd * p)

        return (jax.tree.map(step, params, mu, nu),
                {"mu": mu, "nu": nu, "t": t}, grads)

    return init, update


def ce_loss(logits, labels, valid):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - ll) * valid) / jnp.sum(valid)


def _round(x, lowp):
    """``x`` as if stored in ``lowp``, by ``reduce_precision`` (XLA may
    fold a round trip through ``astype`` away); the gradient passes
    unrounded."""
    if lowp is None:
        return x
    f = ml_dtypes.finfo(lowp)
    return x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, f.nexp, f.nmant) - x)


def _mm(a, b, lowp):
    return _round(a, lowp) @ _round(b, lowp)


def _layer(m, p, last, self_rows, nb_rows, w, mask, w_self, pre,
           lowp=None):
    """One layer of model module ``m`` for a block of output rows from its
    gathered rows (where ``pre``, already transformed); ReLU on every
    layer but the last."""
    out = m.layer(p, last, self_rows, nb_rows, w, mask, w_self, pre, lowp)
    return out if last else jax.nn.relu(out)


# ---------------------------------------------------------------------------
# full graph
# ---------------------------------------------------------------------------

def capped_ell(indptr, indices, k):
    """Each row's neighbours capped at ``k``: the ``k`` highest-weight ones,
    weight ``1/sqrt((deg_u + 1)(deg_v + 1))`` in float32, ties in CSR
    order; narrower rows keep all of theirs.  -> (idx [n, k] int32, kept
    [n] int32), the kept neighbours first in each row."""
    n = indptr.size - 1
    deg = np.diff(indptr)
    seg = np.repeat(np.arange(n, dtype=np.int64), deg)
    pos = np.arange(seg.size, dtype=np.int64) - indptr[seg]
    cw = (1.0 / np.sqrt((deg[seg] + 1.0) * (deg[indices] + 1.0))
          ).astype(np.float32)
    rank = pos.copy()
    sub = np.nonzero(deg[seg] > k)[0]
    order = np.lexsort((pos[sub], -cw[sub], seg[sub]))
    rank[sub[order]] = pos[sub]
    keep = rank < k
    idx = np.zeros((n, k), np.int32)
    idx[seg[keep], rank[keep]] = indices[keep]
    return idx, np.minimum(deg, k).astype(np.int32)


def fullgraph_plan(g, ell_idx, kept, n_layers):
    """Host index plan of the full-graph reference: layer ``l`` computes
    only the rows that the loss reaches, ``rows[l]`` (the training nodes
    for the last layer, and the capped neighbourhood of the next layer's
    rows, with those rows, below it).  Its gathers address the previous
    layer's rows (all nodes below layer 1) by position.  Each layer's
    rows are padded to a multiple of ``CHUNK`` with masked rows."""
    n = g["labels"].size
    deg = np.diff(g["indptr"])
    k = ell_idx.shape[1]
    slot_ok = np.arange(k)[None, :] < kept[:, None]
    rows = [None] * (n_layers + 1)
    rows[n_layers] = np.nonzero(g["train_mask"])[0]
    for l in range(n_layers - 1, 0, -1):
        r = rows[l + 1]
        rows[l] = np.union1d(r, ell_idx[r][slot_ok[r]])
    rows[0] = np.arange(n)
    layers = []
    for l in range(1, n_layers + 1):
        r = rows[l]
        pad = (-r.size) % CHUNK
        nb = ell_idx[r]
        ok = slot_ok[r]
        below = rows[l - 1]
        loc = np.searchsorted(below, np.where(ok, nb, below[0]))
        w = np.where(ok, 1.0 / np.sqrt((deg[r][:, None] + 1.0)
                                       * (deg[nb] + 1.0)), 0.0)
        layers.append(dict(
            nb=np.pad(np.where(ok, loc, 0), ((0, pad), (0, 0))).astype(np.int32),
            self=np.pad(np.searchsorted(below, r), (0, pad)).astype(np.int32),
            w=np.pad(w, ((0, pad), (0, 0))).astype(np.float32),
            mask=np.pad(ok, ((0, pad), (0, 0))).astype(np.float32),
            w_self=np.pad(1.0 / (deg[r] + 1.0), (0, pad)).astype(np.float32)))
    tr = rows[n_layers]
    pad = (-tr.size) % CHUNK
    return dict(layers=layers,
                labels=np.pad(g["labels"][tr], (0, pad)).astype(np.int32),
                valid=np.pad(np.ones(tr.size, np.float32), (0, pad)))


def fullgraph_step(gnn, plan, lowp=None, half_batch=False, model=None):
    """Jitted ``(params, opt_state, feats, data) -> (loss, grads as the
    optimizer gets them, params, opt_state)``: one full-graph training
    step of the reference, layer by layer in chunks of ``CHUNK`` rows.
    The backward runs chunk by chunk too: each chunk's VJP over its own
    gathered rows, scatter-added into the layer's input table, so no
    [rows, K, d] gather is ever whole.  ``model`` as in ``init_params``."""
    m = model or models.load(gnn["model"])
    dims = m.layer_dims(gnn)
    init, update = adam(plan)

    def chunks(x):
        return x.reshape((-1, CHUNK) + x.shape[1:])

    def run(params, opt_state, feats, data):
        n_layers = len(params)

        def layer_fn(l, p, table):
            last = l == n_layers - 1
            pre = m.transforms_first(*dims[l])
            src = _round(m.transform(p, table, lowp) if pre else table, lowp)
            selft = src if m.SELF_FROM_SOURCE else _round(table, lowp)
            d = data["layers"][l]

            def fn(p, self_rows, nb_rows, w, mask, w_self):
                return _layer(m, p, last, self_rows, nb_rows, w, mask,
                              w_self, pre, lowp)
            return src, selft, d, fn

        def forward(params):
            tables = [feats]
            for l, p in enumerate(params):
                src, selft, d, fn = layer_fn(l, p, tables[-1])
                out = jax.lax.map(
                    lambda c: fn(p, selft[c[0]], src[c[1]], c[2], c[3], c[4]),
                    (chunks(d["self"]), chunks(d["nb"]), chunks(d["w"]),
                     chunks(d["mask"]), chunks(d["w_self"])))
                tables.append(out.reshape(-1, out.shape[-1]))
            return tables

        def loss_of(logits):
            valid = data["valid"]
            if half_batch:
                valid = valid * (jnp.arange(valid.size) % 2 == 0)
            return ce_loss(logits, data["labels"], valid)

        def backward(params, tables, g):
            grads = [None] * n_layers
            for l in reversed(range(n_layers)):
                p, table = params[l], tables[l]
                pre = m.transforms_first(*dims[l])
                d = data["layers"][l]
                src, src_vjp = jax.vjp(
                    lambda p, t: _round(m.transform(p, t, lowp)
                                        if pre else t, lowp), p, table)
                selft = src if m.SELF_FROM_SOURCE else _round(table, lowp)
                _, _, _, fn = layer_fn(l, p, table)
                gc = chunks(g)
                xs = (chunks(d["self"]), chunks(d["nb"]), chunks(d["w"]),
                      chunks(d["mask"]), chunks(d["w_self"]))
                need_dh = l > 0

                def body(i, acc):
                    si, ni, w, mask, ws = (x[i] for x in xs)
                    if not need_dh:
                        _, vjp = jax.vjp(
                            lambda p: fn(p, selft[si], src[ni], w, mask, ws), p)
                        return jax.tree.map(jnp.add, acc, vjp(gc[i])[0])
                    dp, dself, dsrc = acc
                    _, vjp = jax.vjp(
                        lambda p, a, b: fn(p, a, b, w, mask, ws), p,
                        selft[si], src[ni])
                    gp, ga, gb = vjp(gc[i])
                    return (jax.tree.map(jnp.add, dp, gp),
                            dself.at[si].add(ga), dsrc.at[ni].add(gb))

                if not need_dh:
                    grads[l] = jax.lax.fori_loop(
                        0, gc.shape[0], body, jax.tree.map(jnp.zeros_like, p))
                    continue
                dp, dself, dsrc = jax.lax.fori_loop(
                    0, gc.shape[0], body,
                    (jax.tree.map(jnp.zeros_like, p), jnp.zeros_like(selft),
                     jnp.zeros_like(src)))
                if m.SELF_FROM_SOURCE:
                    dsrc = dsrc + dself
                    dself = jnp.zeros_like(table)
                gp, gt = src_vjp(dsrc)
                grads[l] = jax.tree.map(jnp.add, dp, gp)
                g = gt + dself
            return grads

        tables = forward(params)
        loss, g = jax.value_and_grad(loss_of)(tables[-1])
        grads = backward(params, tables, g)
        new_params, new_state, clipped = update(grads, opt_state, params)
        return loss, clipped, new_params, new_state

    return init, jax.jit(run)


# ---------------------------------------------------------------------------
# mini-batch
# ---------------------------------------------------------------------------

def sampled_tensors(g, nodes, fanouts):
    """The float32 tensors of one sampled batch, recomputed from its node
    ids and the graph: a slot is real where its id is a neighbour of its
    parent, and the sampler fills the first ``min(deg, beta)`` slots of
    each parent.  Weights ``1/sqrt((s + 1)(deg_v + 1))`` with ``s`` the
    parent's real slots, self weights ``1/(deg + 1)``.

    -> (tensors, faults): ``faults`` counts what breaks the sampler's
    contract: targets outside the training split or drawn twice, slots
    that are not edges, neighbours drawn twice, and parents whose real
    slots are not ``min(deg, beta)``."""
    indptr, indices = g["indptr"], g["indices"]
    deg = np.diff(indptr)
    targets = nodes[0]
    faults = int((~g["train_mask"][targets]).sum()
                 + (targets.size - np.unique(targets).size))
    masks, weights, self_w = [], [], [1.0 / (deg[targets] + 1.0)]
    for d, beta in enumerate(fanouts):
        parent, child = nodes[d], nodes[d + 1]
        want = np.minimum(deg[parent], beta)
        real = np.arange(beta) < want[..., None]
        p = np.broadcast_to(parent[..., None], child.shape)
        lo, hi = indptr[p], indptr[p + 1]
        at = _find(indices, lo, hi, child)
        is_edge = (at < hi) & (indices[np.minimum(at, indices.size - 1)]
                               == child)
        faults += int((real & ~is_edge).sum())
        srt = np.sort(np.where(real, child, -1 - np.arange(beta)), axis=-1)
        faults += int((srt[..., 1:] == srt[..., :-1]).sum())
        s = real.sum(-1, keepdims=True).astype(np.float64)
        w = np.where(real, 1.0 / np.sqrt((s + 1.0) * (deg[child] + 1.0)), 0.0)
        masks.append(real.astype(np.float32))
        weights.append(w.astype(np.float32))
        self_w.append(1.0 / (deg[child] + 1.0))
    tensors = dict(
        feats=[g["feats"][ids.reshape(-1)].reshape(ids.shape + (-1,))
               for ids in nodes],
        masks=masks, weights=weights,
        self_w=[s.astype(np.float32) for s in self_w],
        labels=g["labels"][targets].astype(np.int32))
    return tensors, faults


def _find(indices, lo, hi, child):
    """Position of ``child`` in each sorted CSR row ``[lo, hi)`` (``hi``
    where absent), by a vectorised binary search over the rows."""
    lo, hi = lo.astype(np.int64).copy(), hi.astype(np.int64)
    end = hi.copy()
    while True:
        live = lo < hi
        if not live.any():
            break
        mid = (lo + hi) // 2
        go = live & (indices[np.minimum(mid, indices.size - 1)] < child)
        lo = np.where(go, mid + 1, lo)
        hi = np.where(live & ~go, mid, hi)
    return np.where(lo < end, lo, end)


def sampled_step(gnn, plan, lowp=None, half_batch=False, model=None):
    """Jitted ``(params, opt_state, tensors) -> (loss, grads as the
    optimizer gets them, params, opt_state)`` on one sampled batch: each
    layer aggregates hop ``d + 1`` into hop ``d``.  ``model`` as in
    ``init_params``."""
    m = model or models.load(gnn["model"])
    init, update = adam(plan)

    def loss_fn(params, t):
        hs = [_round(f, lowp) for f in t["feats"]]
        for l, p in enumerate(params):
            last = l == len(params) - 1
            hs = [_layer(m, p, last, hs[d], _round(hs[d + 1], lowp),
                         t["weights"][d], t["masks"][d], t["self_w"][d],
                         False, lowp)
                  for d in range(len(hs) - 1)]
        valid = jnp.ones(t["labels"].shape, F32)
        if half_batch:
            valid = valid * (jnp.arange(valid.size) % 2 == 0)
        return ce_loss(hs[0], t["labels"], valid)

    def run(params, opt_state, t):
        loss, grads = jax.value_and_grad(loss_fn)(params, t)
        new_params, new_state, clipped = update(grads, opt_state, params)
        return loss, clipped, new_params, new_state

    return init, jax.jit(run)


# ---------------------------------------------------------------------------
# the three steps a run compares
# ---------------------------------------------------------------------------

def three_steps(init, step, params, batches, precision="highest"):
    """Losses of three reference steps from ``params``, the first step's
    gradient as the optimizer gets it, and the params after the three.
    ``batches`` holds each step's arguments after the optimizer state;
    matrix products run at ``precision``."""
    state = init(params)
    losses, g0 = [], None
    with jax.default_matmul_precision(precision):
        for args in batches:
            loss, grads, params, state = step(params, state, *args)
            losses.append(float(loss))
            if g0 is None:
                g0 = grads
    return losses, g0, params
