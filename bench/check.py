"""The comparison that decides a run's ``correct``.

The timed path's first three training steps are held against the
float32 reference (``bench/reference.py``) from the same seed, on the
same graph and, for sampled cells, on the node ids the program's
sampler drew.  The numbers compared:

* ``loss0``, ``loss1``, ``loss2``: each step's loss, relative gap;
* ``grad0``: the first gradient as the optimizer got it (Adam's first
  moment after one step over ``1 - b1``), by the worst leaf;
* ``grad0_dist``: the same gradient's distance from the reference's,
  by the worst leaf: leaving out half of a large batch barely moves a
  loss or a gradient's norm, but turns the gradient;
* ``change3``: the params' change over the three steps, by the worst
  leaf, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``sampler_faults`` (sampled cells): targets outside the split or
  repeated, slots that are not edges, repeated neighbours, wrong slot
  counts, over the three batches; exact.

A leaf's gap is ``|norm(program) - norm(reference)|``, its distance
``norm(program - reference)``, each over the larger of the reference's
norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

from bench import reference as R


def _norms(tree):
    return [float(jnp.linalg.norm(jnp.ravel(x))) for x in jax.tree.leaves(tree)]


def leaf_gap(prog, ref, keep=None) -> float:
    p, r = _norms(prog), _norms(ref)
    med = statistics.median(r)
    gaps = [abs(a - b) / max(b, med) for a, b in zip(p, r)]
    if keep is not None:
        gaps = [x for x, k in zip(gaps, keep) if k]
    return max(gaps)


def leaf_dist(prog, ref) -> float:
    """Worst leaf of ``norm(program - reference)`` over the larger of the
    reference's norm of that leaf and of the median leaf."""
    r = _norms(ref)
    med = statistics.median(r)
    d = _norms(jax.tree.map(lambda a, b: jnp.asarray(a) - b, prog, ref))
    return max(x / max(y, med) for x, y in zip(d, r))


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (three), ``g0``, ``p0``
    and ``p3`` (param pytrees, numpy or jax); a sampled cell's ``ref``
    also holds the ``sampler_faults`` it counted in the batches."""
    out = {f"loss{i}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]))}
    out["grad0"] = leaf_gap(prog["g0"], ref["g0"])
    out["grad0_dist"] = leaf_dist(prog["g0"], ref["g0"])
    g = _norms(ref["g0"])
    med = statistics.median(g)
    keep = [x >= 1e-3 * med for x in g]
    sub = lambda a, b: jax.tree.map(lambda x, y: jnp.asarray(x) - y, a, b)  # noqa: E731
    out["change3"] = leaf_gap(sub(prog["p3"], prog["p0"]),
                              sub(ref["p3"], ref["p0"]), keep)
    if "sampler_faults" in ref:
        out["sampler_faults"] = ref["sampler_faults"]
    return out


def reference_run(conf: dict, traffic: dict, graph: dict, seed: int,
                  nodes=None, ell=None, lowp=None, half_batch=False,
                  precision="highest", model=None) -> dict:
    """Three reference steps from ``seed``: ``losses``, ``g0``, ``p0``,
    ``p3``, and for sampled cells ``sampler_faults``.  ``nodes`` holds
    the three batches' node ids per hop (sampled); ``ell`` the capped
    adjacency ``(idx, kept)`` (full graph); ``model`` the model's module
    (``Registry.model``), by default the one the configuration names."""
    gnn, plan = conf["gnn"], conf["plan"]
    p0 = R.init_params(gnn, seed, model)
    out = {"p0": p0}
    if traffic["source"] == "FullGraphSource":
        data = R.fullgraph_plan(graph, *ell, gnn["n_layers"])
        init, step = R.fullgraph_step(gnn, plan, lowp, half_batch, model)
        args = (jnp.asarray(graph["feats"]), jax.device_put(data))
        batches = [args] * 3
    elif traffic["source"] == "SampledSource":
        init, step = R.sampled_step(gnn, plan, lowp, half_batch, model)
        faults, batches = 0, []
        fanouts = traffic["args"]["fanouts"]
        for ids in nodes:
            t, f = R.sampled_tensors(graph, ids, fanouts)
            faults += f
            batches.append((jax.device_put(t),))
        out["sampler_faults"] = faults
    else:
        raise ValueError(f"no reference for source {traffic['source']!r}")
    out["losses"], out["g0"], out["p3"] = R.three_steps(init, step, p0,
                                                        batches, precision)
    return out


def judge(values: dict, limits: dict):
    """-> (correct, checks): the numbers the cell's limits name, each at
    or under its limit and shown beside it.  A cell without limits, a
    limit without its number, or a number that is not finite fails."""
    checks = {name: {"value": values.get(name), "limit": lim}
              for name, lim in limits.items()}
    ok = bool(checks) and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
