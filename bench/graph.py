"""The benchmark's graphs: a vectorised stochastic block model built from
a configuration's data parameters, kept in an on-disk cache.

The model is the one of ``repro.data.synth.make_sbm_graph`` (power-law or
Poisson degree budgets, each node picking half its budget of targets,
a share ``homophily`` of them inside its own class, symmetrised and
deduplicated; class-conditioned Gaussian features), written without a
per-node loop so that a 2^21-node graph builds in seconds.  The splits
take the dataset's published train/val/test counts as shares of ``n``.

The graph is the dataset of a configuration: it depends on the data
parameters only (their ``seed`` included), never on a run's ``--seed``.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

#: bump when the generator's output changes, so old caches are not read
GENERATOR_VERSION = 1
FIELDS = ("indptr", "indices", "feats", "labels", "train_mask", "val_mask",
          "test_mask")


def make_sbm(n: int, n_classes: int, avg_degree: float, homophily: float,
             feat_dim: int, power_law: bool, seed: int, split: dict) -> dict:
    """Host arrays of one SBM graph (CSR rows sorted by neighbour id).

    ``split`` holds the dataset's published ``train``, ``val`` and
    ``test`` node counts out of ``of`` nodes; each becomes the same share
    of ``n``."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    if power_law:
        budget = np.minimum((avg_degree / 2.0) * (rng.pareto(2.0, n) + 1.0),
                            n / 4).astype(np.int64)
    else:
        budget = rng.poisson(avg_degree, n).astype(np.int64)
    picks = np.maximum(np.maximum(budget, 1) // 2, 1)

    src = np.repeat(np.arange(n, dtype=np.int64), picks)
    same = rng.random(src.size, dtype=np.float32) < homophily
    by_class = np.argsort(labels, kind="stable")
    counts = np.bincount(labels, minlength=n_classes)
    starts = np.cumsum(counts) - counts
    c = labels[src[same]]
    dst = np.empty_like(src)
    dst[same] = by_class[starts[c] + (rng.random(c.size) * counts[c])
                         .astype(np.int64)]
    dst[~same] = rng.integers(0, n, size=int((~same).sum()))
    keep = dst != src
    src, dst = src[keep], dst[keep]
    eid = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    del src, dst, same, keep
    rows = eid // n
    indices = (eid - rows * n).astype(np.int32)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    del eid, rows

    mus = rng.normal(0.0, 1.0, (n_classes, feat_dim)).astype(np.float32)
    feats = rng.standard_normal((n, feat_dim), dtype=np.float32)
    step = 1 << 16
    for s in range(0, n, step):
        feats[s:s + step] += mus[labels[s:s + step]]

    perm = rng.permutation(n)
    masks = {}
    lo = 0
    for name in ("train", "val", "test"):
        cnt = int(round(split[name] / split["of"] * n))
        m = np.zeros(n, bool)
        m[perm[lo:lo + cnt]] = True
        masks[name + "_mask"] = m
        lo += cnt
    return dict(indptr=indptr, indices=indices, feats=feats, labels=labels,
                **masks)


def cache_key(data: dict) -> str:
    blob = json.dumps({"v": GENERATOR_VERSION, **data}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def load(name: str, data: dict, cache_root: str):
    """The arrays of configuration ``name``'s graph, and the seconds spent
    building it (None where the cache held it).  A build is written to a
    temporary directory first and renamed into place, so an interrupted
    build leaves no half cache behind."""
    path = os.path.join(cache_root, f"{name}-{cache_key(data)}")
    if os.path.isdir(path):
        return {f: np.load(os.path.join(path, f + ".npy")) for f in FIELDS}, \
            None
    t0 = time.perf_counter()
    arrays = make_sbm(**data)
    build_s = time.perf_counter() - t0
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for f in FIELDS:
        np.save(os.path.join(tmp, f + ".npy"), arrays[f])
    os.rename(tmp, path)
    return arrays, build_s


def derived(name: str, data: dict, cache_root: str, tag: str, fields,
            build):
    """Arrays computed from configuration ``name``'s graph by ``build()``
    (a tuple, one array per name in ``fields``), kept beside the graph's
    cache under ``tag``."""
    path = os.path.join(cache_root, f"{name}-{cache_key(data)}", tag)
    if os.path.isdir(path):
        return tuple(np.load(os.path.join(path, f + ".npy")) for f in fields)
    arrays = build()
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for f, a in zip(fields, arrays):
        np.save(os.path.join(tmp, f + ".npy"), a)
    os.rename(tmp, path)
    return arrays
