"""Readings that the output check's limits are set from, for one cell:

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3 ...

In one process (the cell is set up once), for each seed: the program's
run through ``Trainer.run`` with a short window, then the float32
reference, and the numbers ``bench/check.py`` compares for

* ``program``: the timed path against the reference (the lower
  readings);
* ``control``: the reference computed in float8 (e4m3: aggregation
  tables and the operands of every matrix product), one precision below
  the configuration's bfloat16, in the program's place;
* ``half_batch``: the reference with every second loss row left out and
  the mean taken over the rest, in the program's place.

A step that returns its state unchanged reads ``change3`` = 1 by the
measure itself and needs no run.  One JSON line per seed and reading.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path[0] == HERE:
    sys.path[0] = os.path.dirname(HERE)

import jax.numpy as jnp  # noqa: E402

from bench import check  # noqa: E402
from bench.run import Cell  # noqa: E402

#: the control: the reference computed in float8, one precision below
#: the configuration's bfloat16
CONTROL = jnp.float8_e4m3fn


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    ap.add_argument("--stand-ins", type=int, default=3,
                    help="run the stand-ins on this many of the seeds")
    args = ap.parse_args(argv)
    cell = Cell(args.workload, root) if root else Cell(args.workload)
    for i, seed in enumerate(args.seeds):
        probe = (cell.train(seed, args.seconds, keep_graph=True)
                 if args.program else None)
        nodes = probe.sampled if probe is not None else None
        if nodes is None and cell.traffic["source"] != "FullGraphSource":
            nodes = sampled_nodes(cell, seed)
        ref = cell.reference(seed, nodes=nodes)
        rows = {}
        if probe is not None:
            rows["program"] = check.numbers(probe.readings, ref)
        if i < args.stand_ins:
            rows["control"] = check.numbers(cell.reference(
                seed, nodes, lowp=CONTROL, precision="default"), ref)
            rows["half_batch"] = check.numbers(cell.reference(
                seed, nodes, half_batch=True), ref)
        for kind, values in rows.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": kind, "values": values,
                              "losses": ref["losses"]}), flush=True)
    return 0


def sampled_nodes(cell, seed: int):
    """The node ids of the first three batches the program's sampler
    draws from ``seed`` (as ``SampledSource`` does), without training."""
    import numpy as np
    from repro.core.graph import Graph
    from repro.core.sampler import sample_batch
    from bench import graph as bgraph
    g = Graph(n=cell.n, **{f: cell.arrays[f] for f in bgraph.FIELDS})
    rng = np.random.default_rng(seed)
    args = cell.traffic["args"]
    return [sample_batch(rng, g, args["batch_size"], args["fanouts"]).nodes
            for _ in range(3)]


if __name__ == "__main__":
    sys.exit(main())
