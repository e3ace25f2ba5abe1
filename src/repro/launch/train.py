"""Production-style training driver.

LM archs:  synthetic token pipeline -> jit'd train_step (AdamW, remat,
sharded when a mesh is requested) -> checkpoints + metrics.
GNN arch:  runs the paper's two paradigms on a synthetic preset.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 100
    PYTHONPATH=src python -m repro.launch.train --arch gnn-papers100m \
        --smoke --steps 200
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro.checkpoint import save_checkpoint
from repro.configs.base import get_config
from repro.data import make_preset, token_batches
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def train_lm(args) -> dict:
    from repro.models import model as M
    from repro.models import steps as S

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(model_par=args.model_par)
    key = jax.random.key(args.seed)

    with sh.activate(mesh):
        params = M.init_model(key, cfg)
        specs = M.param_specs(cfg, params)
        params = jax.device_put(params, sh.tree_named(specs, mesh))
        opt, train_step = S.make_train_step(cfg)
        opt_state = opt.init(params)
        step_fn = jax.jit(train_step, donate_argnums=(0, 1))

        losses = []
        t0 = time.perf_counter()
        gen = token_batches(cfg.vocab_size, args.batch, args.seq,
                            seed=args.seed)
        for it in range(args.steps):
            hb = next(gen)
            batch = {"tokens": jnp.asarray(hb["tokens"]),
                     "labels": jnp.asarray(hb["labels"])}
            if cfg.frontend_seq:
                batch["patches"] = jnp.zeros(
                    (args.batch, cfg.frontend_seq, cfg.d_model),
                    M._dt(cfg))
            if cfg.n_enc_layers:
                batch["frames"] = jnp.zeros(
                    (args.batch, cfg.enc_seq, cfg.d_model), M._dt(cfg))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if it % args.log_every == 0:
                tok_s = (args.batch * args.seq * (it + 1)
                         / (time.perf_counter() - t0))
                print(f"step {it:5d} loss {loss:8.4f} "
                      f"acc {float(metrics['acc']):.3f} tok/s {tok_s:,.0f}",
                      flush=True)
            if args.ckpt_every and it and it % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, it, params,
                                {"arch": args.arch, "loss": loss},
                                keep_last=args.keep_last or None)
    result = {"arch": args.arch, "first_loss": losses[0],
              "final_loss": losses[-1], "steps": len(losses)}
    print(json.dumps(result))
    return result


def train_gnn(args) -> dict:
    """Both paradigms through the unified engine; a --sweep-bs /
    --sweep-fanout grid runs through the experiment runner instead."""
    from repro.core.engine import (FullGraphSource, SampledSource,
                                   Trainer, TrainPlan)
    from repro.core.experiment import save_rows, sweep

    cfg = get_config(args.arch, smoke=args.smoke)
    graph = make_preset(args.preset, seed=args.seed)
    cfg_run = cfg.__class__(**{**cfg.__dict__,
                               "n_classes": graph.n_classes,
                               "feat_dim": graph.feats.shape[1]})
    plan = TrainPlan(lr=args.lr, n_iters=args.steps, seed=args.seed,
                     eval_every=args.log_every,
                     ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                     ckpt_keep_last=args.keep_last)
    if args.sweep_bs or args.sweep_fanout:
        # each --sweep-fanout value is ONE grid point, broadcast to all
        # hops by sweep() (so `--sweep-fanout 5 10 15` sweeps β)
        rows = sweep(graph, cfg_run, plan,
                     batch_sizes=args.sweep_bs or [cfg_run.batch_size],
                     fanout_grid=[int(f) for f in args.sweep_fanout]
                     if args.sweep_fanout else [cfg_run.fanout],
                     include_fullgraph=True, verbose=True,
                     journal=args.journal)
        paths = save_rows(f"{args.arch}_sweep", rows)
        result = {"arch": args.arch, "sweep_rows": len(rows), **paths}
        print(json.dumps(result, indent=2))
        return result
    # the two paradigm Trainers share plan.ckpt_dir: namespace their
    # checkpoints (and any --resume) per paradigm so the manifests don't
    # clobber each other
    def _plan_for(tag):
        return (plan if not (plan.ckpt_every or args.resume) else
                plan.__class__(**{**plan.__dict__,
                                  "ckpt_dir": os.path.join(plan.ckpt_dir,
                                                           tag)}))

    pf, pm = _plan_for("fullgraph"), _plan_for("minibatch")
    rf = Trainer(graph, cfg_run, pf, source=FullGraphSource()).run(
        resume_from=pf.ckpt_dir if args.resume else None)
    rm = Trainer(graph, cfg_run, pm, source=SampledSource()).run(
        resume_from=pm.ckpt_dir if args.resume else None)
    result = {
        "arch": args.arch, "preset": args.preset,
        "full_graph": {"final_loss": rf.history.losses[-1],
                       "test_acc": rf.final_test_acc},
        "mini_batch": {"final_loss": rm.history.losses[-1],
                       "test_acc": rm.final_test_acc},
    }
    print(json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--preset", default="arxiv-like")
    ap.add_argument("--sweep-bs", type=int, nargs="*", default=None,
                    help="GNN only: batch sizes for a (b, β) sweep")
    ap.add_argument("--sweep-fanout", type=int, nargs="*", default=None,
                    help="GNN only: fan-out grid values; each value is "
                         "one grid point, broadcast to every hop")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="experiments/ckpt")
    ap.add_argument("--keep-last", type=int, default=0,
                    help="checkpoint retention: keep only the newest K "
                         "steps (0 = keep all)")
    ap.add_argument("--resume", action="store_true",
                    help="GNN only: resume each paradigm from the "
                         "latest checkpoint under its --ckpt-dir "
                         "namespace (exact resume — continues the "
                         "interrupted run bit-for-bit)")
    ap.add_argument("--journal", default=None,
                    help="GNN sweeps: JSONL completion journal for "
                         "crash-safe resume (see core.experiment.sweep)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "gnn":
        train_gnn(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
