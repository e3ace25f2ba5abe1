import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the dry-run compiles against 512 VIRTUAL HOST devices by design; pin the
# cpu platform (unless the caller overrides) so a baked-in libtpu never
# hijacks backend discovery and hangs probing for real hardware
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input-shape x mesh) combination this lowers and
COMPILES the real step function against ShapeDtypeStruct inputs (no
allocation), prints memory_analysis() (proves fit) and cost_analysis()
(FLOPs/bytes), parses the partitioned HLO for collective bytes, and stores
one JSON record per combo under --out (resumable; existing records skip).

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k --multi-pod
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.sharding import activate as sharding_activate
from repro.configs.base import (INPUT_SHAPES, InputShape, get_config,
                                list_archs, shape_applicable)
from repro.launch import gnn_steps
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (active_param_count, collective_bytes,
                                   model_flops, roofline)

HBM_PER_CHIP = 16 * 1024 ** 3      # v5e

# gradient-accumulation depth for the train dry-runs: keeps per-device
# activation memory bounded at the assigned global batch (256).  Big
# models use more microbatches; the global batch and numerics are
# unchanged.
def microbatches_for(cfg, shape) -> int:
    if shape.kind != "train":
        return 1
    big = cfg.d_model * cfg.n_layers
    if big >= 3840 * 48:        # >= gemma3-12b scale
        return 8
    if big >= 2048 * 24:
        return 4
    return 2


def _mem_dict(ma) -> Dict[str, int]:
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    return {f: int(getattr(ma, f, 0)) for f in fields}


def _finish(lowered, t0, extra: Dict[str, Any]) -> Dict[str, Any]:
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = _mem_dict(ma)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    rec = {
        "per_device_flops": flops,
        "per_device_bytes": byt,
        "collective_bytes_per_device": coll,
        "memory": mem,
        "device_bytes_total": mem["argument_size_in_bytes"]
        + mem["temp_size_in_bytes"] + mem["output_size_in_bytes"],
        "fits_hbm": (mem["argument_size_in_bytes"]
                     + mem["temp_size_in_bytes"]
                     + mem["output_size_in_bytes"]) < HBM_PER_CHIP,
        # the CPU backend emulates bf16 math in f32, roughly doubling temp
        # buffers vs a TPU compile (verified on the llama4 breakdown: the
        # dominant temps are f32 copies of bf16 tensors).  Corrected
        # estimate keeps args (real f32 master weights) + temp/2.
        "device_bytes_tpu_estimate": mem["argument_size_in_bytes"]
        + mem["output_size_in_bytes"] + mem["temp_size_in_bytes"] // 2,
        "fits_hbm_tpu_estimate": (mem["argument_size_in_bytes"]
                                  + mem["output_size_in_bytes"]
                                  + mem["temp_size_in_bytes"] // 2)
        < HBM_PER_CHIP,
        "roofline": roofline(flops, byt, coll["total"]),
        "compile_seconds": time.time() - t0,
        "status": "ok",
    }
    rec.update(extra)
    return rec


def dryrun_lm(arch: str, shape: InputShape, multi_pod: bool
              ) -> Dict[str, Any]:
    from repro.models import steps as S
    from repro.models import model as M

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with sharding_activate(mesh):
        params, opt_state = S.abstract_state(
            cfg, mesh, with_opt=(shape.kind == "train"))
        batch = S.batch_specs(cfg, shape, mesh)
        counts = jax.tree.map(lambda x: x, params)  # noqa - keep tree
        if shape.kind == "train":
            mb = microbatches_for(cfg, shape)
            _, train_step = S.make_train_step(cfg, microbatches=mb)
            lowered = jax.jit(train_step).lower(params, opt_state, batch)
        elif shape.kind == "prefill":
            lowered = jax.jit(S.make_prefill_step(cfg)).lower(params, batch)
        else:
            cache = S.cache_shape_specs(cfg, shape, mesh)
            lowered = jax.jit(S.make_serve_step(cfg)).lower(
                params, cache, batch["token"])
        pc = active_param_count(cfg, params)
        mf = model_flops(cfg, params, shape)
        rec = _finish(lowered, t0, {
            "params_total": pc["total"], "params_active": pc["active"],
            "model_flops_global": mf,
        })
    chips = mesh.devices.size
    hlo_global_flops = rec["per_device_flops"] * chips
    rec["model_vs_hlo_flops"] = (rec["model_flops_global"]
                                 / hlo_global_flops
                                 if hlo_global_flops else 0.0)
    rec["chips"] = chips
    return rec


def dryrun_gnn(arch: str, gnn_shape: str, multi_pod: bool) -> Dict[str, Any]:
    import dataclasses
    cfg = get_config(arch)
    if getattr(cfg, "use_agg_kernel", False):
        # the dry-run's 512 devices are virtual CPU devices, where the
        # Pallas gather could only lower through the interpreter, so the
        # roofline numbers here come from the (collective-equivalent)
        # einsum path.  The kernel path itself is compiled for a
        # described v5e in tests/test_tpu_compile.py.
        cfg = dataclasses.replace(cfg, use_agg_kernel=False)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with sharding_activate(mesh):
        params = gnn_steps.gnn_abstract_params(cfg, mesh)
        opt_state = {"step": jax.ShapeDtypeStruct(
            (), jax.numpy.int32,
            sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))}
        if gnn_shape == "fullgraph_train":
            _, step = gnn_steps.make_fullgraph_step(cfg)
            args = gnn_steps.fullgraph_input_specs(cfg, mesh)
            lowered = jax.jit(step).lower(params, opt_state, *args)
            tokens = cfg.n_nodes
        else:
            _, step = gnn_steps.make_minibatch_step(cfg)
            feats, masks, weights, self_w, labels = \
                gnn_steps.minibatch_input_specs(cfg, mesh)
            lowered = jax.jit(step).lower(params, opt_state, feats, masks,
                                          weights, self_w, labels)
            tokens = cfg.batch_size
        rec = _finish(lowered, t0, {"gnn_nodes_per_step": tokens})
    rec["chips"] = mesh.devices.size
    return rec


GNN_SHAPES = ("fullgraph_train", "minibatch_train")


def combos(archs=None, shapes=None, meshes=("single", "multi")):
    archs = archs or list_archs()
    for arch in archs:
        cfg = get_config(arch)
        if cfg.family == "gnn":
            names = shapes or GNN_SHAPES
            for s in names:
                if s not in GNN_SHAPES:
                    continue
                for mp in meshes:
                    yield arch, s, mp == "multi", None
            continue
        names = shapes or list(INPUT_SHAPES)
        for s in names:
            if s not in INPUT_SHAPES:
                continue
            ok, why = shape_applicable(cfg, INPUT_SHAPES[s])
            for mp in meshes:
                yield arch, s, mp == "multi", (None if ok else why)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            skip_reason: Optional[str]) -> Dict[str, Any]:
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16"}
    if skip_reason:
        return {**meta, "status": "skipped", "reason": skip_reason}
    try:
        cfg = get_config(arch)
        if cfg.family == "gnn":
            rec = dryrun_gnn(arch, shape_name, multi_pod)
        else:
            rec = dryrun_lm(arch, INPUT_SHAPES[shape_name], multi_pod)
        rec.update(meta)
        return rec
    except Exception as e:
        # deliberately broad: the dry-run matrix records every
        # arch x shape outcome side by side, so ANY per-cell failure
        # becomes an "error" row instead of aborting the whole report
        return {**meta, "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append")
    ap.add_argument("--shape", action="append")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ("single", "multi")
    if args.multi_pod and not args.single_pod:
        meshes = ("multi",)
    elif args.single_pod and not args.multi_pod:
        meshes = ("single",)

    os.makedirs(args.out, exist_ok=True)
    todo = list(combos(args.arch, args.shape, meshes))
    print(f"dry-run: {len(todo)} combos -> {args.out}", flush=True)
    for arch, shape_name, mp, skip in todo:
        tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[skip-existing] {tag}", flush=True)
            continue
        t0 = time.time()
        rec = run_one(arch, shape_name, mp, skip)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} bound={r['bound_s']:.4f}s"
                     f" fits={rec['fits_hbm']}"
                     f" mem={rec['device_bytes_total']/2**30:.2f}GiB")
        elif status == "error":
            extra = " " + rec["error"][:120]
        print(f"[{status}] {tag} ({time.time()-t0:.0f}s){extra}", flush=True)


if __name__ == "__main__":
    main()
