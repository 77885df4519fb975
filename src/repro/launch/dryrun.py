import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo and
extract roofline terms.  MUST be run as a module entry point
(``python -m repro.launch.dryrun``) so the XLA_FLAGS above land before jax
initializes devices.

Usage:
  python -m repro.launch.dryrun --arch minitron-4b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --multi-pod
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_shape, list_archs, SHAPES, shape_applicable
from repro.launch import mesh as mesh_lib
from repro.models.transformer import model as M
from repro.models.transformer.sharding import axes_to_pspec, tree_shardings
from repro.train import lm_trainer
from repro.train.optimizer import AdamConfig
from repro.utils import hlo_cost


def _shardings(cfg, shape, mesh, specs):
    """NamedSharding trees matching input_specs(cfg, shape)."""
    p_axes = M.param_axes(cfg)
    b_axes = lm_trainer.batch_axes(cfg)
    if shape.kind == "train":
        return {
            "params": tree_shardings(p_axes, specs["params"], mesh),
            "opt_state": tree_shardings(
                lm_trainer.opt_state_axes(p_axes), specs["opt_state"], mesh),
            "batch": tree_shardings(b_axes, specs["batch"], mesh),
        }
    if shape.kind == "prefill":
        return {
            "params": tree_shardings(p_axes, specs["params"], mesh),
            "batch": tree_shardings(b_axes, specs["batch"], mesh),
        }
    c_axes = M.cache_axes(cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P
    return {
        "params": tree_shardings(p_axes, specs["params"], mesh),
        "caches": tree_shardings(c_axes, specs["caches"], mesh),
        "token": NamedSharding(mesh, axes_to_pspec(
            ("batch", None), specs["token"].shape, mesh)),
        "pos": NamedSharding(mesh, P()),
    }


def lower_one(cfg, shape, mesh):
    """Lower + compile one combo; returns (lowered, compiled, seconds)."""
    specs = lm_trainer.input_specs(cfg, shape)
    sh = _shardings(cfg, shape, mesh, specs)
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            step = lm_trainer.make_train_step(cfg, AdamConfig(lr=1e-4))
            jitted = jax.jit(
                step,
                in_shardings=(sh["params"], sh["opt_state"], sh["batch"]),
                out_shardings=(sh["params"], sh["opt_state"], None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(specs["params"], specs["opt_state"],
                                   specs["batch"])
        elif shape.kind == "prefill":
            step = lm_trainer.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(sh["params"], sh["batch"]))
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:
            step = lm_trainer.make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(sh["params"], sh["caches"], sh["token"], sh["pos"]),
                out_shardings=(None, None, sh["caches"]),
                donate_argnums=(1,))
            lowered = jitted.lower(specs["params"], specs["caches"],
                                   specs["token"], specs["pos"])
        compiled = lowered.compile()
    return lowered, compiled, time.time() - t0


def roofline(cfg, shape, mesh, lowered, compiled) -> dict:
    n_dev = mesh.size
    # loop-aware analysis (XLA-CPU cost_analysis counts while bodies once —
    # see utils/hlo_cost.py); raw cost_analysis kept for cross-reference.
    hlo = hlo_cost.analyze(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    flops = hlo["flops"]
    bytes_accessed = hlo["bytes_accessed"]
    coll = hlo["collectives"]
    cbytes = hlo["collective_bytes"]
    # cost_analysis is per-device program; flops there are per-device.
    t_compute = flops / mesh_lib.PEAK_FLOPS_BF16
    t_memory = bytes_accessed / mesh_lib.HBM_BW
    t_collective = cbytes / mesh_lib.ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    # model flops: 6*N*D for train (fwd+bwd), 2*N*D for inference fwd
    n_active = cfg.active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    model_flops = mult * n_active * tokens
    mem = compiled.memory_analysis()
    return {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "devices": n_dev,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_accessed,
        "collective_bytes_per_dev": cbytes,
        "collectives": coll,
        "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
        "compute_s": t_compute, "memory_s": t_memory,
        "collective_s": t_collective, "dominant": dominant,
        "model_flops_global": model_flops,
        "useful_flops_frac": model_flops / max(flops * n_dev, 1.0),
        "bytes_per_device": {
            "argument": getattr(mem, "argument_size_in_bytes", 0),
            "output": getattr(mem, "output_size_in_bytes", 0),
            "temp": getattr(mem, "temp_size_in_bytes", 0),
            "peak": (getattr(mem, "argument_size_in_bytes", 0)
                     + getattr(mem, "temp_size_in_bytes", 0)),
        },
    }


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose=True) -> dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    lowered, compiled, secs = lower_one(cfg, shape, mesh)
    r = roofline(cfg, shape, mesh, lowered, compiled)
    r["compile_s"] = secs
    if verbose:
        mem = compiled.memory_analysis()
        print(f"== {arch} x {shape_name} mesh={r['mesh']} "
              f"(compile {secs:.1f}s)")
        print(f"   memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB per device")
        print(f"   cost_analysis: flops/dev={r['hlo_flops_per_dev']:.3e} "
              f"bytes/dev={r['hlo_bytes_per_dev']:.3e} "
              f"coll_bytes/dev={r['collective_bytes_per_dev']:.3e}")
        print(f"   roofline: compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"-> {r['dominant']} bound; useful_flops={r['useful_flops_frac']:.2f}")
    return r


def main():
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for a in archs:
        for s in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(a, s, mp))
                except Exception as e:
                    traceback.print_exc()
                    results.append({"arch": a, "shape": s, "multi_pod": mp,
                                    "error": f"{type(e).__name__}: {e}"})
    if args.out:
        import pathlib
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.out}")
    errs = [r for r in results if "error" in r]
    print(f"\n{len(results)} combos, {len(errs)} errors, "
          f"{sum(1 for r in results if r.get('skipped'))} skipped")
    if errs:
        for r in errs:
            print("ERROR:", r["arch"], r["shape"], r["error"][:200])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
