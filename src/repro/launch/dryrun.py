import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh with ShapeDtypeStruct stand-ins (no allocation), print
memory/cost analysis, and dump roofline inputs (FLOPs, bytes, per-collective
byte counts) as JSON.

The two os.environ lines above MUST run before any other import (jax locks
the device count on first init). Do not set this flag globally — smoke tests
and benches must see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out results/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, config_for_shape
from repro.configs.base import InputShape, JobConfig, ModelConfig
from repro.launch.mesh import data_parallel_workers, make_production_mesh
from repro.models import model_zoo
from repro.models.common import (
    DEFAULT_RULES,
    MULTI_POD_RULES,
    abstract_params,
    mesh_context,
    param_pspecs,
    resolve_spec,
)
from repro.roofline.analysis import analyze_compiled
from repro.train.train_step import make_serve_step, make_train_step


def _sharded_struct(shape, dtype, spec, mesh):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _batch_pspec(batch: int, mesh, rules) -> P:
    return resolve_spec((batch,), ("batch",), rules, mesh)


def input_specs(cfg: ModelConfig, shape: InputShape, mesh, rules,
                n_workers: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, zero
    allocation) for every model input of one step."""
    b, s = shape.global_batch, shape.seq_len
    bspec = _batch_pspec(b, mesh, rules)
    bdim = bspec[0] if len(bspec) else None

    def tok(shp):
        return _sharded_struct(shp, jnp.int32, P(bdim, None), mesh)

    def emb(shp):
        return _sharded_struct(shp, jnp.dtype(cfg.dtype),
                               P(bdim, None, None), mesh)

    if shape.is_decode:
        return {"tokens": tok((b, 1))}

    if cfg.family == "vlm":
        text = s - cfg.vision.num_patches
        return {"tokens": tok((b, text)), "labels": tok((b, text)),
                "patches": emb((b, cfg.vision.num_patches, cfg.d_model))}
    if cfg.family == "encdec":
        return {"tokens": tok((b, s)), "labels": tok((b, s)),
                "frames": emb((b, cfg.encoder.src_len, cfg.d_model))}
    return {"tokens": tok((b, s)), "labels": tok((b, s))}


def _abstract_with_sharding(defs, mesh, rules, fsdp: bool, dtype):
    avals = abstract_params(defs, dtype)
    pspecs = param_pspecs(defs, mesh, rules, fsdp=fsdp)
    return jax.tree.map(
        lambda a, p: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, p)),
        avals, pspecs)


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              fsdp: bool = True, remat: str = "full",
              rules: Optional[dict] = None, microbatch: int = 1,
              seq_parallel: bool = False,
              cfg_overrides: Optional[dict] = None,
              mesh=None) -> Dict:
    """Lower + compile one (arch × shape) on the production mesh. Returns the
    roofline-input record (also printed)."""
    shape = SHAPES[shape_name]
    overrides = dict(cfg_overrides or {})
    moe_par = overrides.pop("moe_parallelism", None)
    cfg = config_for_shape(arch, shape).with_(
        dtype="bfloat16", param_dtype="bfloat16", **overrides)
    if moe_par is not None and cfg.moe is not None:
        import dataclasses as _dc
        cfg = cfg.with_(moe=_dc.replace(cfg.moe, parallelism=moe_par))
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    rules = dict(rules if rules is not None else
                 (MULTI_POD_RULES if multi_pod else DEFAULT_RULES))
    if seq_parallel:
        # beyond-paper: shard the residual stream's sequence dim over the
        # model axis between blocks (Megatron-SP style) — the per-block
        # all-reduce becomes reduce-scatter + all-gather
        rules["residual"] = ("model",)
    n_workers = data_parallel_workers(mesh)
    job = JobConfig(model=cfg, shape=shape, n_workers=n_workers,
                    microbatch=microbatch)

    t0 = time.time()
    with mesh_context(mesh, rules):
        defs = model_zoo.param_defs(cfg)
        params = _abstract_with_sharding(defs, mesh, rules, fsdp,
                                         jnp.dtype(cfg.param_dtype))
        batch = input_specs(cfg, shape, mesh, rules, n_workers)

        if shape.is_decode:
            cdefs = model_zoo.cache_defs(cfg, shape.global_batch,
                                         shape.seq_len)
            caches = _abstract_with_sharding(cdefs, mesh, rules, False,
                                             jnp.dtype(cfg.dtype))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            step_fn = make_serve_step(cfg)
            lowered = jax.jit(step_fn, donate_argnums=(1,)).lower(
                params, caches, batch["tokens"], pos)
        elif shape.kind == "prefill":
            from repro.train.train_step import make_eval_step
            step_fn = make_eval_step(cfg)
            # prefill = forward pass over the full context (logits only)
            batch_fwd = dict(batch)
            lowered = jax.jit(step_fn).lower(params, batch_fwd)
        else:
            # training step: params+opt donated, optimizer state included
            from repro.optim.sgd import get_optimizer
            opt = get_optimizer(job.optimizer, job.momentum)
            opt_state = jax.eval_shape(opt.init, params)
            opt_state = jax.tree.map(
                lambda a, ref: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=ref.sharding)
                if a.shape == ref.shape else jax.ShapeDtypeStruct(
                    a.shape, a.dtype),
                opt_state, params)
            mask = jax.ShapeDtypeStruct((n_workers,), jnp.float32)
            stepc = jax.ShapeDtypeStruct((), jnp.int32)
            step_fn = make_train_step(cfg, job, remat=remat)
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params, opt_state, batch, mask, stepc)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    record = analyze_compiled(compiled, cfg, shape, mesh,
                              n_params_defs=defs)
    record.update({
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "fsdp": fsdp, "remat": remat,
        "microbatch": microbatch, "seq_parallel": seq_parallel,
        "overrides": cfg_overrides or {},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    })
    mem = compiled.memory_analysis()
    print(f"== {arch} × {shape_name} mesh={record['mesh']} ==")
    print(f"memory_analysis: {mem}")
    from repro.roofline.analysis import xla_cost_analysis
    ca = xla_cost_analysis(compiled)
    print("cost_analysis: flops={:.3e} bytes={:.3e}".format(
        ca.get("flops", -1.0), ca.get("bytes accessed", -1.0)))
    print(json.dumps({k: v for k, v in record.items()
                      if k != "collectives"}, indent=None, default=str))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true",
                    help="shard the residual stream's seq dim over the "
                         "model axis (Megatron-SP style)")
    ap.add_argument("--kv-cache-shard", default=None,
                    choices=["heads", "seq", "none"],
                    help="decode cache sharding (see EXPERIMENTS.md §Perf)")
    ap.add_argument("--out", default=None, help="JSON output path prefix")
    args = ap.parse_args()
    from repro.launch.jitcache import enable_persistent_cache
    enable_persistent_cache()

    combos = ([(a, s) for a in sorted(ARCHS) for s in
               ["train_4k", "prefill_32k", "decode_32k", "long_500k"]]
              if args.all else [(args.arch, args.shape)])
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    results, failures = [], []
    overrides = ({"kv_cache_shard": args.kv_cache_shard}
                 if args.kv_cache_shard else None)
    for arch, shape in combos:
        try:
            rec = lower_one(arch, shape, multi_pod=args.multi_pod,
                            fsdp=not args.no_fsdp, remat=args.remat,
                            microbatch=args.microbatch,
                            seq_parallel=args.seq_parallel,
                            cfg_overrides=overrides,
                            mesh=mesh)
            results.append(rec)
        except Exception as e:  # noqa: BLE001 — report all failures at end
            traceback.print_exc()
            failures.append({"arch": arch, "shape": shape, "error": str(e)})
        if args.out:
            with open(args.out + (".multipod" if args.multi_pod else "")
                      + ".json", "w") as f:
                json.dump({"results": results, "failures": failures}, f,
                          indent=1, default=str)
    print(f"\nDRY-RUN SUMMARY: {len(results)} ok, {len(failures)} failed")
    for f_ in failures:
        print("  FAIL", f_["arch"], f_["shape"], f_["error"][:200])
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
