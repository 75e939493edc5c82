"""Training launcher.

Two modes:
* --local  : run a real (reduced-config) elastic training job on the current
  devices with the simulated spot market — the full paper pipeline
  (strategy → bids → preemptions → masked SGD → cost accounting).
* default  : build the production-mesh job and print the lowered/compiled
  step (delegates to dryrun for the compile; actual pod execution uses the
  same code path on real hardware).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --local \
      --strategy optimal-two-bids --iterations 50
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.base import InputShape, JobConfig
from repro.core import convergence as conv
from repro.core import strategies as strat
from repro.core.cost_model import RuntimeModel, TruncGaussianPrice, UniformPrice
from repro.sim.cluster import VolatileCluster
from repro.sim.spot_market import IIDPrices, SpotMarket, TracePrices, \
    synthetic_history


def default_problem() -> conv.SGDProblem:
    """A conservative constant set for LM fine-tuning-scale jobs; examples
    calibrate these from the quadratic oracle or short probe runs."""
    return conv.SGDProblem(alpha=0.05, c=1.0, mu=1.0, L=4.0, M=8.0, G0=10.0)


def build_strategy(name, prob, eps, theta, n, dist, rt):
    if name == "no-interruptions":
        return strat.no_interruptions(prob, eps, n, dist, rt)
    if name == "optimal-one-bid":
        return strat.optimal_one_bid(prob, eps, theta, n, dist, rt)
    if name == "optimal-two-bids":
        return strat.optimal_two_bids(prob, eps, theta, n, dist, rt)
    if name == "dynamic-bids":
        return strat.DynamicBids(prob, eps, theta, dist, rt,
                                 stage1=(n // 4, n // 2), stage2=(n // 2, n),
                                 switch_at=max(1, int(0.4 * strat.optimal_two_bids(
                                     prob, eps, theta, n // 2, dist, rt
                                 ).total_iterations)))
    raise ValueError(name)


def supervise(args) -> int:
    """--supervise: pin the workload as a WorkerSpec in the run dir and
    hand it to the self-healing supervisor (launch/supervisor.py)."""
    import os

    from repro.launch import supervisor as sup_mod
    from repro.launch.workload import WorkerSpec

    # one two-bid fleet per strategy flavor: high/low split bids around
    # the uniform price band, matching the paper's two-bid policies
    n = args.workers
    bids = tuple(tuple([hi] * (n // 2) + [lo] * (n - n // 2))
                 for hi, lo in ((0.9, 0.5), (0.8, 0.6), (1.0, 0.4)))
    spec = WorkerSpec(arch=args.arch, n_workers=n, seq_len=args.seq,
                      global_batch=args.batch, bids=bids,
                      iterations=args.iterations or 12,
                      seeds=args.seeds, n_ticks=args.n_ticks,
                      save_every=args.save_every,
                      keep_last=args.keep_last,
                      mesh=args.mesh or 0, seed=args.seed,
                      reduce_depth=args.reduce_depth,
                      param_dtype=args.param_dtype,
                      zoo=args.zoo)
    os.makedirs(args.run_dir, exist_ok=True)
    spec.save(os.path.join(args.run_dir, sup_mod.SPEC_NAME))
    if args.fault_plan:
        from repro.chaos import FaultPlan
        FaultPlan.load(args.fault_plan).save(
            os.path.join(args.run_dir, sup_mod.PLAN_NAME))

    sup = sup_mod.Supervisor(args.run_dir, sup_mod.SupervisorConfig(
        max_restarts=args.max_restarts, hang_timeout=args.hang_timeout,
        devices=args.devices, seed=args.seed))
    summary = sup.run()
    print(json.dumps(summary, indent=1))
    return 0 if summary["ok"] else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2-7b")
    ap.add_argument("--config", default=None, metavar="NAME",
                    help="alias for --arch accepting underscore spelling "
                         "(qwen2_7b == qwen2-7b)")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    ap.add_argument("--reduce-depth", type=int, default=None, metavar="N",
                    help="run the FULL arch config (real widths/vocab) at "
                         "N layers instead of the reduced smoke variant "
                         "(--supervise workload spec)")
    ap.add_argument("--param-dtype", default=None,
                    help="override the model param/activation dtype (e.g. "
                         "bfloat16 — implies the zoo mixed-precision "
                         "program)")
    ap.add_argument("--zoo", action="store_true",
                    help="train through the zoo↔engine adapter "
                         "(trainer.train_zoo: mixed-precision carries, "
                         "bf16 checkpoints) in the supervised worker")
    ap.add_argument("--local", action="store_true",
                    help="reduced config + simulated market on this host")
    ap.add_argument("--strategy", default="optimal-two-bids",
                    choices=["no-interruptions", "optimal-one-bid",
                             "optimal-two-bids", "dynamic-bids"])
    ap.add_argument("--price", default="uniform",
                    choices=["uniform", "gaussian", "trace"])
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--theta", type=float, default=400.0)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--iterations", type=int, default=None)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batched", action="store_true",
                    help="scan-native engine: strategy × --seeds replicas "
                         "trained in one compiled call (implies --local)")
    ap.add_argument("--seeds", type=int, default=4,
                    help="number of market seeds for --batched")
    ap.add_argument("--megabatch", action="store_true",
                    help="fold the replica axis into blocked params + a "
                         "widened batch dim instead of outer vmap "
                         "(requires --batched; dense fp32 SGD models only)")
    ap.add_argument("--fused-update", action="store_true",
                    help="apply the elastic SGD update with the fused "
                         "Pallas kernel (requires --megabatch)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="shard the batched grid's scenario axis over N "
                         "devices via simulate_sharded (requires "
                         "--batched; bit-exact with the unsharded run; "
                         "on CPU, force virtual devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--mesh-replica", type=int, default=None, metavar="M",
                    help="additionally shard the seed/replica axis over M "
                         "devices (2-D N x M scenario x replica mesh; "
                         "requires --mesh)")
    ap.add_argument("--supervise", action="store_true",
                    help="run durable batched training under the "
                         "self-healing supervisor (subprocess worker, "
                         "heartbeat watchdog, restart-on-crash; requires "
                         "--run-dir)")
    ap.add_argument("--run-dir", default=None,
                    help="supervisor run directory (spec, checkpoints, "
                         "heartbeat, recovery log)")
    ap.add_argument("--save-every", type=int, default=8,
                    help="durable checkpoint cadence in ticks (--supervise)")
    ap.add_argument("--n-ticks", type=int, default=64,
                    help="market-tick budget of the durable run "
                         "(--supervise)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint steps retained by GC (--supervise)")
    ap.add_argument("--fault-plan", default=None,
                    help="chaos FaultPlan JSON to inject (--supervise)")
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--hang-timeout", type=float, default=120.0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices in the supervised worker")
    args = ap.parse_args()
    if args.config:
        # accept the underscore spelling of registry names
        arch = args.config.replace("_", "-")
        if arch not in ARCHS:
            ap.error(f"--config {args.config!r} does not name a config "
                     f"(known: {', '.join(sorted(ARCHS))})")
        args.arch = arch
    if args.param_dtype and args.param_dtype not in ("float32", "fp32",
                                                     "f32"):
        args.zoo = True           # mixed precision needs the zoo carry
    from repro.launch.jitcache import enable_persistent_cache
    enable_persistent_cache()
    if args.supervise:
        if args.run_dir is None:
            ap.error("--supervise requires --run-dir")
        return supervise(args)
    if args.fused_update and not args.megabatch:
        ap.error("--fused-update requires --megabatch")
    if args.megabatch and not args.batched:
        ap.error("--megabatch requires --batched")
    if args.mesh_replica and args.mesh is None:
        ap.error("--mesh-replica requires --mesh")
    if args.mesh is not None and not args.batched:
        ap.error("--mesh requires --batched")
    if args.batched:
        args.local = True

    if not args.local:
        from repro.launch.dryrun import lower_one
        rec = lower_one(args.arch, args.shape)
        print(json.dumps({k: v for k, v in rec.items() if k != "collectives"},
                         default=str, indent=1))
        return

    if args.reduce_depth:
        cfg = get_config(args.arch).with_(num_layers=args.reduce_depth)
    else:
        cfg = get_config(args.arch).reduced()
    if args.param_dtype:
        cfg = cfg.with_(dtype=args.param_dtype,
                        param_dtype=args.param_dtype)
    shape = InputShape("local", seq_len=args.seq, global_batch=args.batch,
                       kind="train")
    job = JobConfig(model=cfg, shape=shape, n_workers=args.workers)

    if args.price == "uniform":
        dist = UniformPrice(0.2, 1.0)
        proc = IIDPrices(dist, seed=args.seed)
    elif args.price == "gaussian":
        dist = TruncGaussianPrice()
        proc = IIDPrices(dist, seed=args.seed)
    else:
        trace = synthetic_history(seed=args.seed)
        proc = TracePrices(trace, step=0.05)
        dist = proc.empirical_dist()
    rt = RuntimeModel(kind="exp", lam=2.0, delta=0.05)
    prob = default_problem()

    strategy = build_strategy(args.strategy, prob, args.eps, args.theta,
                              args.workers, dist, rt)
    cluster = VolatileCluster(n_workers=args.workers, runtime=rt,
                              market=SpotMarket(proc), seed=args.seed)

    from repro.train.trainer import ElasticTrainer
    trainer = ElasticTrainer(job=job, cluster=cluster, strategy=strategy,
                             seed=args.seed)
    if args.batched:
        mesh = None
        if args.mesh is not None:
            from repro.launch.mesh import (make_scenario_mesh,
                                           make_scenario_replica_mesh)
            mesh = (make_scenario_replica_mesh(args.mesh, args.mesh_replica)
                    if args.mesh_replica else make_scenario_mesh(args.mesh))
        res = trainer.run_batched(seeds=args.seeds,
                                  iterations=args.iterations,
                                  megabatch=args.megabatch,
                                  use_fused_update=args.fused_update,
                                  mesh=mesh)
        out = {name: res.run(name).summary for name in res.names}
        out["_engine"] = {"replicas": len(res.names) * res.n_seeds,
                          "megabatch": args.megabatch,
                          "fused_update": args.fused_update,
                          "mesh": None if mesh is None else
                          dict(zip(mesh.axis_names,
                                   (int(s) for s in mesh.devices.shape)))}
        print(json.dumps(out, indent=1, default=float))
        return
    summary = trainer.run(iterations=args.iterations)
    del summary["log"]
    print(json.dumps(summary, indent=1, default=float))


if __name__ == "__main__":
    import sys
    sys.exit(main())
