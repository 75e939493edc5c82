"""Readings that a cell's correctness limits are set from.

    python3 -m bench.calibrate --workload <cell> --seeds 1 2 ... [--control 3]

For each seed, in one process: the program's set-up through its first
three iterations (the same call and feed as a run, without the timed
window), then the float32 reference. On the first ``--control`` seeds it
also reads the control (the reference with every matmul operand rounded
to float8, the precision below the configuration's bfloat16) and the
half-batch fault (the reference over half of the active rows). Each
reading is the gap to the float32 reference, by `check.gaps`. One JSON
line per seed and variant goes to standard output. Needs a TPU, as a run
does. The limits set from these readings go into
`bench/limits/<cell>.json`; the readings themselves are kept in PERF.md.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np


def readings(cell, seeds, n_control: int, out=sys.stdout):
    """Yield one dict per (seed, variant)."""
    import jax

    from bench import check, train_cell

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        plan = train_cell.make_plan(cell.config, cell.traffic, seed)
        run = train_cell.Run()
        state, _, _ = train_cell.setup(plan, run)
        jax.block_until_ready(state)
        del state
        gc.collect()
        t1 = time.perf_counter()
        ref = train_cell.reference_readings(plan)
        t2 = time.perf_counter()
        rows = [("program", run.readings)]
        if i < n_control:
            rows += [(v, train_cell.reference_readings(plan, v))
                     for v in ("fp8", "half")]
        for variant, got in rows:
            rec = {"cell": cell.name, "seed": seed, "variant": variant,
                   **check.gaps(got, ref),
                   "losses": [float(x) for x in got.losses],
                   "ref_losses": [float(x) for x in ref.losses],
                   "setup_s": t1 - t0, "reference_s": t2 - t1}
            keep = check.compared_leaves(ref)
            for what in ("grad_norms", "change_norms"):
                per = check.leaf_gaps(getattr(got, what), getattr(ref, what),
                                      keep)
                rec[f"worst_{what}"] = sorted(per.items(),
                                              key=lambda kv: -kv[1])[:3]
                rec[f"median_{what}"] = float(np.median(list(per.values())))
            if variant == "program":
                rec["y_first"] = [float(y) for y in run.first_y]
                rec["y_expected"] = [float(y) for y in run.expected_first_y]
            print(json.dumps(rec), file=out, flush=True)
            yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                         "control and the half-batch fault")
    args = ap.parse_args(argv)
    from bench import run as bench_run

    sys.path.insert(0, bench_run.SRC)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench_run.CACHE_DIR
    import jax

    from bench.spec import load_cell
    from repro.launch.jitcache import enable_persistent_cache

    enable_persistent_cache()
    bench_run.configure_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("bench.calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    for _ in readings(cell, args.seeds, args.control):
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
