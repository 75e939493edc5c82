"""Run one cell of ``BENCHMARK.json`` once on the chips of this machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON line as the last line of standard output: ``correct``,
``attempted`` (iterations the window ran), ``failed`` (those whose loss
was not finite), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit, which also end standard error. Exits 2,
printing no result, without a TPU, with fewer chips than the cell asks
for, on a device the peak table does not hold, or in a checkout without
the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run's profile is written, read, then removed
TRACE_DIR = os.path.join(BENCH_DIR, ".work", "trace")


def _refuse(msg: str) -> int:
    print(f"bench.run: {msg}", file=sys.stderr, flush=True)
    return 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str):
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def layer_value(metric: str, record, bench_dir: str = BENCH_DIR):
    from bench.spec import load_module

    return load_module("layers", metric, bench_dir).read(record)


def flops_per_row(config, bench_dir: str = BENCH_DIR) -> float:
    from bench.spec import load_module

    mod = load_module("flops", config["family"], bench_dir)
    return mod.flops_per_row(config["model"], config["layout"])


def configure_cache(jax) -> None:
    """Every program, small ones too, comes from the persistent cache after
    a first run; the cache is never evicted (eviction reads a per-entry
    access-time file, and one missing file stops every later write)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return _refuse(f"no program under {SRC}: run from a checkout")
    if args.seed < 0:
        return _refuse(f"--seed {args.seed} is negative")
    sys.path.insert(0, SRC)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

    from bench.spec import load_cell

    cell = load_cell(args.workload)

    import jax

    from bench.spans import CompileClock

    clock = CompileClock()
    from repro.launch.jitcache import enable_persistent_cache

    enable_persistent_cache()
    configure_cache(jax)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        return _refuse(f"JAX found no TPU (platform {platform!r}); the "
                       "benchmark measures the chip only")
    if len(devices) < cell.chips:
        return _refuse(f"{args.workload} needs {cell.chips} chips, JAX "
                       f"sees {len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        return _refuse(str(e))

    line = measure(cell, args.seed, args.seconds, bool(args.trace), clock,
                   peaks, {"platform": platform,
                           "kind": devices[0].device_kind,
                           "count": len(devices)})
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def measure(cell, seed: int, seconds: float, traced: bool, clock, peaks,
            device, t_start: float = None) -> dict:
    """One run of ``cell`` on whatever devices JAX has: the result line.
    Prints the numbers compared, each beside its limit, as the last lines
    of standard error."""
    from bench import check
    from bench import trace as trace_mod
    from bench import train_cell

    plan = train_cell.make_plan(cell.config, cell.traffic, seed)
    trace_dir = TRACE_DIR if traced else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    compile_s_setup = []
    run, reference = train_cell.run_cell(
        plan, seconds, lambda: clock.compiles,
        T_START if t_start is None else t_start, trace_dir=trace_dir,
        on_setup_done=lambda: compile_s_setup.append(clock.seconds))
    numbers = check.gaps(run.readings, reference)
    numbers["y_mismatch"] = check.y_mismatch(run)
    correct, table = check.decide(numbers, cell.limits)

    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    rows = run.shard_steps * plan.rows_per_worker
    record = {"window_s": run.window_s, "chips": cell.chips,
              "useful_flops": rows * flops_per_row(cell.config),
              "peak_flops": peaks["bf16_flops_per_s"],
              "compile_s": compile_s_setup[0], "trace": None}
    line = {"correct": correct and run.nonfinite == 0,
            "attempted": run.iterations, "failed": run.nonfinite}
    if traced:
        reduced = trace_mod.reduce(trace_mod.load(
            trace_mod.newest_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = reduced
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = layer_value(m["name"], record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"tokens_per_s": run.tokens / run.window_s,
                  "setup_s": run.setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line["metrics"] = metrics
    line["device"] = device
    if traced:
        line["breakdown"] = {"device_ops": reduced.device_ops,
                             "idle_gaps": reduced.idle_gaps}
    line["checks"] = table
    detail = {"window_s": run.window_s, "chunks": run.chunks,
              "iterations": run.iterations, "tokens": run.tokens,
              "setup_s": run.setup_s, "compile_s": compile_s_setup[0],
              "compiles_in_window": run.compiles_in_window,
              "chunk_s": run.chunk_s, "gc_s": run.gc_s,
              "host_cpu_s": run.host_cpu_s,
              "reference_s": run.reference_s, "seed": seed}
    print(f"bench.run: {json.dumps(detail)}", file=sys.stderr)
    for name, row in table.items():
        print(f"check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    return line


if __name__ == "__main__":
    sys.exit(main())
