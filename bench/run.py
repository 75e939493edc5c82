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


def open_cell(workload: str, seed: int):
    """Everything a run does before its set-up: the cell's files, the
    compile clock and cache, and the chips. Returns (cell, clock, peaks,
    device), or the exit code of a refusal."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return _refuse(f"no program under {SRC}: run from a checkout")
    if seed < 0:
        return _refuse(f"--seed {seed} is negative")
    sys.path.insert(0, SRC)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

    from bench.spec import load_cell

    cell = load_cell(workload)

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
        return _refuse(f"{workload} needs {cell.chips} chips, JAX "
                       f"sees {len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        return _refuse(str(e))
    return cell, clock, peaks, {"platform": platform,
                                "kind": devices[0].device_kind,
                                "count": len(devices)}


def main(argv=None) -> int:
    args = parse(argv)
    opened = open_cell(args.workload, args.seed)
    if isinstance(opened, int):
        return opened
    cell, clock, peaks, device = opened
    line = measure(cell, args.seed, args.seconds, bool(args.trace), clock,
                   peaks, device)[0]
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def read_trace(record: dict, profile: str, counters) -> None:
    """What a traced window gives the per-layer readers, added to
    ``record``: ``trace``, the profile reduced by `bench.trace.reduce`
    (the device idle share and the line's breakdown); ``layers``, the
    profile by the program's scopes and spans (`bench.layer_trace`); and
    ``counters``, the program's counters over the window."""
    from bench import layer_trace

    record["trace"], record["layers"] = layer_trace.load(profile)
    record["counters"] = counters


def measure(cell, seed: int, seconds: float, traced: bool, clock, peaks,
            device, t_start: float = None):
    """One run of ``cell`` on whatever devices JAX has: (the result line,
    the record the per-layer readers read, the `train_cell.Run`). Prints
    the numbers compared, each beside its limit, as the last lines of
    standard error."""
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
              "compile_s": compile_s_setup[0], "trace": None,
              "layers": None, "counters": None}
    line = {"correct": correct and run.nonfinite == 0,
            "attempted": run.iterations, "failed": run.nonfinite}
    if traced:
        t0 = time.perf_counter()
        read_trace(record, trace_mod.newest_xplane(trace_dir),
                   run.counters)
        read_s = time.perf_counter() - t0
        shutil.rmtree(trace_dir, ignore_errors=True)
        lost_s = record["layers"].lost_s
        if lost_s:
            print(f"bench.run: the profile lost {lost_s!r} s of ops nested "
                  "in a loop; the scope shares are over the ops it kept",
                  file=sys.stderr)
        reduced = record["trace"]
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
    if traced:
        detail.update(read_trace_s=read_s, lost_s=lost_s)
    print(f"bench.run: {json.dumps(detail)}", file=sys.stderr)
    for name, row in table.items():
        print(f"check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    return line, record, run


if __name__ == "__main__":
    sys.exit(main())
