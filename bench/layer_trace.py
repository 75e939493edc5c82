"""Which layer of the program each second of a traced window went to.

    python3 -m bench.layer_trace --workload <cell> --seed <n> --seconds <s>

runs a cell's set-up and one traced window, as ``bench.run --trace 1``
does, and prints one JSON line: the per-layer metrics of ``bench/layers/``
that read the program's scopes, spans and counters (`repro.obs`), the
device seconds of each scope, the counters over the window, the window's
tokens/s as traced, and the top ops and idle gaps named by their layer. It
compares nothing with the reference. Needs a TPU, as a run does.

The reduction, on top of `bench.trace` (whose numbers it leaves as they
are):

- an op's layer: each device op's event metadata holds its ``op_name`` in
  the stat ``tf_op``, a path such as
  ``jit(f)/while/body/repro.step/transpose(jvp(repro.loss_head))/dot``.
  The innermost of `SCOPES` in it is the op's scope; a backward or
  recomputed op carries its forward scope in ``transpose(jvp(...))``, and
  a fusion carries its root's. `bench.trace.load` keeps only names and
  times, so the metadata is read from the file's protobuf here;
- scope seconds: each op's self time in the window goes to its scope, or
  to ``unscoped``; they add up to the busy time, averaged over the chips
  that ran, as `bench.trace.reduce` averages it;
- host idle: the window's idle time during which some host span of the
  program (``repro.*``) was open;
- idle gaps are labelled with the innermost ``bench.*`` or ``repro.*``
  span open at their midpoint.

Against a program without scopes or program spans (one that predates
them) the scope seconds and the host idle are None, and so is every
metric that reads them.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace as trace_mod

#: the stat of a device op's event metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"
#: the program's device scopes (`repro.obs`)
SCOPES = ("repro.market", "repro.record", "repro.gate", "repro.step",
          "repro.loss_head", "repro.update")
#: where an op under none of `SCOPES` is counted
UNSCOPED = "unscoped"
#: host spans of the program start with this
PROGRAM_SPAN = "repro."
#: the per-layer metrics this reduction feeds, as ``bench/layers/`` names
#: them
METRICS = ("loss_head_share", "update_share", "body_share", "market_share",
           "host_idle", "useful_shard_share", "idle_tick_share")
#: where the traced window's profile is written, read, then removed
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".work", "layer_trace")

_SCOPE_SEGMENT = re.compile(r"(?:^|[/(])(repro\.[a-z_]+)(?=[/):]|$)")


# ------------------------------------------------- the profile's metadata


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes (a view, not a copy) for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _map_values(entries) -> Dict[int, memoryview]:
    """A protobuf map<int64, message>: its entries' keys (field 1) to their
    values (field 2)."""
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_names(path: str) -> Dict[str, str]:
    """Each device op's event name (what `bench.trace.load` keys ops by)
    to its ``op_name``, from the `OP_NAME_STAT` stat of the device planes'
    event metadata. The schema read is XPlane's (``xplane.proto``): a space
    holds planes (1); a plane its name (2), event metadata (4) and stat
    metadata (5); an event metadata its name (2) and stats (5); a stat its
    metadata id (1) and a string (5) or a reference to a stat metadata
    whose name is the string (7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                stats.append(value)
        if not trace_mod.DEVICE_PLANE.match(name):
            continue
        stat_names = {k: _text(dict(_fields(v)).get(2, b""))
                      for k, v in _map_values(stats).items()}
        wanted = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        for meta in _map_values(events).values():
            event_name, op_name = "", None
            for field, value in _fields(meta):
                if field == 2:
                    event_name = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        op_name = (_text(stat[5]) if 5 in stat else
                                   stat_names.get(stat.get(7), ""))
            if op_name:
                out[event_name] = op_name
    return out


def program_spans(path: str) -> List[trace_mod.Event]:
    """The program's host spans (``repro.*``) in the profile."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_SPAN)]


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of `SCOPES` among the path segments of ``op_name``
    (a segment may be wrapped, as in ``transpose(jvp(repro.step))``)."""
    found = [s for s in _SCOPE_SEGMENT.findall(op_name) if s in SCOPES]
    return found[-1] if found else None


# --------------------------------------------------------- the reduction


@dataclasses.dataclass
class Layers:
    """A traced window by layer. Seconds are averaged over the chips that
    ran, as `bench.trace.Reduced`'s busy time is."""

    window_s: float
    busy_s: float
    scope_s: Optional[Dict[str, float]]   # scope (or UNSCOPED) -> seconds;
    #                                       None if no op had a scope
    host_idle_s: Optional[float]          # idle under a program span; None
    #                                       if the program opened none
    device_ops: List[Tuple[str, float]]   # top 10, ``<op> [<scope>]``
    unscoped_ops: List[Tuple[str, float]]  # top 10 of the ops in no scope
    idle_gaps: List[Tuple[str, float]]    # 10 longest, by innermost span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: trace_mod.Trace, names: Dict[str, str],
           top: int = 10) -> Layers:
    """``trace`` with the program's spans among its ``spans``, and
    ``names`` from `op_names`."""
    lo, hi = trace_mod.window_of(trace.spans)
    program = trace_mod.union([(a, b) for n, a, b in trace.spans
                               if n.startswith(PROGRAM_SPAN)], lo, hi)
    busy_per_chip, host_idle = [], 0.0
    per_scope, per_op = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for ops in trace.devices.values():
        busy = trace_mod.union([(a, b) for _, a, b in ops], lo, hi)
        if not busy:
            continue
        busy_per_chip.append(sum(b - a for a, b in busy))
        for name, t in trace_mod.self_times(ops, lo, hi).items():
            scope = scope_of(names.get(name, ""))
            per_scope[scope or UNSCOPED] += t
            short = trace_mod.short_name(name)
            per_op[f"{short} [{scope}]" if scope else short] += t
            if scope is None:
                unscoped[short] += t
        idle = trace_mod.gaps(busy, lo, hi)
        host_idle += _overlap(idle, program)
        gaps += [(trace_mod.label(trace.spans, (a + b) / 2), (b - a) * 1e-9)
                 for a, b in idle]
    if not busy_per_chip:
        raise ValueError("no device operation ran inside the window")
    chips = len(busy_per_chip)
    scoped = any(s != UNSCOPED for s in per_scope)

    def top_of(times):
        return [[n, s * 1e-9] for n, s in
                sorted(times.items(), key=lambda kv: -kv[1])[:top]]

    return Layers(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy_per_chip) / chips * 1e-9,
        scope_s=({s: t / chips * 1e-9 for s, t in per_scope.items()}
                 if scoped else None),
        host_idle_s=host_idle / chips * 1e-9 if program else None,
        device_ops=top_of(per_op), unscoped_ops=top_of(unscoped),
        idle_gaps=[list(g) for g in
                   sorted(gaps, key=lambda g: -g[1])[:top]])


def load(path: str) -> Layers:
    trace = trace_mod.load(path)
    trace.spans += program_spans(path)
    return reduce(trace, op_names(path))


# ---------------------------------------------- what the readers share


def scope_share(record, *scopes: str) -> Optional[float]:
    """Percent of the traced window the chip spent in ``scopes``: None
    without a trace, or against a program without scopes."""
    trace = record.get("trace")
    per_scope = getattr(trace, "scope_s", None)
    if not per_scope:
        return None
    return 100.0 * sum(per_scope.get(s, 0.0) for s in scopes) / \
        trace.window_s


def counter_ratio(record, part: str, whole: str) -> Optional[float]:
    """``part / whole`` of the window's counters: None without counters,
    or with ``whole`` zero."""
    counters = record.get("counters") or {}
    if not counters.get(whole):
        return None
    return counters.get(part, 0) / counters[whole]


# ----------------------------------------------------------- a traced run


def measure(cell, seed: int, seconds: float, compile_count,
            trace_dir: str = TRACE_DIR) -> dict:
    """Set-up and one traced window of ``cell``: the result line."""
    import jax

    from bench import run as bench_run
    from bench import train_cell
    from repro import obs

    plan = train_cell.make_plan(cell.config, cell.traffic, seed)
    run = train_cell.Run()
    state, tick, scenarios = train_cell.setup(plan, run)
    jax.block_until_ready(state)
    gc.collect()
    gc.freeze()
    shutil.rmtree(trace_dir, ignore_errors=True)
    before = obs.snapshot()
    jax.profiler.start_trace(trace_dir)
    try:
        state = train_cell.window(plan, run, state, tick, scenarios,
                                  seconds, compile_count)
    finally:
        jax.profiler.stop_trace()
    after = obs.snapshot()
    del state
    layers = load(trace_mod.newest_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    record = {"window_s": run.window_s, "trace": layers,
              "counters": {k: v - before.get(k, 0)
                           for k, v in after.items()}}
    metrics = {m: bench_run.layer_value(m, record) for m in METRICS}
    scope_s = layers.scope_s or {}
    return {"cell": cell.name, "seed": seed, "metrics": metrics,
            "device_idle": 100.0 * layers.idle_share,
            "tokens_per_s_traced": run.tokens / run.window_s,
            "window_s": layers.window_s, "busy_s": layers.busy_s,
            "scope_s": {s: t for s, t in scope_s.items() if s != UNSCOPED},
            "unscoped_s": scope_s.get(UNSCOPED),
            "host_idle_s": layers.host_idle_s,
            "counters": record["counters"], "chunk_s": run.chunk_s,
            "compiles_in_window": run.compiles_in_window,
            "breakdown": {"device_ops": layers.device_ops,
                          "unscoped_ops": layers.unscoped_ops,
                          "idle_gaps": layers.idle_gaps}}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run as bench_run

    sys.path.insert(0, bench_run.SRC)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench_run.CACHE_DIR
    import jax

    from bench.spans import CompileClock
    from bench.spec import load_cell
    from repro.launch.jitcache import enable_persistent_cache

    clock = CompileClock()
    enable_persistent_cache()
    bench_run.configure_cache(jax)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("bench.layer_trace: JAX found no TPU", file=sys.stderr)
        return 2
    line = measure(load_cell(args.workload), args.seed, args.seconds,
                   lambda: clock.compiles)
    line["device"] = {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(jax.devices())}
    line["wall_s"] = time.perf_counter() - t_start
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
