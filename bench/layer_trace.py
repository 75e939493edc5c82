"""Which layer of the program each second of a traced window went to.

    python3 -m bench.layer_trace --workload <cell> --seed <n> --seconds <s>

runs a cell once with ``--trace 1``, through `bench.run`'s own traced
path, and prints one JSON line: every per-layer reader of
``bench/layers/`` (those ``BENCHMARK.json`` lists and the rest), the
device seconds of each scope, the counters over the window, the window's
tokens/s as traced, and the top ops and idle gaps named by their layer.
Needs a TPU, as a run does.

The reduction, beside `bench.trace`'s (whose numbers it leaves as they
are):

- an op's layer: each device op's event metadata holds its ``op_name`` in
  the stat ``tf_op``, a path such as
  ``jit(f)/while/body/repro.step/transpose(jvp(repro.loss_head))/dot``.
  Its scope is the innermost name in that path, wrapped or not, that is a
  scope: one of `SCOPES`, or one the program says it opened
  (``repro.obs.scope_names()``, where the program has it; dotted names
  such as ``repro.moe.route`` too). A backward or recomputed op carries
  its forward scope in ``transpose(jvp(...))``, and a fusion carries its
  root's. `bench.trace.load` keeps only names and times, so the metadata
  is read from the file's protobuf here;
- scope seconds: each op's self time in the window goes to its scope, or
  to ``unscoped``; they add up to the busy time, averaged over the chips
  that ran, as `bench.trace.reduce` averages it;
- a hole: a loop op (``while``) does next to nothing itself, so where the
  loops' self time on a chip passes `LOST_SHARE` of the window, the
  profiler kept the loop but dropped ops nested in it, as it now and
  then does. That time is ``lost_s``; it goes to no scope, and that
  chip's scope seconds are scaled up to its busy time, so each share is
  read over the ops the profile kept;
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
import json
import os
import re
import sys
import time
from collections import defaultdict
from typing import (AbstractSet, Dict, FrozenSet, Iterator, List, Optional,
                    Tuple)

from bench import trace as trace_mod

#: the stat of a device op's event metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"
#: the program's device scopes that every program since they came in opens
#: (`repro.obs`); a program that declares its scopes adds its own
SCOPES = ("repro.market", "repro.record", "repro.gate", "repro.step",
          "repro.loss_head", "repro.update")
#: where an op under no scope is counted
UNSCOPED = "unscoped"
#: host spans of the program start with this
PROGRAM_SPAN = "repro."
#: the kind of a loop op, whose own work is its condition: about no time
LOOP = "while"
#: loops' self time over this share of the window is nested ops the
#: profiler dropped
LOST_SHARE = 0.01

#: the names in an ``op_name`` path: its segments, and what they wrap
_NAME = re.compile(r"[^/(),:\s]+")


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


def known_scopes() -> FrozenSet[str]:
    """`SCOPES` and every scope the program says it opened: the names
    ``repro.obs.scope_names()`` returns, where the program has it. A
    program records a name when it traces the scope, which a run does
    even where its compiled programs come from the persistent cache."""
    from repro import obs

    declared = getattr(obs, "scope_names", None)
    return frozenset(SCOPES).union(declared() if declared else ())


def scope_of(op_name: str, scopes: Optional[AbstractSet[str]] = None
             ) -> Optional[str]:
    """The innermost name in ``op_name``'s path that is one of ``scopes``
    (`known_scopes` by default); a name may be wrapped, as in
    ``transpose(jvp(repro.step))``."""
    scopes = known_scopes() if scopes is None else scopes
    found = [n for n in _NAME.findall(op_name) if n in scopes]
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
    lost_s: float                         # nested ops the profiler dropped
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
    scopes = known_scopes()
    lo, hi = trace_mod.window_of(trace.spans)
    program = trace_mod.union([(a, b) for n, a, b in trace.spans
                               if n.startswith(PROGRAM_SPAN)], lo, hi)
    busy_per_chip, host_idle, lost = [], 0.0, 0.0
    per_scope, per_op = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for ops in trace.devices.values():
        busy = trace_mod.union([(a, b) for _, a, b in ops], lo, hi)
        if not busy:
            continue
        busy_s = sum(b - a for a, b in busy)
        busy_per_chip.append(busy_s)
        times = trace_mod.self_times(ops, lo, hi)
        loops = {n for n in times
                 if trace_mod.short_name(n).endswith(f" {LOOP}")}
        loop_s = sum(times[n] for n in loops)
        hole = LOST_SHARE * (hi - lo) < loop_s < busy_s
        lost += loop_s if hole else 0.0
        scale = busy_s / (busy_s - loop_s) if hole else 1.0
        for name, t in times.items():
            short = trace_mod.short_name(name)
            scope = scope_of(names.get(name, ""), scopes)
            per_op[f"{short} [{scope}]" if scope else short] += t
            if hole and name in loops:
                continue
            per_scope[scope or UNSCOPED] += t * scale
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
        lost_s=lost / chips * 1e-9,
        device_ops=top_of(per_op), unscoped_ops=top_of(unscoped),
        idle_gaps=[list(g) for g in
                   sorted(gaps, key=lambda g: -g[1])[:top]])


def load(path: str) -> Tuple[trace_mod.Reduced, Layers]:
    """The profile at ``path`` reduced twice: by `bench.trace.reduce`,
    over the harness's spans alone (the device idle share and the traced
    line's breakdown), and by layer."""
    trace = trace_mod.load(path)
    plain = trace_mod.reduce(trace)
    trace.spans = trace.spans + program_spans(path)
    return plain, reduce(trace, op_names(path))


# ---------------------------------------------- what the readers share


def scope_share(record, *scopes: str) -> Optional[float]:
    """Percent of the traced window the chip spent in ``scopes``: None
    without a trace, or against a program without scopes."""
    layers = record.get("layers")
    per_scope = getattr(layers, "scope_s", None)
    if not per_scope:
        return None
    return 100.0 * sum(per_scope.get(s, 0.0) for s in scopes) / \
        layers.window_s


def counter_ratio(record, part: str, whole: str) -> Optional[float]:
    """``part / whole`` of the window's counters: None without counters,
    or with ``whole`` zero."""
    counters = record.get("counters") or {}
    if not counters.get(whole):
        return None
    return counters.get(part, 0) / counters[whole]


# ----------------------------------------------------------- a traced run


def readers() -> List[str]:
    """Every per-layer metric that has a reader under ``bench/layers/``."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "layers")
    return sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and not f.startswith("_"))


def measure(cell, seed: int, seconds: float, clock, peaks, device,
            t_start: float) -> dict:
    """One run of ``cell`` through `bench.run`'s traced path, set-up,
    window, trace and reference alike: the line this module prints."""
    from bench import run as bench_run

    line, record, run = bench_run.measure(cell, seed, seconds, True, clock,
                                          peaks, device, t_start)
    layers = record["layers"]
    scope_s = layers.scope_s or {}
    return {"cell": cell.name, "seed": seed, "correct": line["correct"],
            "metrics": {m: bench_run.layer_value(m, record)
                        for m in readers()},
            "device_idle": 100.0 * record["trace"].idle_share,
            "tokens_per_s_traced": run.tokens / run.window_s,
            "window_s": layers.window_s, "busy_s": layers.busy_s,
            "scope_s": {s: t for s, t in scope_s.items() if s != UNSCOPED},
            "unscoped_s": scope_s.get(UNSCOPED),
            "host_idle_s": layers.host_idle_s, "lost_s": layers.lost_s,
            "counters": record["counters"], "chunk_s": run.chunk_s,
            "compiles_in_window": run.compiles_in_window,
            "breakdown": {"device_ops": layers.device_ops,
                          "unscoped_ops": layers.unscoped_ops,
                          "idle_gaps": layers.idle_gaps},
            "device": line["device"]}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run as bench_run

    opened = bench_run.open_cell(args.workload, args.seed)
    if isinstance(opened, int):
        return opened
    cell, clock, peaks, device = opened
    line = measure(cell, args.seed, args.seconds, clock, peaks, device,
                   t_start)
    line["wall_s"] = time.perf_counter() - t_start
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
