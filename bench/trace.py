"""From the profiler's ``.xplane.pb`` to the numbers of a traced run.

The trace holds device planes (``/device:TPU:<n>``), whose ``XLA Ops``
line has one event per operation the chip ran, and the host plane, on
whose Python thread the harness's `spans` sit. Host and device events
share the profiler's clock. The window is the stretch from the start of
the first ``bench.chunk`` span to the end of the last.

- busy: the union of the device-op intervals inside the window, per chip,
  averaged over the chips that ran anything;
- idle share: 1 - busy / window;
- top ops: device self time per op (an op's time less the ops nested in
  it, such as a loop's body), summed by op name over the window;
- idle gaps: the stretches of the window with no op on the chip, each
  labelled with the innermost harness span open at its midpoint (``host``
  where none was).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

#: one event: (name, start_ns, end_ns)
Event = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
OP_KIND = re.compile(r"(?<![\w.%])([a-z][a-z0-9_-]*)\(")
WINDOW_SPAN = "bench.chunk"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]      # plane name -> device ops
    spans: List[Event]                   # the harness's host spans


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, spans)


def window_of(spans: Sequence[Event]) -> Tuple[float, float]:
    chunks = [s for s in spans if s[0] == WINDOW_SPAN]
    if not chunks:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return min(s[1] for s in chunks), max(s[2] for s in chunks)


def union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kLoop`` -> ``%fusion.12
    fusion``: the op's name and its kind, without shapes and operands."""
    name, _, rest = hlo.partition(" = ")
    kind = OP_KIND.search(rest)
    return f"{name} {kind.group(1)}" if kind else name


def self_times(ops: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Per op name, the time inside [lo, hi] in which that op ran and no
    op nested in it did. Events on one line nest or are disjoint."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []           # [name, end, self time]

    def close(entry):
        out[entry[0]] += entry[2]

    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        close(stack.pop())
    return out


def label(spans: Sequence[Event], t: float) -> str:
    """The innermost harness span open at ``t`` (latest start wins)."""
    open_ = [s for s in spans if s[1] <= t < s[2]]
    return max(open_, key=lambda s: s[1])[0] if open_ else "host"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                         # averaged over the chips used
    device_ops: List[Tuple[str, float]]   # top 10, seconds
    idle_gaps: List[Tuple[str, float]]    # 10 longest, seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: Trace, top: int = 10) -> Reduced:
    lo, hi = window_of(trace.spans)
    busy_per_chip, per_op = [], defaultdict(float)
    all_gaps: List[Tuple[str, float]] = []
    for ops in trace.devices.values():
        busy = union([(a, b) for _, a, b in ops], lo, hi)
        if not busy:
            continue
        busy_per_chip.append(sum(b - a for a, b in busy))
        for name, t in self_times(ops, lo, hi).items():
            per_op[short_name(name)] += t
        all_gaps += [(label(trace.spans, (a + b) / 2), (b - a) * 1e-9)
                     for a, b in gaps(busy, lo, hi)]
    if not busy_per_chip:
        raise ValueError("no device operation ran inside the window")
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy_per_chip) / len(busy_per_chip) * 1e-9,
        device_ops=[[n, s * 1e-9] for n, s in ops_top],
        idle_gaps=[list(g) for g in
                   sorted(all_gaps, key=lambda g: -g[1])[:top]])
