"""What the elastic training path tells a profiler: host spans, device
scopes and counters.

Spans are `jax.profiler.TraceAnnotation`s: they land in the profiler's
trace, on the clock of the device ops, and cost next to nothing when no
profiler records. Scopes are `jax.named_scope`s: they only name the ops
traced inside them, in each op's ``op_name`` metadata (a backward or
recomputed op carries its forward scope inside ``transpose(jvp(...))``).
Counters are plain integers kept for the life of the process.

Host spans (the benchmark's ``host_idle`` reads the idle device time
under any of them, and its idle gaps carry their names):

- ``repro.prepare.scenarios``: `trainer._prepare_batched` stacking the
  scenarios;
- ``repro.prepare.batches``: `trainer._prepare_batched` stacking the feed
  and copying it to the device;
- ``repro.engine.init``: the engine building a fresh run's carry;
- ``repro.engine.dispatch``: the engine's jitted call, until it returns;
- ``repro.engine.fetch``: the engine copying the trajectories to the host,
  which waits for the device.

Device scopes (the benchmark gives each op's self time to the innermost
one in its ``op_name``):

- ``repro.market``: `engine._market_tick` — ``market_share``;
- ``repro.record``: the clock, cost and trajectory updates of a tick —
  ``market_share``;
- ``repro.step``: the model step of a tick; what runs under no inner
  scope (the layers, forward, backward and recomputed) — ``body_share``;
- ``repro.loss_head``: the unembedding and the cross-entropy (logsumexp,
  gold logit, token weights) — ``loss_head_share``;
- ``repro.update``: the optimizer update and the casts of the mixed
  precision carry — ``update_share``;
- ``repro.gate``: the tick's ``lax.cond`` on ``running`` around
  ``repro.step`` (in a vmapped grid a select over the model carry) —
  ``update_share``.

Counters, added after each engine fetch over the run's (scenario, seed)
replicas:

- ``engine.ticks``: ticks run;
- ``engine.running_ticks``: ticks on which the iteration ran —
  ``idle_tick_share`` is one less their ratio;
- ``engine.shard_slots``: ticks × the fleet width;
- ``engine.active_shards``: Σ of the active workers over the iterations
  that ran — ``useful_shard_share`` is their ratio to the slots;
- ``engine.skipped_shards``: shard slots whose forward and backward did
  not run: 0 on a vmapped grid, which computes every worker's shard on
  every tick; on a one-cell run, which branches, the slots of idle and
  finished ticks, and with the program's ``cell_step_fn`` (the zoo
  program's) every slot but the active shards.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict

import jax

_COUNTS: Counter = Counter()


def span(name: str):
    """A host span: a context manager recording ``name`` in the
    profiler's trace while it is open."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope: a context manager naming the ops traced inside."""
    return jax.named_scope(name)


def count(name: str, n: int) -> None:
    _COUNTS[name] += int(n)


def snapshot() -> Dict[str, int]:
    """Every counter's value so far; take the difference of two to count
    what happened between them."""
    return dict(_COUNTS)
