"""Share of the window's worker-shard slots (ticks × workers) that the
engine counts as skipped: its ``skipped_shards`` over its
``shard_slots``. The engine works the count out on the host from the
path it chose (one cell unvmapped, with or without a per-worker step),
not from what the device ran, so under one path the traffic fixes it
(37.5 % under the spot mix) and it cannot see a step that computes a
slot it was meant to skip; the body's device time shows that. A
`bench.layer_trace` printout, not a ``BENCHMARK.json`` metric."""
from bench.layer_trace import counter_ratio


def read(record):
    share = counter_ratio(record, "engine.skipped_shards",
                          "engine.shard_slots")
    return None if share is None else 100.0 * share
