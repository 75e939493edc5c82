"""Share of the window's worker-shard slots (ticks × workers) that
trained: the engine's ``active_shards`` (Σ active workers over the
iterations that ran) over its ``shard_slots``. It counts slots, not work:
the traffic fixes it (62.5 % under the spot mix), whether or not the step
computes the other slots' shards. A `bench.layer_trace` printout, not a
``BENCHMARK.json`` metric."""
from bench.layer_trace import counter_ratio


def read(record):
    share = counter_ratio(record, "engine.active_shards",
                          "engine.shard_slots")
    return None if share is None else 100.0 * share
