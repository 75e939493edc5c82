"""Share of the worker shards the model step computed over the window
that trained: the engine's ``active_shards`` (Σ active workers over the
iterations that ran) over its ``shard_slots`` (ticks × workers). The step
computes every worker's shard on every tick, so the rest is discarded."""
from bench.layer_trace import counter_ratio


def read(record):
    share = counter_ratio(record, "engine.active_shards",
                          "engine.shard_slots")
    return None if share is None else 100.0 * share
