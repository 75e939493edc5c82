"""Share of the traced window the chip spent in the engine's tick outside
the model: the device self time of the ops under ``repro.market`` (price,
bids, mask, runtime and cost of a tick) or ``repro.record`` (clock, cost
and trajectory updates)."""
from bench.layer_trace import scope_share


def read(record):
    return scope_share(record, "repro.market", "repro.record")
