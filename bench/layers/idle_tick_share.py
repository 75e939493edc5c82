"""Share of the window's engine ticks on which no iteration ran (every
worker preempted), each of which still pays a whole model step: one less
the engine's ``running_ticks`` over its ``ticks``."""
from bench.layer_trace import counter_ratio


def read(record):
    share = counter_ratio(record, "engine.running_ticks", "engine.ticks")
    return None if share is None else 100.0 * (1.0 - share)
