"""Share of the traced window the chip spent updating the carry: the
device self time of the ops under ``repro.update`` (the optimizer and the
mixed-precision casts) or ``repro.gate`` (the engine landing the stepped
model on running ticks). XLA may fuse the two; a fusion takes its root's
scope, so they are read as one. Lower is better: the update trains no
token, so a cheaper one raises ``tokens_per_s`` as it lowers this."""
from bench.layer_trace import scope_share


def read(record):
    return scope_share(record, "repro.update", "repro.gate")
