"""Share of the traced window the chip spent in the model's layers: the
device self time of the ops under the program's ``repro.step`` scope and
under no scope inside it (forward, backward and recomputation of the
layers, the embedding)."""
from bench.layer_trace import scope_share


def read(record):
    return scope_share(record, "repro.step")
