"""Share of the traced window the chip spent in the model's layers: the
device self time of the ops under the program's ``repro.step`` scope and
under no scope inside it (forward, backward and recomputation of the
layers, the embedding). Lower is better: the layers are the first
bottleneck of both cells, and a change that makes them cheaper lowers
this share as it raises ``tokens_per_s``. A gain in another layer raises
it, which that layer's own share and ``tokens_per_s`` show."""
from bench.layer_trace import scope_share


def read(record):
    return scope_share(record, "repro.step")
