"""Share of the traced window the chip spent in the loss head: the device
self time of the ops under the program's ``repro.loss_head`` scope (final
norm, unembedding, logsumexp, gold logit and token weights, forward and
backward). Lower is better: a cheaper head lowers it as it raises
``tokens_per_s``."""
from bench.layer_trace import scope_share


def read(record):
    return scope_share(record, "repro.loss_head")
