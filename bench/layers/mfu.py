"""Model FLOP utilization of the whole step, over the whole window: the
model FLOPs of the active workers' rows in the iterations the window ran,
over the window's host-clock seconds times the chips times their bf16
peak. Preempted shards and idle ticks count for nothing, so it cannot pass
100%. The window holds the trainer's host time, the engine's market and
gate and the model step alike, so within one cell this is `tokens_per_s`
times a constant: it bounds every kernel's share, and attributes nothing
to one layer."""


def read(record):
    if not record.get("useful_flops") or not record.get("peak_flops"):
        return None
    return 100.0 * record["useful_flops"] / (
        record["window_s"] * record["chips"] * record["peak_flops"])
