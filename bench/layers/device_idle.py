"""Share of the traced window in which no operation ran on the chip."""


def read(record):
    trace = record.get("trace")
    return None if trace is None else 100.0 * trace.idle_share
