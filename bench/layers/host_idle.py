"""Share of the traced window in which the chip ran nothing while the
program's host code (a ``repro.*`` span: preparing the feed, building,
dispatching or fetching an engine call) was open."""


def read(record):
    trace = record.get("trace")
    idle = getattr(trace, "host_idle_s", None)
    return None if idle is None else 100.0 * idle / trace.window_s
