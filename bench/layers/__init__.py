"""Per-layer metrics, one reader per file named as the metric in
``BENCHMARK.json``. Each defines ``read(record) -> float | None`` over the
run's record (a dict: ``window_s``, ``useful_flops``, ``chips``,
``peak_flops``, ``compile_s``; in a traced run also ``trace``, the
`bench.trace.Reduced` of the window, ``layers``, its
`bench.layer_trace.Layers`, and ``counters``, the program's counters over
the window; each None in an untraced run). A reader that finds nothing to
read returns None and the metric is left out of the line."""
