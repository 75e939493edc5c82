"""The chip benchmark of the elastic trainer.

`python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of ``BENCHMARK.json`` once and prints one JSON line. Each
configuration (``configs/``), traffic mix (``traffic/``), per-layer metric
(``layers/``), model-FLOP count (``flops/``) and correctness limit
(``limits/``) is a file of its own, found by the name that
``BENCHMARK.json`` gives it; ``reference/`` holds the plain float32 models
that decide ``correct``.
"""
