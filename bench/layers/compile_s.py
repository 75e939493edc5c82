"""Seconds of set-up spent tracing, lowering and compiling (or loading a
compiled program from the persistent cache), from JAX's monitoring
events."""


def read(record):
    return record.get("compile_s")
