"""The harness's own instrumentation: named spans around each call into a
layer, written into the profiler's trace when one is recording, and the
compile time JAX reports through its monitoring events (the listener of
the bring-up check `chip_smoke._Phase`, copied)."""
from __future__ import annotations

import contextlib

import jax

#: span names, by the layer the call goes into
CHUNK = "bench.chunk"          # train_zoo: engine scan of one chunk, fetch
BATCH = "bench.batch"          # the feed handing train_zoo a batch
INIT = "bench.init"            # the weights and carry made on the device
REFERENCE = "bench.reference"  # the reference, after the window


@contextlib.contextmanager
def span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


class CompileClock:
    """Seconds spent tracing, lowering and compiling, and how many backend
    compiles ran, from JAX's own duration events. One per process: JAX
    keeps its listeners for the life of the process."""

    def __init__(self):
        from jax._src import dispatch

        self.seconds = 0.0
        self.compiles = 0
        self._events = {dispatch.BACKEND_COMPILE_EVENT,
                        dispatch.JAXPR_TRACE_EVENT,
                        dispatch.JAXPR_TO_MLIR_MODULE_EVENT}
        self._backend = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self._events:
            self.seconds += duration
            if event == self._backend:
                self.compiles += 1
