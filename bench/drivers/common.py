"""What both window loops share: spans on the profiler's clock, the
compile counter, the traced window, and the device's peak memory."""
from __future__ import annotations

import contextlib
import glob
import shutil
import tempfile
import time

import jax

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts programs lowered (every new program or shape in this
    process) and, of those, the ones the persistent cache held."""

    def __init__(self):
        self.lowered = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self.lowered += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def span(name: str, on: bool):
    """A host span in the profiler's trace (``bench.<name>``) when tracing."""
    return jax.profiler.TraceAnnotation(f"bench.{name}") if on else contextlib.nullcontext()


class TracedWindow:
    """Profiler on around the window, reduced to a ``TraceSummary`` after."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.summary = None
        self._win = None

    def start(self):
        if self.on:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()

    def stop(self):
        if self.on and self._win is not None:
            self._win.__exit__(None, None, None)
            self._win = None
            jax.profiler.stop_trace()

    def reduce(self):
        from bench import trace_reduce

        if self.on and self.dir is not None:
            try:
                path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[0]
                self.summary = trace_reduce.reduce(path)
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)
        return self.summary


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def now() -> float:
    return time.perf_counter()
