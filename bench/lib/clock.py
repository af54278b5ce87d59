"""Host spans and JAX's own compile times.

``Spans`` writes the benchmark's spans into the profiler's trace in a
traced run, so that device gaps can be named by what the host was doing.
The kinds keep the times the metrics need (each fit's, each step's) on
``time.perf_counter`` themselves.

``CompileClock`` adds up the seconds JAX reports for tracing, lowering
and compiling (or loading a cached executable), from ``jax.monitoring``
events: the same source ``chip_smoke.py``'s ``PhaseTimer`` reads.
"""
from __future__ import annotations

import contextlib

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Spans:
    """The benchmark's spans around its calls into the program. In a
    traced run each is a ``jax.profiler.TraceAnnotation``, on the same
    clock as the device's operations; otherwise a span costs nothing."""

    def __init__(self, traced: bool = False):
        self.traced = traced

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield


class CompileClock:
    """Seconds of tracing, lowering and compiling since construction."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
