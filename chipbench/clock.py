"""Host-side clocks of a run: named spans and JAX's compile events."""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts the programs JAX compiles, from its own monitoring events. A
    program read from the persistent cache records no backend compile."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1


class Spans:
    """Host spans around the calls a run makes into the program. Each is a
    ``jax.profiler.TraceAnnotation`` as well, so that a profiler trace of
    the run names the host's work beside the device's."""

    def __init__(self):
        self.seconds = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name].append(time.perf_counter() - t0)
