"""Host-clock spans and JAX compile events, kept in memory."""

from __future__ import annotations

import time
from contextlib import contextmanager


class CompileCounter:
    """Counts JAX's ``/jax/core/compile/*`` duration events (tracing,
    lowering, backend compile), so a window can show it compiled
    nothing."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration


class Spans:
    """Named host-clock spans; with ``annotate`` each is also a
    `jax.profiler.TraceAnnotation`, so it lands in the profiler's trace
    on the device's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.rows.append((name, t0, t1))
