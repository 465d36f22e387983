"""Host spans around the benchmark's own calls into each layer.

Off (``--trace 0``) a span costs one attribute test, so the end-to-end run
is not the traced run. On, each span is a ``jax.profiler.TraceAnnotation``
(it lands in the profiler's trace, on the profiler's clock, where the idle
gaps are attributed) and a ``perf_counter`` pair kept in memory for the
readers that want a median.
"""

from __future__ import annotations

import contextlib
import threading
import time


class Spans:
    def __init__(self, on: bool):
        self.on = on
        self.rows = []  # (name, t0, t1, thread name); list.append is atomic
        if on:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter(),
                                  threading.current_thread().name))

    def durations(self, name: str, t_from: float, t_to: float):
        """Seconds of every span called ``name`` that began inside [t_from, t_to)."""
        return [t1 - t0 for n, t0, t1, _ in self.rows if n == name and t_from <= t0 < t_to]
