"""Host spans of the benchmark's own loop.

Each span is timed by the host's clock and, while it lasts, is a
`jax.profiler.TraceAnnotation` of the same name, so that in a traced run
it also sits on the profiler's clock next to the device's operations and
an idle gap can be named by what the host was doing in it.
"""

from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.events = []          # (name, start, end) by time.perf_counter

    @contextmanager
    def __call__(self, name: str):
        import jax

        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.events.append((name, start, time.perf_counter()))

    def durations(self, since: float, until: float) -> dict:
        """name -> durations in seconds of the spans that ended in
        (since, until]."""
        out = {}
        for name, start, end in self.events:
            if since < end <= until:
                out.setdefault(name, []).append(end - start)
        return out
