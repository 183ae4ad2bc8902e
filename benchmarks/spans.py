"""The benchmark's own spans: (name, start, end, counts) on the host's
``perf_counter``, and, in a traced run, the same interval as a
``jax.profiler.TraceAnnotation`` so it sits on the profiler's clock beside
the device's operations."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, traced: bool):
        self.traced = traced
        self.records: list[tuple[str, float, float, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **counts):
        if self.traced:
            import jax

            annotation = jax.profiler.TraceAnnotation(f"bench:{name}")
        else:
            annotation = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
        finally:
            self.records.append((name, t0, time.perf_counter(), counts))

    def named(self, name: str) -> list[tuple[str, float, float, dict]]:
        return [r for r in self.records if r[0] == name]
