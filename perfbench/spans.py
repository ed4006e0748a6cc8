"""The benchmark's own spans around the calls into each layer.

A `Recorder` times a span on the host clock always (two `perf_counter` calls)
and, in a traced run, also writes it into the profiler's trace as a
`jax.profiler.TraceAnnotation`, so that the device's idle gaps can be laid
against what the host was doing on the trace's own clock.  Spans are kept in
memory; nothing is written while the window runs.
"""

from __future__ import annotations

import contextlib
import time

SPAN_NAMES = ("train.step", "train.loss_read", "add_request", "engine.step",
              "traffic.wait")


class Recorder:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: dict = {}   # name -> [(start, end)] on time.perf_counter

    @contextlib.contextmanager
    def span(self, name):
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    def add(self, name, start, end):
        """A span the driver works out itself (e.g. due -> admitted)."""
        self.spans.setdefault(name, []).append((start, end))

    def within(self, name, lo, hi):
        return [(s, e) for s, e in self.spans.get(name, ()) if lo <= s and e <= hi]
