"""The benchmark's own host spans: each is written into the profiler's
trace (`jax.profiler.TraceAnnotation`, on the device events' clock) and
kept here on the host clock, so that a run without the profiler can still
read them."""

import contextlib
import time

import jax

WINDOW_SPAN = "bench/window"  # the span around what a traced run measures


class Spans:
    def __init__(self):
        self.rows = []  # (name, start, end) on time.perf_counter

    @contextlib.contextmanager
    def span(self, name: str, **tags):
        with jax.profiler.TraceAnnotation(name, **tags):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    @contextlib.contextmanager
    def traced_window(self, trace_dir: str):
        """The profiler on, and the window's span open, around the body.
        Python's own frames are left out: they would be most of the trace
        and slow the host that the run measures."""
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with self.span(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def walls(self, name: str, since: float = 0.0):
        """Durations of the spans called `name` that began at or after
        `since`."""
        return [t1 - t0 for n, t0, t1 in self.rows
                if n == name and t0 >= since]
