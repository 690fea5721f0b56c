"""The benchmark's own spans around the calls into the program's layers.

A span records its host-clock duration and, in a traced run, also opens a
``jax.profiler.TraceAnnotation`` of the same name, so the profiler puts
it on the device trace's clock and idle gaps can be named by it."""
from __future__ import annotations

import functools
import time
from collections import defaultdict
from contextlib import contextmanager

WINDOW = "bench.window"
SOURCE = "bench.source"
ROUTE = "router.route"
METER = "meter.record_update"
PLAN = "planner.plan_fleet_mixed"
CLIENT = "bench.client"
# the program's own Tracer spans (mirrored into the profiler by
# ObsConfig(profiler_annotations=True))
PROGRAM = ("plan", "ingest", "replan", "finalize")


class Spans:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.times = defaultdict(list)  # name -> [seconds]
        self.counters = defaultdict(float)

    @contextmanager
    def span(self, name: str):
        if self.annotate:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.times[name].append(time.perf_counter() - t0)
            if self.annotate:
                ann.__exit__(None, None, None)

    def wrap(self, name: str, fn, count=None):
        """``fn`` inside span ``name``; ``count(result, counters)`` then
        adds the call's counts."""
        @functools.wraps(fn)
        def inner(*a, **kw):
            with self.span(name):
                out = fn(*a, **kw)
            if count is not None:
                count(out, self.counters)
            return out
        return inner
