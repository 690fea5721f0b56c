"""The shared timing API: the two measurement disciplines the repo's
benchmarks hand-rolled, plus the one span helper.

* ``time_jax`` — device-dispatch timing: one warm-up call to compile,
  then ``reps`` back-to-back dispatches with a single
  ``block_until_ready`` on the last result (the steady-state per-call
  latency of a jitted step; compile time excluded). Returns
  microseconds per call — the ``BENCH_*.json`` unit.
* ``time_best`` — host-call timing: best of ``repeats`` full wall-clock
  runs (the right discipline for host-side planners whose first call
  may compile — the best run is the steady state). Returns seconds.
* ``span`` — a ``perf_counter`` interval, recorded into a
  ``trace.Tracer`` when one is given and otherwise opened as a bare
  ``jax.profiler.TraceAnnotation`` of the same name (nearly free while
  no profile is taken). Either way the profiler sees the span under its
  bare name, on the device trace's clock.

Every span's duration also lands in a process-wide per-name tally of
the newest ``RECENT`` (``recent``). Like ``jits``' probes, it needs no
plumbing between an instrumented call site (the planner, which has no
tracer of its own) and whoever reads it, such as a benchmark that
drives the program in its own process.

``benchmarks/streams_bench.py``, ``benchmarks/planner_bench.py`` and
``online.evaluate`` all measure through this module.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List

from jax.profiler import TraceAnnotation

RECENT = 4096  # newest durations kept per span name

_TALLY: Dict[str, deque] = {}
_TALLY_LOCK = threading.Lock()


def time_jax(fn, *args, reps: int = 20, **kwargs) -> float:
    """Steady-state microseconds per call of a jitted callable."""
    import jax
    jax.block_until_ready(fn(*args, **kwargs))  # compile
    t0 = time.perf_counter_ns()
    out = None
    for _ in range(reps):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter_ns() - t0) / 1000.0 / reps


def time_best(fn, repeats: int = 3) -> float:
    """Best-of-``repeats`` wall seconds of a host call."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


class Span:
    """Result object of ``span`` — ``dur_s`` is valid after the block;
    ``attrs`` is the span record's attribute dict (writes before the
    block ends reach the tracer's record)."""

    __slots__ = ("name", "dur_s", "attrs")

    def __init__(self, name: str, attrs=None):
        self.name = name
        self.dur_s = 0.0
        self.attrs = {} if attrs is None else attrs


def _tally(name: str, dur_s: float) -> None:
    with _TALLY_LOCK:
        if name not in _TALLY:
            _TALLY[name] = deque(maxlen=RECENT)
        _TALLY[name].append(dur_s)


@contextmanager
def span(name: str, tracer=None, **attrs):
    """Time a block as span ``name``: into ``tracer`` (a
    ``trace.Tracer``) when one is given, so ad-hoc timing and the event
    timeline share records, else under a bare profiler annotation."""
    sp = Span(name, attrs)
    ctx = (tracer.span(name, **attrs) if tracer is not None
           else TraceAnnotation(name))
    with ctx as out:
        if tracer is not None:
            sp.attrs = out
        t0 = time.perf_counter()
        yield sp
        sp.dur_s = time.perf_counter() - t0
    _tally(name, sp.dur_s)


def recent(name: str, n: int = RECENT) -> List[float]:
    """The newest ``n`` durations (seconds, oldest first) of span
    ``name`` in this process; empty for a name never timed."""
    with _TALLY_LOCK:
        durs = list(_TALLY.get(name, ()))
    return durs[-n:] if n > 0 else []
