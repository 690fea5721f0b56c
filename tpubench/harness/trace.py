"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` turns an ``.xplane.pb`` into a plain ``Trace``: per device, the
op events and the program (module) events, and the host annotation spans,
all as (name, start_ns, dur_ns) on the profile's one clock. The functions
below read only that plain form, so the tests check them on a small
recorded trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, dur_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda evs: [tuple(e) for e in evs]  # noqa: E731
        return cls(ops={k: tup(v) for k, v in d["ops"].items()},
                   modules={k: tup(v) for k, v in d["modules"].items()},
                   host=tup(d["host"]))

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def load(log_dir: str, host_names: Sequence[str] = ()) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``. Host events are
    kept only when their name is one of ``host_names`` (annotations)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    names = set(host_names)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = tr.ops
                elif line.name == MODULES_LINE:
                    dest = tr.modules
                else:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name in names)
    return tr


def union_ns(intervals: Sequence[Tuple[float, float]],
             lo: float = float("-inf"), hi: float = float("inf")) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: Trace, device: str, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some op ran on ``device``."""
    return union_ns([(s, s + d) for _, s, d in tr.ops.get(device, [])],
                    lo, hi)


def window(tr: Trace, name: str) -> Optional[Tuple[float, float]]:
    """[start, end) of the host span ``name`` (the measured window)."""
    spans = [(s, s + d) for n, s, d in tr.host if n == name]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def program_ns(tr: Trace, device: str, prefix: str, lo: float,
               hi: float) -> Tuple[float, int]:
    """Summed device time and count of the program (module) events whose
    name starts with ``prefix`` and that start in [lo, hi)."""
    evs = [(s, d) for n, s, d in tr.modules.get(device, [])
           if n.startswith(prefix) and lo <= s < hi]
    return sum(d for _, d in evs), len(evs)


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def op_name(hlo: str) -> str:
    """``%fusion.6 = f32[8]{0} fusion(...)`` -> ``fusion.6 (fusion)``: the
    instruction and its opcode, without the operands."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    m = _OPCODE.search(" " + rest)
    return f"{head.lstrip('%')} ({m.group(1)})" if m else head.lstrip("%")


def top_ops(tr: Trace, device: str, lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` ops (by ``op_name``) with the most device seconds in
    [lo, hi)."""
    tot: Dict[str, float] = {}
    for name, s, d in tr.ops.get(device, []):
        if lo <= s < hi:
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + d
    return [(k, v / 1e9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, device: str, lo: float, hi: float,
              span_names: Sequence[str], n: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of ``device`` in [lo, hi), each named by
    the innermost host span (of ``span_names``) that covers at least half
    of it, else by the span that covers most of it, else ``"(no span)"``."""
    busy = sorted((s, s + d) for _, s, d in tr.ops.get(device, []))
    gaps = []
    cursor = lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    gaps = [(s, e) for s, e in gaps if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(nm, s, s + d) for nm, s, d in tr.host if nm in span_names]
    out = []
    for s, e in gaps[:n]:
        cover = [(min(e, se) - max(s, ss), se - ss, nm)
                 for nm, ss, se in spans if min(e, se) > max(s, ss)]
        half = [c for c in cover if 2 * c[0] >= e - s]
        if half:
            name = min(half, key=lambda c: c[1])[2]
        elif cover:
            name = max(cover)[2]
        else:
            name = "(no span)"
        out.append((name, (e - s) / 1e9))
    return out

