"""One run of one cell: check the device, set up, measure the window,
read the trace, compare with the reference, and build the result line."""
from __future__ import annotations

import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from . import registry, spans as sp, trace as trace_mod


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Readings:
    """What a per-layer metric reader may read."""
    cell: dict
    shapes: dict
    units: int  # chunks or requests completed in the traced window
    spans: Dict[str, List[float]]
    counters: Dict[str, float]
    trace: Optional[trace_mod.Trace] = None
    window_ns: Optional[tuple] = None
    device: Optional[str] = None  # the trace's plane of the chip used
    device_kind: str = ""

    def busy_ns(self) -> Optional[float]:
        if self.trace is None or self.window_ns is None or not self.device:
            return None
        return trace_mod.busy_ns(self.trace, self.device, *self.window_ns)

    def program_ms(self, prefix: str) -> Optional[float]:
        """Device ms per unit of the programs named ``prefix...``."""
        if self.trace is None or self.window_ns is None or not self.device:
            return None
        total, count = trace_mod.program_ns(self.trace, self.device, prefix,
                                            *self.window_ns)
        if not count or not self.units:
            return None
        return total / self.units / 1e6


@dataclass
class Ctx:
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    spans: Optional[sp.Spans] = None
    trace_dir: Optional[str] = None
    lowered_in_window: int = 0
    _lowered: List[int] = field(default_factory=lambda: [0])

    @contextmanager
    def window(self):
        import jax
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        before = self._lowered[0]
        try:
            if self.spans:
                with self.spans.span(sp.WINDOW):
                    yield
            else:
                yield
        finally:
            self.lowered_in_window = self._lowered[0] - before
            if self.trace:
                jax.profiler.stop_trace()


def _count_lowerings(counter: List[int]) -> None:
    import jax

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            counter[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        rehearse: bool = False, root: str = registry.ROOT,
        use_cache: bool = True, t_start: Optional[float] = None,
        log=None) -> dict:
    """The result line of one run (a dict); raises ``NoChip``."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    reg = registry.Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    limits = reg.limits(workload)
    if rehearse:
        cfg, traffic = registry.rehearsal(cfg), registry.rehearsal(traffic)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
    if len(devices) < int(cell["chips"]):
        raise NoChip(f"the cell needs {cell['chips']} chips, JAX found "
                     f"{len(devices)}")
    if use_cache:
        from repro.core import jaxcompat
        jaxcompat.compile_cache(root)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    kind = reg.kind(traffic["kind"])
    with tempfile.TemporaryDirectory(prefix="tpubench-trace-") as tdir:
        ctx = Ctx(cell=cell, cfg=cfg, traffic=traffic, seed=int(seed),
                  seconds=float(seconds), trace=bool(trace), t_start=t_start,
                  spans=sp.Spans(annotate=True) if trace else None,
                  trace_dir=tdir)
        _count_lowerings(ctx._lowered)
        out = kind.run(ctx)
        peak = 0
        for d in devices[:int(cell["chips"])]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        tr = None
        if trace:
            tr = trace_mod.load(tdir, host_names=set(sp.PROGRAM)
                                | set(ctx.spans.times))
    log(f"{workload}: seed {seed}, setup {out.setup_s:.3f} s, "
        f"{out.attempted} in {out.window_s:.3f} s, source/schedule late "
        f"{out.late_s:.3f} s, programs lowered in the window "
        f"{ctx.lowered_in_window}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed}
    if not trace:
        metrics = {m["name"]: {"value": float(
            reg.end_to_end_reader(m["name"])(out)), "unit": m["unit"]}
                   for m in reg.end_to_end(workload)}
    else:
        rd = _readings(cell, out, ctx, tr, dev)
        metrics = {}
        for m in reg.per_layer(workload):
            v = reg.reader(m["name"])(rd)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if rd.busy_ns() is not None:
            lo, hi = rd.window_ns
            device["busy_s"] = rd.busy_ns() / 1e9
            device["window_s"] = (hi - lo) / 1e9
            names = sorted((set(sp.PROGRAM) | set(rd.spans)) - {sp.WINDOW})
            result["breakdown"] = {
                "device_ops": [list(x) for x in trace_mod.top_ops(
                    tr, rd.device, lo, hi)],
                "idle_gaps": [list(x) for x in trace_mod.idle_gaps(
                    tr, rd.device, lo, hi, names)]}
    result["metrics"] = metrics
    result["device"] = device
    result["lowered_in_window"] = ctx.lowered_in_window
    result["late_s"] = out.late_s

    ref = kind.reference(out.inputs, kind.PRECISION)
    gaps = kind.gaps(ref, out.got, out.inputs)
    checks = {name: {"value": gaps[name], "limit": float(limits[name])}
              for name in sorted(gaps)}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result


def _readings(cell, out, ctx, tr, dev) -> Readings:
    rd = Readings(cell=cell, shapes=out.shapes, units=out.attempted,
                  spans=dict(ctx.spans.times),
                  counters=dict(ctx.spans.counters), trace=tr,
                  device_kind=dev.device_kind)
    if tr is not None and tr.devices:
        rd.device = tr.devices[0]
        rd.window_ns = trace_mod.window(tr, sp.WINDOW)
    return rd
