"""The benchmark's reductions: trace -> device numbers, and the least
bytes of a fleet step. CPU only; loads no accelerator library."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import cost, trace  # noqa: E402
from harness.runner import Readings  # noqa: E402

DEV = "/device:TPU:0"


def small_trace():
    """Two step programs with overlapping ops, a plan program, and host
    spans: window [0, 100), meter span over the first gap."""
    return trace.Trace(
        ops={DEV: [("fusion.1", 10, 20), ("fusion.2", 25, 10),
                   ("sort.3", 60, 10), ("fusion.1", 90, 20)]},
        modules={DEV: [("jit_step(7)", 10, 25), ("jit_step(7)", 60, 10),
                       ("jit__plan_jit(3)", 90, 20)]},
        host=[("bench.window", 0, 100), ("meter.record_update", 35, 20),
              ("router.route", 72, 10), ("jit_step", 0, 1)])


def test_union_merges_overlaps_and_clips():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_ns([(0, 10), (5, 15)], lo=3, hi=12) == 9
    assert trace.union_ns([(5, 5), (7, 6)]) == 0


def test_busy_is_the_union_of_ops_in_the_window():
    tr = small_trace()
    lo, hi = trace.window(tr, "bench.window")
    assert (lo, hi) == (0, 100)
    # [10,35) + [60,70) + [90,100) clipped at the window's end
    assert trace.busy_ns(tr, DEV, lo, hi) == 25 + 10 + 10


def test_program_selection_by_name_prefix_and_start():
    tr = small_trace()
    assert trace.program_ns(tr, DEV, "jit_step", 0, 100) == (35, 2)
    assert trace.program_ns(tr, DEV, "jit__plan_jit", 0, 100) == (20, 1)
    assert trace.program_ns(tr, DEV, "jit_step", 50, 100) == (10, 1)


def test_top_ops_sum_by_name():
    tr = small_trace()
    top = trace.top_ops(tr, DEV, 0, 100)
    assert top[0] == ("fusion.1", 40 / 1e9)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2", "sort.3"]


def test_idle_gaps_named_by_the_covering_span():
    tr = small_trace()
    gaps = trace.idle_gaps(tr, DEV, 0, 100,
                           ["meter.record_update", "router.route"])
    # gaps: [0,10) none, [35,60) meter covers 20, [70,90) route covers 10
    assert gaps[0] == ("meter.record_update", 25 / 1e9)
    assert gaps[1] == ("router.route", 20 / 1e9)
    assert gaps[2] == ("(no span)", 10 / 1e9)


def test_readings_per_unit_and_idle_share():
    tr = small_trace()
    rd = Readings(cell={}, shapes={}, units=2, spans={}, counters={},
                  trace=tr, window_ns=(0, 100), device=DEV)
    assert rd.program_ms("jit_step") == 35 / 2 / 1e6
    assert rd.busy_ns() == 45


def test_op_names_drop_operands():
    assert trace.op_name("%fusion.6 = f32[8]{0:T(1024)} fusion(f32[8] %a), "
                         "kind=kCustom") == "fusion.6 (fusion)"
    assert trace.op_name("%copy-start.6 = (s32[8]{0:T(8,128)}, u32[]{:S(2)})"
                         " copy-start(s32[8] %x)") == "copy-start.6 (copy-start)"
    assert trace.op_name("no hlo here") == "no hlo here"


FIXTURE = os.path.join(HERE, "fixtures", "exact_dense_trace.json")


def test_recorded_trace_reduces():
    """A slice of a traced exact_dense window recorded on a TPU v5e chip:
    every step program event lies inside the window, and the device is
    busy for at least the step programs' time."""
    with open(FIXTURE) as f:
        rec = json.load(f)
    tr = trace.Trace.from_json(rec["trace"])
    lo, hi = trace.window(tr, "bench.window")
    dev = tr.devices[0]
    total, count = trace.program_ns(tr, dev, "jit_step", lo, hi)
    assert count == rec["expect"]["steps"]
    busy = trace.busy_ns(tr, dev, lo, hi)
    assert busy >= total * 0.99
    assert busy <= hi - lo
    gaps = trace.idle_gaps(tr, dev, lo, hi, rec["expect"]["span_names"])
    assert [g[0] for g in gaps[:1]] == rec["expect"]["longest_gap"]
    assert trace.top_ops(tr, dev, lo, hi)[0][1] > 0


def test_exact_step_bytes_by_hand():
    # 2 tenants, K = 4, W = 8: reservoir (2*4*8 + 2*4) read and written,
    # chunk 2*8*8 read, mask 2*8 and evicted 2*4*4 written
    assert cost.step_bytes("exact", 2, 4, 8) == \
        2 * (64 + 8) + 128 + 16 + 32
    m, k, w = 8192, 1024, 1024
    assert cost.step_bytes("exact", m, k, w) == 243_335_168


def test_logmem_step_bytes_by_hand():
    assert cost.step_bytes("logmem", 64, 65536, 32768) == 64 * 32768 * 9


def test_unknown_engine_and_device_are_errors():
    with pytest.raises(ValueError):
        cost.step_bytes("other", 1, 1, 1)
    with pytest.raises(KeyError):
        cost.peaks("no such chip")
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
