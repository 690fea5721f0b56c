"""The readers of the program's own spans (``harness/program.py`` and the
``ingest.*`` / ``plan.*`` metrics): per-unit means of the newest spans,
nothing from a program without them, and a number in every cell that
lists them in a traced CPU rehearsal."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import program, registry  # noqa: E402
from harness.runner import Readings  # noqa: E402
from repro.obs import timers  # noqa: E402

REG = registry.Registry(ROOT)
READS_PROGRAM = [m for m in REG.spec["per_layer"]
                 if m["name"].startswith(("ingest.", "plan."))]


def _readings(units):
    return Readings(cell={}, shapes={}, units=units, spans={}, counters={})


def test_span_ms_is_the_mean_of_the_newest_units(monkeypatch):
    durs = {"ingest.meter": [9.0, 9.0, 0.5, 0.25, 0.75]}
    monkeypatch.setattr(timers, "recent",
                        lambda name, n: durs.get(name, [])[-n:])
    # warm-up spans (the 9 s ones) lie before the window's three units
    assert program.span_ms(_readings(3), "ingest.meter") == 500.0
    assert program.span_ms(_readings(3), "ingest.stage") is None
    assert program.span_ms(_readings(0), "ingest.meter") is None


def test_a_program_without_span_tallies_reads_nothing(monkeypatch):
    monkeypatch.delattr(timers, "recent")
    for m in READS_PROGRAM:
        assert REG.reader(m["name"])(_readings(4)) is None, m["name"]


def test_span_ms_reads_real_spans():
    name = "plan.test_reader"
    for _ in range(4):
        with timers.span(name):
            pass
    ms = program.span_ms(_readings(2), name)
    assert ms is not None and 0.0 <= ms < 1e3


def test_program_span_metrics_are_listed_for_their_cells():
    assert {m["name"] for m in READS_PROGRAM} == {
        "ingest.stage_ms", "ingest.wait_ms", "ingest.fetch_ms",
        "ingest.meter_ms", "ingest.route_ms", "plan.pack_ms",
        "plan.solve_ms", "plan.unpack_ms"}
    assert {c for m in READS_PROGRAM for c in m["workloads"]} == {
        "exact_dense", "logmem_routed", "exact_plan_arrivals"}
    for m in READS_PROGRAM:
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["workloads"]
        for cell in m["workloads"]:
            assert any(e["name"] == m["moves"]
                       for e in REG.end_to_end(cell)), (m["name"], cell)


@pytest.mark.parametrize("workload", REG.cell_names())
def test_traced_rehearsal_reads_every_program_span(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(2**31 + 29), "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    mine = [m["name"] for m in READS_PROGRAM if workload in m["workloads"]]
    for name in mine:
        assert name in got and got[name]["value"] >= 0.0, name
    # the program's span nests the benchmark's span around the same call
    for outer, inner in (("ingest.meter_ms", "meter_ms"),
                         ("ingest.route_ms", "route_ms")):
        if outer in got and inner in got:
            assert got[outer]["value"] >= got[inner]["value"]
