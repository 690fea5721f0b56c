"""The harness finds every part of a cell by name: a new configuration,
traffic mix, traffic kind, popularity law, end-to-end and per-layer
metric and limits file are picked up from their files and BENCHMARK.json
entries, with no edit to an existing file."""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import registry  # noqa: E402
from harness.runner import Readings  # noqa: E402


def test_benchmark_json_names_resolve():
    reg = registry.Registry(ROOT)
    for name in reg.cell_names():
        cell = reg.cell(name)
        cfg = reg.config(cell["config"])
        tr = reg.traffic(cell["traffic"])
        kind = reg.kind(tr["kind"])
        for part in ("PRECISION", "inputs", "run", "reference", "gaps"):
            assert hasattr(kind, part), (tr["kind"], part)
        assert set(reg.limits(name))
        assert "rehearse" in cfg and "rehearse" in tr
        assert any(m["name"] == "setup_s" for m in reg.end_to_end(name))
        assert len(reg.end_to_end(name)) >= 2
        assert reg.per_layer(name)
        for m in reg.per_layer(name):
            assert callable(reg.reader(m["name"]))
        for m in reg.end_to_end(name):
            assert callable(reg.end_to_end_reader(m["name"]))


def test_new_parts_are_found_without_editing(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # a new deployment, mix, metric and cell: files and entries only
    cfg = json.load(open(root / "tpubench/configs/fleet_exact_k1024.json"))
    cfg.update(name="fleet_exact_k256", k=256)
    (root / "tpubench/configs/fleet_exact_k256.json").write_text(
        json.dumps(cfg))
    tr = json.load(open(root / "tpubench/traffic/dense_chunks.json"))
    tr["docs_per_tenant"] = 256
    (root / "tpubench/traffic/dense_narrow.json").write_text(json.dumps(tr))
    (root / "tpubench/metrics/units_seen.py").write_text(
        "def read(rd):\n    return float(rd.units)\n")
    (root / "tpubench/limits/exact_k256_narrow.json").write_text(
        json.dumps({"limits": {"survivor_mismatch": 0,
                               "meter_mismatch": 0}}))
    spec["configs"].append({"name": "fleet_exact_k256", "source": "x",
                            "file": "tpubench/configs/fleet_exact_k256.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "exact_k256_narrow",
                              "config": "fleet_exact_k256",
                              "traffic": "dense_narrow", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "units_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "docs_per_s",
                              "workloads": ["exact_k256_narrow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = registry.Registry(str(root))
    assert "exact_k256_narrow" in reg.cell_names()
    cell = reg.cell("exact_k256_narrow")
    assert reg.config(cell["config"])["k"] == 256
    assert reg.traffic(cell["traffic"])["docs_per_tenant"] == 256
    assert reg.limits("exact_k256_narrow")["meter_mismatch"] == 0
    names = [m["name"] for m in reg.per_layer("exact_k256_narrow")]
    assert names == ["units_seen"]
    rd = Readings(cell=cell, shapes={}, units=3, spans={}, counters={})
    assert reg.reader("units_seen")(rd) == 3.0
    # the existing cells still see only their own metrics
    assert "units_seen" not in [m["name"] for m in reg.per_layer(
        "exact_dense")]


def test_rehearsal_overlays_tiny_sizes():
    reg = registry.Registry(ROOT)
    cfg = registry.rehearsal(reg.config("fleet_exact_k1024"))
    assert cfg["tenants"] < 1024 and "rehearse" not in cfg


# a traffic kind no file of the benchmark knows: a few plan requests of a
# fixed size, counted, compared with nothing but their count
NEW_KIND = '''
import time
from harness import common

PRECISION = "float64"


def inputs(cfg, tr, seed, units):
    return {"units": units}


def run(ctx):
    t0 = time.perf_counter()
    n = int(ctx.traffic["requests"])
    return common.Outcome(setup_s=t0 - ctx.t_start, window_s=1.0,
                          latencies_s=[0.001] * n, attempted=n, failed=0,
                          inputs=inputs(ctx.cfg, ctx.traffic, ctx.seed, n),
                          got={"units": n})


def reference(inp, precision):
    return {"units": inp["units"]}


def gaps(ref, got, inp):
    return {"unit_gap": float(abs(ref["units"] - got["units"]))}
'''


def test_new_kind_law_and_metric_need_no_edit(tmp_path):
    """A cell of a new traffic kind with a new end-to-end metric, and a
    routed cell with a new popularity law, both rehearsed through the
    runner from a checkout that only adds files and entries."""
    from harness import runner
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = root / "tpubench"
    (bench / "kinds/counted.py").write_text(NEW_KIND)
    (bench / "traffic/counted_small.json").write_text(json.dumps(
        {"kind": "counted", "requests": 5, "rehearse": {}}))
    (bench / "limits/counted_cell.json").write_text(json.dumps(
        {"limits": {"unit_gap": 0}}))
    (bench / "end_to_end/requests_done.py").write_text(
        "def read(out):\n    return float(out.attempted)\n")
    (bench / "popularity/first_tenant_half.py").write_text(
        "import numpy as np\n\n\n"
        "def draw(g, m, size):\n"
        "    t = g.integers(0, m, size, dtype=np.int32)\n"
        "    return np.where(g.random(size) < 0.5, 0, t).astype(np.int32)\n")
    tr = json.load(open(bench / "traffic/routed_uniform.json"))
    tr["popularity"] = "first_tenant_half"
    (bench / "traffic/routed_skewed.json").write_text(json.dumps(tr))
    (bench / "limits/logmem_skewed.json").write_text(
        (bench / "limits/logmem_routed.json").read_text())
    spec["workloads"] += [
        {"name": "counted_cell", "config": "fleet_exact_k1024",
         "traffic": "counted_small", "chips": 1, "why": "x"},
        {"name": "logmem_skewed", "config": "fleet_logmem_case1",
         "traffic": "routed_skewed", "chips": 1, "why": "x"}]
    spec["end_to_end"].append({"name": "requests_done", "unit": "1",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["counted_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    def rehearse(cell):
        return runner.run(cell, 7, 0.5, False, rehearse=True,
                          use_cache=False, root=str(root),
                          log=lambda msg: None)
    line = rehearse("counted_cell")
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["requests_done"]["value"] == 5.0
    assert set(line["metrics"]) == {"requests_done", "setup_s"}
    line = rehearse("logmem_skewed")
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0
    reg = registry.Registry(str(root))
    law = reg.popularity("first_tenant_half")
    import numpy as np
    ids = law(np.random.default_rng(0), 8, 4000)
    assert (ids == 0).mean() > 0.5
