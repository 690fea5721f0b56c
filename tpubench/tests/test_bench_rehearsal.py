"""Every registered cell, rehearsed at its tiny sizes on the CPU for a
second and a half: the run prints its result line naming the CPU and
comes out correct. Without a chip and without ``--rehearse`` it prints
no result."""
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

from harness import registry  # noqa: E402

CELLS = registry.Registry(ROOT).cell_names()


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_a_correct_cpu_line(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", str(2**31 + 11), "--seconds", "1.5",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["lowered_in_window"] == 0
    assert "setup_s" in line["metrics"]
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")


def test_no_chip_means_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
