"""A ``--dry-cpu`` run of each one-chip cell at a tiny size: the whole flow
of a run but the look for a chip. Every line is tagged and no device metric
or result line is printed."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_dry_cpu_run(cell):
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 77), "--seconds", "1", "--trace", "1", "--dry-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    out = done.stdout.strip().splitlines()
    assert out and all(line.startswith("[DRY-CPU] ") for line in out)
    assert "correct=True" in out[-1]
    assert not any(line.lstrip().startswith("{") for line in out)
    tagged = [l for l in done.stderr.splitlines() if "compare" in l]
    assert tagged and all(l.startswith("[DRY-CPU] ") for l in tagged)
    for word in ("tokens/s", "samples/s", "_ms", "mfu", "roofline", "setup_s"):
        assert word not in done.stdout
