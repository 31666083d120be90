"""``chip_smoke.py`` as the driver runs it: a fresh process, from the root
of the checkout. On this CPU sandbox the real command must refuse to run;
its explicit dry mode walks every phase at a tiny size."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(args, cache_dir):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "DL4J_TPU_PALLAS")}  # one CPU device
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _results(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_refuses_cpu_and_dry_mode_walks_every_phase(tmp_path):
    real = _run([], tmp_path)
    assert real.returncode != 0, real.stdout
    assert "no TPU" in real.stderr
    assert not _results(real.stdout)

    dry = _run(["--dry-cpu"], tmp_path)
    assert dry.returncode == 0, dry.stdout + dry.stderr
    lines = dry.stdout.splitlines()
    assert lines and all(ln.startswith("[DRY-CPU] ") for ln in lines)
    for phase in ("P0 device", "P1 trainer", "P2 server", "P3 kernels",
                  "multichip: not run, 1 device"):
        assert any(phase in ln for ln in lines), phase
    assert not any("FAILED" in ln for ln in lines)
    # a dry run never prints the result line a chip run ends with
    assert not _results(dry.stdout)
    summary = json.loads(
        (REPO / "chiprun_out" / "chip_smoke" / "summary.json").read_text())
    assert summary["device"]["platform"] == "cpu" and not summary["failed"]
    # the cache went where the environment placed it
    assert any(tmp_path.iterdir())
