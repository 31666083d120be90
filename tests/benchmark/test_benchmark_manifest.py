"""BENCHMARK.json against itself, before any chip time (a ``layer`` in plain
words refused PR 22), and that a later PR can add a configuration, a cell
and a per-layer metric as new files and new entries only."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def m():
    return manifest.load(ROOT)


def test_manifest_passes_its_self_check(m):
    assert manifest.problems(m, ROOT) == []


def test_every_name_is_of_the_drivers_alphabet(m):
    names = [w[k] for w in m["workloads"] for k in ("name", "config", "traffic")]
    names += [c["name"] for c in m["configs"]]
    names += [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    names += [p["layer"] for p in m["per_layer"]]
    for n in names:
        assert manifest.NAME.match(n), n
    for e in m["end_to_end"] + m["per_layer"]:
        assert manifest.UNIT.match(e["unit"]), e


def _mutations():
    def layer_in_words(m):
        m["per_layer"][0]["layer"] = "train step program"
    def long_unit(m):
        m["end_to_end"][0]["unit"] = "samples per second per chip"
    def moves_nothing(m):
        m["per_layer"][0]["moves"] = "no_such_metric"
    def second_cell(m):
        m["workloads"].append(dict(m["workloads"][0], name="again",
                                   traffic="train_seq1024_b4"))
    def moves_unreported(m):
        # a metric listed for a cell that does not report what it moves
        second_cell(m)
        m["per_layer"][0]["workloads"].append("again")
    def too_many_four_chip(m):
        second_cell(m)          # one cell may always ask for four, two not
        for w in m["workloads"]:
            w["chips"] = 4
    def bound_too_wide(m):
        m["end_to_end"][0]["bound"] = 0.5
    def no_setup(m):
        m["end_to_end"] = [e for e in m["end_to_end"] if e["name"] != "setup_s"]
    def extra_key(m):
        m["per_layer"][0]["why"] = "because"
    def missing_file(m):
        m["workloads"][0]["traffic"] = "no_such_mix"
    def pair_twice(m):
        m["workloads"].append(dict(m["workloads"][0], name="again"))
    return [layer_in_words, long_unit, moves_nothing, moves_unreported,
            too_many_four_chip, bound_too_wide, no_setup, extra_key,
            missing_file, pair_twice]


@pytest.mark.parametrize("mutate", _mutations(), ids=lambda f: f.__name__)
def test_self_check_catches(m, mutate):
    broken = copy.deepcopy(m)
    mutate(broken)
    # files are looked for only where the mutation is about one, so that a
    # mutation's second cell is not caught for lacking a limits file
    root = ROOT if mutate.__name__ == "missing_file" else None
    assert manifest.problems(broken, root) != []


def test_a_later_pr_adds_a_cell_a_config_and_a_metric_as_new_files(tmp_path):
    """In a copy: a new configuration file, a new traffic file, a new limits
    file, a new reader and new entries; no file that was there is edited
    but BENCHMARK.json, which gains entries. The new cell then runs."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    m = manifest.load(ROOT)
    cfg = json.load(open(root / "benchmark/configs/gpt2-small.json"))
    cfg.update(name="gpt2-wide", n_head=4)
    cfg["dry_cpu"]["n_head"] = 4
    (root / "benchmark/configs/gpt2-wide.json").write_text(json.dumps(cfg))
    mix = json.load(open(root / "benchmark/traffic/train_seq1024_b4.json"))
    mix["batch"] = 2
    (root / "benchmark/traffic/train_seq1024_b2.json").write_text(
        json.dumps(mix))
    (root / "benchmark/limits/gpt2w_train_1chip.json").write_text(json.dumps(
        {"loss1_gap": 1e-3, "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-3}))
    (root / "benchmark/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return run.measures.get('steps')\n")
    m["configs"].append({"name": "gpt2-wide", "source": "https://example.org",
                         "file": "benchmark/configs/gpt2-wide.json",
                         "reduced": [], "why": "a test's configuration"})
    m["workloads"].append({"name": "gpt2w_train_1chip", "config": "gpt2-wide",
                           "traffic": "train_seq1024_b2", "chips": 1,
                           "why": "a test's cell"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "resnet50_train_1chip" in e.get("workloads", []):
            e["workloads"].append("gpt2w_train_1chip")
    m["per_layer"].append({"name": "steps_in_window", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry_training",
                           "moves": "train_samples_per_s_chip",
                           "workloads": ["gpt2w_train_1chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(manifest.load(str(root)), str(root)) == []
    env = dict(os.environ, PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload",
         "gpt2w_train_1chip", "--seed", "5", "--seconds", "1", "--dry-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "correct=True" in done.stdout
    after = {p: p.read_bytes() for p in before}
    assert after == before      # nothing that was there was edited


SERVING_METRICS = [     # (name, unit, better, source, layer): readers are kept
    ("serve_compiles_in_window", "count", "lower", "program_counter",
     "entry_serving"),
    ("ttft_p95_ms", "ms", "lower", "host_clock", "entry_serving"),
    ("itl_p95_ms", "ms", "lower", "host_clock", "entry_serving"),
    ("decode_rows_per_step", "rows", "higher", "program_counter",
     "token_scheduler"),
    ("kv_pages_used_share", "%", "higher", "program_counter",
     "token_scheduler"),
    ("decode_step_device_ms", "ms", "lower", "device_trace",
     "decode_programs"),
    ("prefill_device_ms_per_ktok", "ms/ktok", "lower", "device_trace",
     "decode_programs"),
    ("decode_roofline", "%", "higher", "device_trace", "decode_programs"),
    ("serve_step_mfu", "%", "higher", "host_clock", "decode_programs"),
    ("device_idle_share.serve", "%", "lower", "device_trace", "device"),
]


def test_a_later_pr_adds_the_serving_cell_as_entries_and_a_limits_file(
        tmp_path):
    """The serving cell that PERF.md's Open questions lists first: its
    entry, traffic mix, configuration and readers are kept, so a later PR
    adds a limits file and entries to BENCHMARK.json and no code."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    m = manifest.load(ROOT)
    cell = "gpt2s_serve_offline"
    (root / f"benchmark/limits/{cell}.json").write_text(json.dumps(
        {"served_logit_gap": 1e-5, "sample_missing": 0}))
    m["configs"].append({
        "name": "gpt2-small", "file": "benchmark/configs/gpt2-small.json",
        "source": "https://huggingface.co/openai-community/gpt2",
        "reduced": [], "why": "a test's configuration"})
    m["workloads"].append({"name": cell, "config": "gpt2-small",
                           "traffic": "serve_offline", "chips": 1,
                           "why": "a test's cell"})
    m["end_to_end"].append({
        "name": "serve_out_tokens_per_s", "unit": "tokens/s",
        "better": "higher", "bound": 0.1, "source": "host_clock",
        "workloads": [cell]})
    for name, unit, better, source, layer in SERVING_METRICS:
        m["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "serve_out_tokens_per_s",
            "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert manifest.problems(manifest.load(str(root)), str(root)) == []
    done = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload", cell,
         "--seed", str(2**31 + 77), "--seconds", "1", "--trace", "1",
         "--dry-cpu"], cwd=root, env=dict(os.environ, PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "correct=True" in done.stdout
    assert {p: p.read_bytes() for p in before} == before


def test_run_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    paths: another exit code than 0 and no result."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "resnet50_train_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in (done.stdout.strip().splitlines() or [""])[-1]


def test_run_fails_without_a_chip():
    """On the CPU, without --dry-cpu: another exit code than 0, no result."""
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "resnet50_train_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
