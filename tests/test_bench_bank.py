"""Banking semantics of bench.py's durable record of accelerator runs
(``_BANK_PATH``): best value per metric, frozen first-ever baselines."""

import json

import bench


def _bank_to(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_BANK_PATH", str(tmp_path / "bank.json"))
    return lambda: json.load(open(bench._BANK_PATH))


def test_bank_keeps_max_by_default(tmp_path, monkeypatch):
    load = _bank_to(tmp_path, monkeypatch)
    bench._bank_record({"metric": "thr", "value": 10.0})
    bench._bank_record({"metric": "thr", "value": 5.0})
    bench._bank_record({"metric": "thr", "value": 12.0})
    d = load()
    assert d["records"]["thr"]["value"] == 12.0
    assert len(d["runs"]) == 3
    # first value ever banked is the frozen vs_baseline denominator
    assert d["baselines"]["thr"] == 10.0


def test_bank_min_direction_keeps_min(tmp_path, monkeypatch):
    load = _bank_to(tmp_path, monkeypatch)
    bench._bank_record({"metric": "step_ms", "value": 120.0,
                        "direction": "min"})
    bench._bank_record({"metric": "step_ms", "value": 90.0,
                        "direction": "min"})
    bench._bank_record({"metric": "step_ms", "value": 200.0,
                        "direction": "min"})
    assert load()["records"]["step_ms"]["value"] == 90.0


def test_bank_direction_inherited_and_persisted(tmp_path, monkeypatch):
    """A caller that forgets direction on a min-metric must not bank a
    regression — neither on the forgetful call nor on any later one."""
    load = _bank_to(tmp_path, monkeypatch)
    bench._bank_record({"metric": "step_ms", "value": 100.0,
                        "direction": "min"})
    bench._bank_record({"metric": "step_ms", "value": 90.0})  # inherits min
    d = load()
    assert d["records"]["step_ms"]["value"] == 90.0
    assert d["records"]["step_ms"]["direction"] == "min"
    bench._bank_record({"metric": "step_ms", "value": 200.0})  # still min
    assert load()["records"]["step_ms"]["value"] == 90.0
