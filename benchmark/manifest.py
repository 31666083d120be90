"""``BENCHMARK.json`` read and checked against itself. ``run.py`` checks
before it touches a device, and the tests check before any chip time: a
name the driver cannot read refused PR 22 before a single run."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def line_ok(s, limit=200) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= limit
            and "\n" not in s and "\t" not in s)


def problems(m: dict, root: str | None = None) -> list:
    """Every way in which the manifest breaks its contract; empty if none.
    With ``root``, also that every file a cell needs is there."""
    bad = []
    say = bad.append
    if set(m) != KEYS:
        say(f"top-level keys {sorted(m)} are not exactly {sorted(KEYS)}")
        return bad
    if not (1 <= len(m["paths"]) <= 16 and all(
            PATH.match(p) and not p.startswith("/") and ".." not in p
            for p in m["paths"])):
        say("paths: 1 to 16 relative directories")
    if not (1 <= len(m["command"]) <= 32 and all(map(line_ok, m["command"]))):
        say("command: 1 to 32 strings of 1 to 200 characters")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        say("run_seconds: a whole number from 1 to 51")

    configs = {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            say(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]) or c["name"] in configs:
            say(f"config name {c['name']!r}")
        if not (line_ok(c["source"]) and line_ok(c["why"])):
            say(f"config {c['name']}: source and why, one line of 1 to 200")
        if not (len(c["reduced"]) <= 16 and all(
                NAME.match(k) for k in c["reduced"])):
            say(f"config {c['name']}: reduced")
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            say(f"config {c['name']}: file {c['file']} not under paths")
        configs[c["name"]] = c
    if not 1 <= len(configs) <= 24:
        say("configs: 1 to 24")
    if len({c["file"] for c in configs.values()}) != len(configs):
        say("two configurations share a file")

    cells, pairs = {}, set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            say(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for key in ("name", "config", "traffic"):
            if not NAME.match(str(w[key])):
                say(f"workload {w['name']}: {key} {w[key]!r}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            say(f"workload {w['name']}: name or pair repeated")
        if w["config"] not in configs:
            say(f"workload {w['name']}: no configuration {w['config']!r}")
        if w["chips"] not in (1, 4):
            say(f"workload {w['name']}: chips {w['chips']!r}")
        if not line_ok(w["why"]):
            say(f"workload {w['name']}: why, one line of 1 to 200")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
    if not 1 <= len(cells) <= 24:
        say("workloads: 1 to 24")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        say(f"{four} cells of {len(cells)} ask for 4 chips")
    unused = set(configs) - {w["config"] for w in cells.values()}
    if unused:
        say(f"configurations no cell uses: {sorted(unused)}")

    def metric(entry, keys, kind):
        name = entry.get("name")
        allowed = keys | {"workloads"}
        if not (keys <= set(entry) <= allowed):
            say(f"{kind} metric {name}: keys {sorted(entry)}")
            return False
        if not NAME.match(str(name)):
            say(f"{kind} metric name {name!r}")
        if not UNIT.match(str(entry["unit"])):
            say(f"{kind} metric {name}: unit {entry['unit']!r}")
        if entry["better"] not in ("lower", "higher"):
            say(f"{kind} metric {name}: better {entry['better']!r}")
        if entry["source"] not in SOURCES:
            say(f"{kind} metric {name}: source {entry['source']!r}")
        for cell in entry.get("workloads", []):
            if cell not in cells:
                say(f"{kind} metric {name}: no cell {cell!r}")
        return True

    e2e = {}
    for e in m["end_to_end"]:
        if not metric(e, {"name", "unit", "better", "bound", "source"},
                      "end_to_end"):
            continue
        if e["source"] not in ("host_clock", "device_trace"):
            say(f"end_to_end metric {e['name']}: source {e['source']!r}")
        if not (isinstance(e["bound"], (int, float))
                and 0.01 <= e["bound"] <= 0.1):
            say(f"end_to_end metric {e['name']}: bound {e['bound']!r}")
        if e["name"] in e2e:
            say(f"metric name {e['name']!r} twice")
        e2e[e["name"]] = e
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        say("end_to_end: setup_s must be there, in every cell")
    if not 1 <= len(e2e) <= 16:
        say("end_to_end: 1 to 16 metrics")
    reports = {cell: {n for n, e in e2e.items()
                      if cell in e.get("workloads", cells)} for cell in cells}
    for cell, names in reports.items():
        if len(names - {"setup_s"}) < 1:
            say(f"cell {cell}: no end-to-end metric besides setup_s")

    per_layer, layered = {}, set()
    for p in m["per_layer"]:
        if not metric(p, {"name", "unit", "better", "source", "layer",
                          "moves"}, "per_layer"):
            continue
        if p["name"] in per_layer or p["name"] in e2e:
            say(f"metric name {p['name']!r} twice")
        if not NAME.match(str(p["layer"])):
            say(f"per_layer metric {p['name']}: layer {p['layer']!r} must be "
                "1 to 64 of letters, digits, '_', '.', '-'")
        if p["moves"] not in e2e:
            say(f"per_layer metric {p['name']}: moves {p['moves']!r}")
            continue
        for cell in p.get("workloads", cells):
            if cell in cells and p["moves"] not in reports[cell]:
                say(f"per_layer metric {p['name']}: cell {cell} does not "
                    f"report {p['moves']}")
            layered.add(cell)
        per_layer[p["name"]] = p
    if not 1 <= len(per_layer) <= 128:
        say("per_layer: 1 to 128 metrics")
    for cell in set(cells) - layered:
        say(f"cell {cell}: no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        say("the manifest is over 64 KiB")

    if root is not None:
        for c in configs.values():
            if not os.path.isfile(os.path.join(root, c["file"])):
                say(f"config {c['name']}: {c['file']} is missing")
        for w in cells.values():
            for rel in (f"benchmark/traffic/{w['traffic']}.json",
                        f"benchmark/limits/{w['name']}.json"):
                if not os.path.isfile(os.path.join(root, rel)):
                    say(f"cell {w['name']}: {rel} is missing")
        for name in per_layer:
            rel = f"benchmark/metrics/{name.replace('.', '_')}.py"
            if not os.path.isfile(os.path.join(root, rel)):
                say(f"per_layer metric {name}: {rel} is missing")
    return bad


def cell(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r}; cells: "
                   f"{[w['name'] for w in m['workloads']]}")


def config_entry(m: dict, name: str) -> dict:
    return next(c for c in m["configs"] if c["name"] == name)


def metrics_of(m: dict, kind: str, cell_name: str) -> list:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) that the
    cell reports."""
    return [e for e in m[kind]
            if cell_name in e.get("workloads", [cell_name])]
