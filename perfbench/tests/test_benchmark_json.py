"""BENCHMARK.json against its contract, as far as a file can be checked: every
file it names exists, every name and unit uses the allowed characters, every
metric has its reader, and nothing is listed that no cell reports."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(line(w) for w in BENCH["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["reduced"] == c["reduced"] and all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(ROOT, "perfbench", "reference", doc["family"] + ".py"))
        assert any(w["config"] == c["name"] for w in BENCH["workloads"]), "a configuration without a cell"
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and line(w["why"])
        with open(os.path.join(ROOT, "perfbench", "workloads", w["name"] + ".json")) as f:
            doc = json.load(f)
        assert doc["config"] == w["config"] and doc["chips"] == w["chips"] and doc["why"] == w["why"]
        assert os.path.exists(os.path.join(ROOT, "perfbench", "kinds", doc["kind"] + ".py"))


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)

    def where(m):
        return set(m.get("workloads", cells))

    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(ROOT, "perfbench", "end_to_end", m["name"] + ".py"))
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e and m["moves"] != "setup_s"
        assert where(m) <= where(e2e[m["moves"]]), f"{m['name']} is reported where {m['moves']} is not"
        assert os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", m["name"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert where(m) <= cells and where(m)
    for c in cells:
        assert any(c in where(m) for m in BENCH["end_to_end"] if m["name"] != "setup_s")
        assert any(c in where(m) for m in BENCH["per_layer"])


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in BENCH["per_layer"]:
        assert f"| {m['layer']} |" in perf, f"layer {m['layer']!r} is not a row of PERF.md's list of layers"
