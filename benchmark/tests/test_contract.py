"""BENCHMARK.json against the static rules of the benchmark's contract, and
against the files the harness will look for. Seconds; run by hand with the
other tests of this directory."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32 and all(map(line,
                                                        bench["command"]))
    assert 1 <= len(bench["paths"]) <= 16
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit into 43200 s
    cells = 24
    need = ((2 + 14 * cells) * (bench["run_seconds"] + 60)
            + cells * 2 * 90 + 1200)
    assert need <= 43200, need


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")), key
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        ref = os.path.join(os.path.dirname(os.path.join(ROOT, c["file"])),
                           cfg["reference"] + ".py")
        assert os.path.exists(ref), ref


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in bench["configs"]}
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        four += w["chips"] == 4
        for sub, name in (("workloads", w["name"]), ("traffic", w["traffic"])):
            path = os.path.join(ROOT, "benchmark", sub, name + ".json")
            assert os.path.exists(path), path
        extras = json.load(open(os.path.join(
            ROOT, "benchmark", "workloads", w["name"] + ".json")))
        assert extras["limits"], "a cell compares at least one number"
    assert four <= max(1, len(names) // 4)


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    assert len(e2e) == len(bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = [m["name"] for m in bench["per_layer"]]
    assert len(layers) == len(set(layers)) and 1 <= len(layers) <= 128
    assert not set(layers) & set(e2e)
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        reader = os.path.join(ROOT, "benchmark", "metrics",
                              m["name"] + ".py")
        assert os.path.exists(reader), reader
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert any("mfu" in n for n in layers)


def test_files_under_paths_are_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
