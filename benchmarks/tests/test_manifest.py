"""`BENCHMARK.json` against the files it names. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q"""
from __future__ import annotations

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _json(ROOT, "BENCHMARK.json")


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_names_units_and_lines(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    assert len(names) == len(set(names))
    for m in _metrics(manifest):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for group in ("configs", "workloads"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got))
        for e in manifest[group]:
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] \
                and "\t" not in e["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in manifest["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_every_cell_names_files_that_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        wl = _json(HERE, "workloads", w["name"] + ".json")
        for key in ("config", "traffic", "chips", "why"):
            assert wl[key] == w[key], (w["name"], key)
        used.add(w["config"])
        c = configs[w["config"]]
        assert c["file"] == f"benchmarks/configs/{w['config']}.json"
        cfg = _json(ROOT, c["file"])
        assert cfg["source"] == c["source"] and 1 <= len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            HERE, "families", cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(
            HERE, "drivers", wl["driver"] + ".py"))
    assert used == set(configs)


def test_layer_metrics_match_their_files(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    for m in manifest["per_layer"]:
        spec = _json(HERE, "layer_metrics", m["name"] + ".json")
        for key in ("unit", "better", "layer", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
        # the cells the manifest lists are the ones the files give it
        applies = set()
        for cell in cells:
            wl = _json(HERE, "workloads", cell + ".json")
            if wl["driver"] in spec["drivers"] \
                    or m["name"] in wl.get("layer_metrics", ()):
                applies.add(cell)
        assert applies == reported_in(m), m["name"]
        # what it should move is reported in every cell where it is
        assert m["moves"] in e2e
        assert reported_in(m) <= reported_in(e2e[m["moves"]]), m["name"]
    for cell in cells:
        assert sum(cell in reported_in(m) for m in e2e.values()) >= 2
        assert any(cell in reported_in(m) for m in manifest["per_layer"])
