"""The serving bounds of `BENCHMARK.json` against the chip runs they were
set from (`benchmarks/spreads.json`, which also holds the rule's constants)
and the rule of `benchmarks/spreads.py`. No chip, no jax. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q"""
from __future__ import annotations

import json
import math
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import spreads  # noqa: E402

DATA = spreads.load()
SERVING = DATA["rule"]["metrics"]


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_hand(data, metric):
    """The rule, written out again without the module's helpers."""
    rule, by_q, by_r = data["rule"], {}, {}
    for cell, sets in data["cells"].items():
        for s in sets:
            if s["seconds"] != data["window_seconds"]:
                continue
            v = sorted(r["metrics"][metric] for r in s["runs"])
            mid = statistics.median(v)
            v.remove(v[0] if mid - v[0] > v[-1] - mid else v[-1])
            q1, _, q3 = statistics.quantiles(v, n=4)
            mid = statistics.median(v)
            by_q.setdefault((cell, s["machine"]), []).append((q3 - q1) / mid)
            by_r.setdefault((cell, s["machine"]), []).append(
                (v[-1] - v[0]) / mid)

    def widest(by):
        return max(sum(sorted(x)[-2:]) / len(sorted(x)[-2:])
                   for x in by.values())
    need = max(rule["times_quartiles"] * widest(by_q),
               rule["times_range"] * widest(by_r))
    for c in data.get("checks", []):
        if c["metric"] == metric:
            lo, hi = min(c["spreads"]), max(c["spreads"])
            took = lo if hi >= rule["far_off"] * lo else hi
            need = max(need, rule["times_check"] * took / c["median"])
    want = math.ceil(need / rule["step"] - 1e-9) * rule["step"]
    return round(min(rule["cap"], max(rule["floor"], want)), 6)


@pytest.mark.parametrize("metric", SERVING)
def test_bound_is_what_the_rule_gives_from_the_runs(manifest, metric):
    held = {m["name"]: m for m in manifest["end_to_end"]}[metric]
    assert held["bound"] == spreads.bound(DATA, metric) \
        == _by_hand(DATA, metric)
    assert 0.01 <= held["bound"] <= 0.1


def test_the_rule_clears_the_drivers_check():
    """The driver refuses a bound under twice what it reads (by quartiles)
    and over 0.1; whatever the constants become, they stay inside that."""
    rule = DATA["rule"]
    assert rule["times_quartiles"] >= 2 and rule["times_range"] >= 2
    assert rule["times_check"] >= 2 and rule["far_off"] > 1
    for c in DATA.get("checks", []):
        assert c["metric"] in SERVING and c["cell"] in DATA["cells"]
        assert len(c["spreads"]) == 2 and min(c["spreads"]) > 0
    assert 0.01 <= rule["floor"] <= rule["cap"] <= 0.1
    assert 0 < rule["step"] <= 0.01


def test_every_serving_cell_was_measured_at_the_window(manifest):
    assert manifest["run_seconds"] == DATA["window_seconds"]
    serving = set()
    for m in manifest["end_to_end"]:
        if m["name"] in SERVING:
            serving |= set(m["workloads"])
    assert serving and serving == set(DATA["cells"])
    for cell in serving:
        at_window = spreads.sets_at_window(DATA, cell)
        assert sum(len(s["runs"]) for s in at_window) >= 12, cell
        assert len({r["seed"] for s in at_window for r in s["runs"]}) >= 12
        for s in at_window:
            assert len(s["runs"]) == 6 and s["machine"], (cell, s["set"])
            seeds = [r["seed"] for r in s["runs"]]
            assert len(seeds) == len(set(seeds))
            for r in s["runs"]:
                assert set(SERVING) <= set(r["metrics"])


@pytest.mark.parametrize("values, quartiles, span, rest", [
    ([1, 2, 3, 4, 5, 6], (5.25 - 1.75) / 3.5, 5 / 3.5, [2, 3, 4, 5, 6]),
    ([10.0] * 6, 0.0, 0.0, [10.0] * 5),
    ([100, 101, 99, 100, 102, 90], 4.5 / 100, 12 / 100,
     [100, 101, 99, 100, 102]),
])
def test_the_two_measures_and_the_run_left_out(values, quartiles, span, rest):
    assert spreads.quartiles(values) == pytest.approx(quartiles)
    assert spreads.span(values) == pytest.approx(span)
    assert sorted(spreads.without_farthest(values)) == sorted(rest)
    assert spreads.quartiles(rest) <= spreads.span(rest)


def _data(sets, checks=(), **rule):
    """`sets`: [(cell, machine, seconds, values)]; `checks`: [(median,
    the two spreads)] that a check of the driver's read of cell `c`."""
    cells = {}
    for i, (cell, machine, seconds, values) in enumerate(sets):
        cells.setdefault(cell, []).append({
            "set": str(i), "seconds": seconds, "machine": machine, "runs": [
                {"seed": j, "metrics": {"m": v}}
                for j, v in enumerate(values)]})
    read = [{"by": str(i), "cell": "c", "metric": "m", "median": mid,
             "spreads": list(two)} for i, (mid, two) in enumerate(checks)]
    return {"window_seconds": 45, "cells": cells, "checks": read, "rule": dict(
        {"metrics": ["m"], "times_quartiles": 3, "times_range": 2,
         "times_check": 3, "far_off": 4, "too_loose": 8, "step": 0.005,
         "floor": 0.01, "cap": 0.1}, **rule)}


WIDE = [100, 101, 99, 100, 102, 90]     # without 90: quartiles 2 %, range 3 %
NARROW = [1000, 1001, 999, 1000, 1002, 900]     # a tenth of that
FLAT = [7.0] * 6


def test_a_reading_is_the_mean_of_a_cells_two_widest_sets_on_a_machine():
    data = _data([("c", "A", 45, WIDE), ("c", "A", 45, NARROW),
                  ("c", "A", 45, FLAT), ("c", "B", 45, NARROW),
                  ("c", "A", 20, [1, 2, 3, 4, 5, 6]), ("d", "A", 45, FLAT)])
    got = spreads.readings(data, "m", spreads.quartiles)
    assert got == {("c", "A"): pytest.approx(0.011),
                   ("c", "B"): pytest.approx(0.002), ("d", "A"): 0.0}
    assert spreads.readings(data, "m", spreads.span)["c", "A"] \
        == pytest.approx(0.0165)
    # 3 x 1.1 % = 2 x 1.65 % = 3.3 % -> 0.035; the 20 s set is no input
    assert spreads.bound(data, "m") == 0.035
    # the whole sets' quartiles, the far run in: 4.5 and 2.7 %
    assert spreads.too_loose_over(data, "m") == {
        "A": pytest.approx(8 * 0.045), "B": pytest.approx(8 * 0.027)}


@pytest.mark.parametrize("sets, rule, want", [
    ([("c", "A", 45, WIDE)], {}, 0.06),             # 3 x 2 % = 2 x 3 %
    ([("c", "A", 45, WIDE)], {"times_range": 4}, 0.1),      # 12 %: the cap
    ([("c", "A", 45, NARROW)], {}, 0.01),           # 0.6 %: the floor
    ([("c", "A", 45, NARROW)], {"floor": 0.005, "step": 0.001}, 0.006),
    ([("c", "A", 45, FLAT), ("d", "B", 45, WIDE)], {}, 0.06),   # the wider
])
def test_bound_rounds_up_and_keeps_to_floor_and_cap(sets, rule, want):
    assert spreads.bound(_data(sets, **rule), "m") == want


@pytest.mark.parametrize("checks, want", [
    ([(100, (1.0, 2.5))], 0.075),           # alike: 3 x the wider, 2.5 %
    ([(100, (0.5, 2.5))], 0.015),           # five times apart: the narrower
    ([(100, (0.1, 0.2))], 0.01),            # under the sets' reading: theirs
    ([(100, (1.0, 2.5)), (100, (3.0, 2.0))], 0.09),     # the largest line
    ([(100, (5.5, 2.4))], 0.1),             # 16.5 %: the cap
])
def test_a_refusing_checks_reading_raises_the_bound(checks, want):
    assert spreads.bound(_data([("c", "A", 45, NARROW)], checks), "m") == want
    assert _by_hand(_data([("c", "A", 45, NARROW)], checks), "m") == want
