"""BENCHMARK.json keeps to its contract, and the harness finds every
configuration, traffic mix, driver, reference, limit and metric by name."""
import json
import math
import re

import pytest

from gnsbench import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    for word in BENCH["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]


def test_every_cell_reports_enough():
    names = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        mine = [m for m in BENCH["end_to_end"]
                if w["name"] in m.get("workloads", names)]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = [m for m in BENCH["per_layer"]
                 if w["name"] in m.get("workloads", names)]
        assert layer
        for m in layer:
            assert w["name"] in e2e[m["moves"]].get("workloads", names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(names) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_check_fits_its_budget_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_found_by_name(name):
    cell = harness.load_cell(name)
    assert callable(cell.driver.setup) and callable(cell.driver.check)
    for fn in harness.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, fn)), fn
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    for entry in cell.per_layer:
        mod = cell.readers[entry["name"]]
        assert (mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT) == (
            entry["layer"], entry["source"], entry["moves"], entry["unit"])
        assert mod.read({}) is None           # nothing to read: nothing


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
        assert cfg[key] != cfg["published"][key]
    for key, value in cfg["published"].items():
        if key != "note" and key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert math.isclose(cfg["cache_fraction"], 0.01)
