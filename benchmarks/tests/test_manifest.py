"""BENCHMARK.json against the contract's rules, and the rule that the
harness is driven by data."""
import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) \
        and 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"]
                                       for w in manifest["workloads"]]


def test_every_metric_moves_an_end_to_end_metric_its_cells_report(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    for m in manifest["per_layer"]:
        assert m["moves"] in end, m
        assert set(cells_of(m, manifest)) <= cells
        assert set(cells_of(m, manifest)) \
            <= set(cells_of(end[m["moves"]], manifest)), m["name"]
    for cell in cells:
        others = [m for m in manifest["end_to_end"] if m["name"] != "setup_s"
                  and cell in cells_of(m, manifest)]
        layers = [m for m in manifest["per_layer"]
                  if cell in cells_of(m, manifest)]
        assert others and layers, cell


def test_everything_named_exists_as_a_file(manifest):
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    configs = {c["name"]: c for c in manifest["configs"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs)
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert os.path.exists(os.path.join(
            BENCH, "references", cfg["reference"] + ".py"))
    for w in manifest["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "lib", "runners", mix["runner"] + ".py"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["unit"] == m["unit"]
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        if "layer" in m:
            assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]


def test_no_cell_configuration_or_metric_name_in_the_code(manifest):
    with open(os.path.join(BENCH, "tests", "data",
                           "manifest_with_unproven_cells.json")) as f:
        unproven = json.load(f)
    names = set()
    for m in (manifest, unproven):
        names |= {e["name"] for group in ("configs", "workloads",
                                          "end_to_end", "per_layer")
                  for e in m[group]}
        names |= {w["traffic"] for w in m["workloads"]}
    code = [p for p in glob.glob(os.path.join(BENCH, "**", "*.py"),
                                 recursive=True)
            if os.sep + "tests" + os.sep not in p]
    assert code
    for path in code:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(r"(?<![\w.\-])" + re.escape(name)
                                 + r"(?![\w.\-])", text), (path, name)
