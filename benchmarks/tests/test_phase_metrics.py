"""The scheduler-phase metrics wait, as files, for the serving cells: the
fixture manifest lists them with the cells that are built and not yet
proven, and ``run.py --tiny --trace 1`` reads every one of them that a
CPU run can (those over the program's spans)."""
import json
import os

import pytest

from benchmarks.tests.test_tiny_runs import BENCH, ROOT, run_tiny

MANIFEST = os.path.join(BENCH, "tests", "data",
                        "manifest_with_phase_metrics.json")


def phase_metrics():
    with open(MANIFEST) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        accepted = {m["name"] for m in json.load(f)["per_layer"]}
    with open(os.path.join(BENCH, "tests", "data",
                           "manifest_with_unproven_cells.json")) as f:
        accepted |= {m["name"] for m in json.load(f)["per_layer"]}
    out = {}
    for m in manifest["per_layer"]:
        if m["name"] in accepted:
            continue
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        for cell in m["workloads"]:
            out.setdefault(cell, []).append((m, spec))
    return out


SOURCE = {"span_stat": "program_span", "span_self": "program_span",
          "idle_under_span": "device_trace", "device_scope": "device_trace"}


def test_the_files_agree_with_the_manifest_and_the_names_exist():
    import inspect
    from deeplearning4j_tpu.models import bert
    from deeplearning4j_tpu.serving import generation

    source = inspect.getsource(generation)
    by_cell = phase_metrics()
    assert sorted(len(v) for v in by_cell.values()) == [8, 15]
    for cell, metrics in by_cell.items():
        for m, spec in metrics:
            assert spec["unit"] == m["unit"] and spec["layer"] == m["layer"]
            assert spec["moves"] == m["moves"]
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
            assert m["source"] == SOURCE[spec["reader"]]
            if spec["reader"] == "device_scope":
                assert spec["params"]["scope"] in bert.SCOPES
                assert spec["params"]["innermost_of"] == list(bert.SCOPES)
            else:
                assert '"' + spec["params"]["span"] + '"' in source


@pytest.mark.parametrize("cell", sorted(phase_metrics()))
def test_tiny_traced_run_reads_every_span_metric(cell):
    line = run_tiny("--workload", cell, "--manifest", MANIFEST, trace=1,
                    seconds=2)
    assert line["rehearsal"]["checks_passed"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m, spec in phase_metrics()[cell]
            if SOURCE[spec["reader"]] == "program_span"}
    assert len(want) == 5
    assert want <= set(line["rehearsal"]["metrics_read"])
