"""``run.py --tiny`` drives each runner kind end to end on the CPU, and a
cell is added with data files alone."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def run_tiny(*extra, trace=0, seconds=1.5):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--tiny", "--seed",
         str(2**31 + 17), "--seconds", str(seconds), "--trace", str(trace),
         *extra], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


MANIFESTS = (os.path.join(ROOT, "BENCHMARK.json"),
             # the cells that are built and not yet proven on the chip
             os.path.join(BENCH, "tests", "data",
                          "manifest_with_unproven_cells.json"))


def cell_for_each_runner():
    seen = {}
    for path in MANIFESTS:
        with open(path) as f:
            manifest = json.load(f)
        for w in manifest["workloads"]:
            with open(os.path.join(BENCH, "traffic",
                                   w["traffic"] + ".json")) as f:
                kind = (json.load(f)["runner"], w["chips"])
            seen.setdefault(kind, (w["name"], path))
    return sorted(seen.items())


@pytest.mark.parametrize("kind,cell", cell_for_each_runner())
@pytest.mark.parametrize("trace", [0, 1])
def test_each_runner_kind_runs_at_tiny(kind, cell, trace):
    name, manifest = cell
    line = run_tiny("--workload", name, "--manifest", manifest, trace=trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    # a CPU run prints counts only: no metric, never "correct"
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == kind[1]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True
    assert line["rehearsal"]["metrics_read"]


def test_off_the_chip_without_tiny_it_exits_non_zero_and_prints_nothing():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_a_new_cell_is_a_traffic_file_and_an_entry(tmp_path):
    """A later PR adds files and entries and edits nothing that is there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    base = manifest["workloads"][0]
    with open(os.path.join(BENCH, "traffic", base["traffic"] + ".json")) as f:
        mix = json.load(f)
    mix["tiny"] = dict(mix["tiny"], batch=2)
    mix_path = os.path.join(BENCH, "traffic", "tmp-test-mix.json")
    manifest["workloads"].append(dict(base, name="x", traffic="tmp-test-mix"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append("x")
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(manifest))
    try:
        with open(mix_path, "w") as f:
            json.dump(mix, f)
        line = run_tiny("--workload", "x", "--manifest", str(other))
    finally:
        os.remove(mix_path)
    assert line["rehearsal"]["checks_passed"] is True
    assert line["attempted"] > 0


def test_a_sharded_configuration_is_a_file_and_runs_on_four_devices(tmp_path):
    """The train runner takes its mesh from the configuration's deployment:
    a four-chip cell is a configuration file, a cell entry and no code."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for base in manifest["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               base["traffic"] + ".json")) as f:
            if json.load(f)["runner"] == "train":
                break
    entry = next(c for c in manifest["configs"] if c["name"] == base["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["deployment"] = dict(config["deployment"], chips=4,
                                mesh={"data": 2, "model": 2})
    path = tmp_path / "sharded.json"
    path.write_text(json.dumps(config))
    manifest["configs"].append(dict(entry, name="sharded", file=str(path)))
    manifest["workloads"].append(dict(base, name="x4", config="sharded",
                                      chips=4))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append("x4")
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(manifest))
    line = run_tiny("--workload", "x4", "--manifest", str(other), trace=1)
    assert line["device"]["count"] == 4
    assert line["rehearsal"]["checks_passed"] is True   # shards checked too
