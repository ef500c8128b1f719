"""``trainer.head_rows_ms.train``: the metric file, its entry, and what the
``device_scope`` reader makes of a recorded window of the chip's trace of
the train step with the compacted head (``head_rows_fixture.json``: the
step's ``conditional`` with the operations of the branch that ran, cut from
a ``bert-base-train --trace 1`` run; written by ``xscope.write``)."""
import json
import os

import pytest

from benchmarks.lib import xplane, xscope
from benchmarks.lib.observe import Observed
from benchmarks.readers import device_scope

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.dirname(os.path.dirname(__file__))
ROOT = os.path.dirname(BENCH)
NAME = "trainer.head_rows_ms.train"


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "head_rows_fixture.expected.json")) as f:
        return json.load(f)


def observed_over(fixture, monkeypatch):
    planes = xscope.read(os.path.join(DATA, fixture))
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    return Observed(facts={"trace_steps": 1}, trace=xplane.reduce(planes))


def test_the_file_and_its_entry_agree_and_name_the_programs_scope():
    from deeplearning4j_tpu.models import bert

    spec = spec_of(NAME)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry["name"] == NAME and entry["source"] == "device_trace"
    assert entry["workloads"] == ["bert-base-train"]
    for key in ("unit", "layer", "moves"):
        assert entry[key] == spec[key]
    assert entry["better"] == "lower" and spec["reader"] == "device_scope"
    params = spec["params"]
    assert params["scope"] == "head_rows" == bert.SCOPES[-1]
    # the nine names the accepted files list, and the one nested in lm_head
    nine = spec_of("trainer.head_loss_ms.train")["params"]["innermost_of"]
    assert params["innermost_of"] == nine + ["head_rows"]
    assert set(params["innermost_of"]) <= set(bert.SCOPES)


def test_it_reads_the_compaction_and_the_accepted_files_read_it_in_lm_head(
        expected, monkeypatch):
    obs = observed_over("head_rows_fixture.json", monkeypatch)
    rows_ms = device_scope.read(spec_of(NAME)["params"], obs)
    assert rows_ms == pytest.approx(1e3 * expected["head_rows_s"])
    assert 0 < rows_ms < 3.0
    by = expected["by_scope_s"]
    head_ms = device_scope.read(
        spec_of("trainer.head_loss_ms.train")["params"], obs)
    assert head_ms == pytest.approx(1e3 * (by["lm_head"] + by["loss"]))
    # nested in lm_head: the nine names count the compaction there
    ten = xscope.scope_seconds(
        xscope.traced(), spec_of(NAME)["params"]["innermost_of"])
    assert ten["lm_head"] + ten["head_rows"] == pytest.approx(by["lm_head"])
    assert ten["loss"] == pytest.approx(by["loss"])


def test_the_conditional_is_an_event_around_its_branchs_operations(
        expected):
    """What the trace does with control flow (PERF.md section 7): the
    ``conditional`` is one event with no scope path, as long as the branch
    that ran, whose operations are events of their own. The readers sum
    events, so its time lands under no name on top of the busy time."""
    planes = xscope.read(os.path.join(DATA, "head_rows_fixture.json"))
    events = xplane.device_ops(planes)["/device:TPU:0"]
    assert len(events) == expected["events"]
    (name, start, dur, path), = [e for e in events
                                 if e[0].startswith("conditional")]
    assert path == "" and dur / 1e9 == pytest.approx(
        expected["conditional_s"])
    inside = [e for e in events if start < e[1] and e[1] + e[2] <= start + dur]
    assert len(inside) > 30
    assert all("/cond/branch_1_fun/" in e[3] or e[3] == "" for e in inside)
    by = xscope.scope_seconds(planes, spec_of(
        "trainer.unscoped_pct.train")["params"]["none_of"])
    busy = xplane.reduce(planes)["busy_s"]
    assert sum(by.values()) == pytest.approx(busy + dur / 1e9, rel=1e-3)
    assert by[None] > dur / 1e9


def test_a_program_without_the_compacted_head_reads_nothing(monkeypatch):
    obs = observed_over("scope_fixture.json", monkeypatch)   # PR 26's trace
    assert device_scope.read(spec_of(NAME)["params"], obs) is None
    assert device_scope.read(spec_of(NAME)["params"], Observed()) is None
