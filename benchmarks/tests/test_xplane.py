import json
import os

import pytest

from benchmarks.lib import xplane
from benchmarks.lib.observe import Observed
from benchmarks.readers import device_idle, device_ops

DATA = os.path.join(os.path.dirname(__file__), "data")


def planes(ops, anchor_trace_ns=1_000.0, anchor_pc_ns=501_000.0):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [xplane.ANCHOR, anchor_trace_ns, 10.0, {"pc_ns": anchor_pc_ns}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0.0, 1e9, {}]]},
            {"name": xplane.OPS_LINE, "events": ops}]},
    ]


def test_busy_is_the_union_and_idle_the_rest():
    ops = [["fusion.1", 0.0, 100.0, {}], ["fusion.2", 50.0, 100.0, {}],
           ["copy.3", 300.0, 100.0, {}]]
    r = xplane.reduce(planes(ops))
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(250e-9)    # 0-150 and 300-400
    obs = Observed(trace=r)
    assert device_idle.read({}, obs) == pytest.approx(37.5)
    assert r["device_ops"][0][1] == pytest.approx(100e-9)
    assert {n for n, _ in r["device_ops"]} == {"fusion.1", "fusion.2",
                                               "copy.3"}
    assert device_ops.read({"pattern": "^fusion", "as": "pct_of_busy"},
                           obs) == pytest.approx(80.0)


def test_gaps_are_labelled_by_what_the_host_was_doing():
    ops = [["a", 0.0, 100.0, {}], ["b", 300.0, 100.0, {}],
           ["c", 1000.0, 100.0, {}]]
    # host clock = trace clock + 500 us; spans are given in host seconds
    spans = [("in_decode_step", (500_000 + 90) / 1e9, (500_000 + 310) / 1e9)]
    r = xplane.reduce(planes(ops), spans)
    assert r["clock_offset_known"]
    assert r["idle_gaps"][0] == ["between_spans", pytest.approx(600e-9)]
    assert r["idle_gaps"][1] == ["in_decode_step", pytest.approx(200e-9)]
    assert r["idle_by_label_s"]["in_decode_step"] == pytest.approx(200e-9)


def test_without_the_anchor_gaps_are_unattributed():
    p = planes([["a", 0.0, 10.0, {}], ["b", 30.0, 10.0, {}]])
    p[0]["lines"][0]["events"] = []
    r = xplane.reduce(p, [("in_prefill", 0.0, 1.0)])
    assert r["idle_gaps"] == [["unattributed", pytest.approx(20e-9)]]


def test_busy_is_averaged_over_devices_and_no_ops_reads_nothing():
    p = planes([["a", 0.0, 100.0, {}]])
    p.append({"name": "/device:TPU:1", "lines": [
        {"name": xplane.OPS_LINE, "events": [["a", 0.0, 50.0, {}]]}]})
    r = xplane.reduce(p)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(75e-9)
    assert xplane.reduce(planes([])) is None
    assert device_idle.read({}, Observed()) is None


def test_recorded_trace_of_the_chip():
    """A window of a real trace taken on the TPU v5e, in the plain form."""
    path = os.path.join(DATA, "trace_fixture.json")
    with open(path) as f:
        recorded = json.load(f)
    r = xplane.reduce(recorded)
    assert r is not None and r["devices"] >= 1
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == 10
    assert sum(s for _, s in r["device_ops"]) <= sum(r["ops_s"].values())
    assert xplane.clock_offset_ns(recorded) is not None
    with open(os.path.join(DATA, "trace_fixture.expected.json")) as f:
        want = json.load(f)
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["device_ops"][0][0] == want["top_op"]
