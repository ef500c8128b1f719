"""``lib/xscope.py`` and the readers over it, on a recorded window of the
chip's trace of the training step (written by ``xscope.write``) and on a
trace taken here on the CPU."""
import glob
import importlib
import json
import os
import time

import pytest

from benchmarks.lib import xplane, xscope
from benchmarks.lib.observe import Observed
from benchmarks.readers import device_ops, device_scope, idle_under_span

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.dirname(os.path.dirname(__file__))


@pytest.fixture(scope="module")
def recorded():
    return xscope.read(os.path.join(DATA, "scope_fixture.json"))


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "scope_fixture.expected.json")) as f:
        return json.load(f)


@pytest.fixture()
def observed(recorded, monkeypatch):
    """What a traced run hands the readers, with the recorded planes in the
    place of the run's own trace file."""
    monkeypatch.setattr(xscope, "traced", lambda: recorded)
    return Observed(facts={"trace_steps": 1}, trace=xplane.reduce(recorded))


def vocabulary():
    with open(os.path.join(BENCH, "metrics",
                           "trainer.unscoped_pct.train.json")) as f:
        return json.load(f)["params"]["none_of"]


def test_names_in_a_path_and_the_innermost_of_a_vocabulary():
    path = "jit(step)/transpose(jvp(mlp))/dot_general:"
    assert xscope.names_in(path) == ["jit", "step", "transpose", "jvp",
                                     "mlp", "dot_general"]
    assert xscope.innermost(path, {"mlp", "loss"}) == "mlp"
    assert xscope.innermost("jit(step)/loss/mlp/add", {"mlp", "loss"}) \
        == "mlp"
    assert xscope.innermost("jit(step)/lm_loss/add", {"loss"}) is None
    assert xscope.innermost("", {"loss"}) is None


def test_the_scopes_and_the_unscoped_rest_add_up_to_busy(recorded, expected):
    names = vocabulary()
    by_scope = xscope.scope_seconds(recorded, names)
    reduced = xplane.reduce(recorded)
    assert set(by_scope) == set(names) | {None}
    assert sum(by_scope.values()) == pytest.approx(reduced["busy_s"])
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"])
    for name, seconds in by_scope.items():
        assert seconds == pytest.approx(expected["by_scope_s"][str(name)])


def test_every_metric_file_of_the_training_step_reads_the_recorded_trace(
        observed, expected):
    got = {}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("moves") != "train_tokens_per_s":
            if spec["reader"] == "device_scope":
                # a serving program's scope: the train step has none of it
                assert device_scope.read(spec["params"], observed) is None
            continue
        if spec["reader"] == "device_scope" or (
                spec["reader"] == "device_ops"
                and spec["params"].get("per") == "trace_steps"):
            reader = importlib.import_module(
                "benchmarks.readers." + spec["reader"])
            got[os.path.basename(path)[:-5]] = (
                spec, reader.read(spec["params"], observed))
    scoped = {k: v for k, (s, v) in got.items()
              if s["reader"] == "device_scope" and s["unit"] == "ms"}
    share = [v for k, (s, v) in got.items() if s["unit"] == "%"]
    kernels = {k: v for k, (s, v) in got.items()
               if s["reader"] == "device_ops"}
    assert len(scoped) == 3 and len(share) == 1 and len(kernels) == 2
    assert all(v is not None and v > 0 for _, v in got.values())
    by = expected["by_scope_s"]
    assert sorted(scoped.values()) == pytest.approx(sorted(1e3 * x for x in (
        by["lm_head"] + by["loss"], by["optimizer"],
        by["attn_qkv"] + by["attn_out"] + by["mlp"])))
    assert share[0] == pytest.approx(100 * by["None"] / expected["busy_s"])
    # the kernels' names stand in the event names, and the two kernels are
    # all the custom calls there are
    assert sorted(kernels.values()) == pytest.approx(sorted(
        1e3 * x for x in expected["kernels_s"].values()))
    custom = device_ops.read({"pattern": " custom-call$",
                              "as": "ms_per_unit", "per": "trace_steps"},
                             observed)
    assert sum(kernels.values()) == pytest.approx(custom)
    assert sum(kernels.values()) == pytest.approx(1e3 * by["attention"])


def test_device_scope_forms_and_what_it_reads_without_names(
        observed, recorded, monkeypatch):
    names = vocabulary()
    ms = {"as": "ms_per_unit", "per": "trace_steps"}
    whole = device_scope.read(dict(ms, scope="mlp", innermost_of=names),
                              observed)
    assert whole > 0
    # a kernel's name is a component of its operations' paths too, and
    # innermost to the scope the kernel is called in
    assert device_scope.read(
        dict(ms, scope="mha_packed_bwd",
             innermost_of=names + ["mha_packed_bwd"]), observed) > 0
    assert device_scope.read(
        {"scope": "mlp", "innermost_of": names, "as": "pct_of_busy"},
        observed) == pytest.approx(
            100 * whole / 1e3 / observed.trace["busy_s"])
    assert device_scope.read(
        dict(ms, scope="no_such_scope", innermost_of=names), observed) is None
    # a program without the names (the parent of the PR that brought them)
    bare = [dict(p, lines=[dict(l, events=[[e[0], e[1], e[2], ""]
                                           for e in l["events"]])
                           for l in p["lines"]])
            if xplane.DEVICE_PLANE.match(p["name"]) else p for p in recorded]
    monkeypatch.setattr(xscope, "traced", lambda: bare)
    assert device_scope.read({"scope": None, "none_of": names,
                              "as": "pct_of_busy"}, observed) is None
    assert device_scope.read(dict(ms, scope="mlp", innermost_of=names),
                             observed) is None
    # and no traced window at all
    assert device_scope.read(dict(ms, scope="mlp", innermost_of=names),
                             Observed()) is None


def test_idle_under_a_host_span(recorded, expected, observed):
    span = "PjitFunction(jit(step))"
    under_s, window_s = xscope.idle_under(recorded, span)
    assert window_s == pytest.approx(expected["window_s"])
    assert under_s == pytest.approx(expected["idle_under_step_call_s"])
    assert idle_under_span.read({"span": span}, observed) \
        == pytest.approx(100 * under_s / window_s)
    assert idle_under_span.read({"span": "serving.admit"}, observed) is None
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "scheduler", "events": [
            ["serving.admit", 90.0, 120.0, {"step": 1}],
            ["serving.decode.commit", 380.0, 500.0, {"step": 1}]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": xplane.OPS_LINE,
                                             "events": [
            ["a", 0.0, 100.0, ""], ["b", 300.0, 100.0, ""],
            ["c", 900.0, 100.0, ""]]}]}]
    # idle 100-300 and 400-900; admit covers 100-210, commit 400-880
    assert xscope.idle_under(planes, "serving.admit") \
        == pytest.approx((110e-9, 1000e-9))
    assert xscope.idle_under(planes, "serving.decode.commit")[0] \
        == pytest.approx(480e-9)
    assert xscope.host_intervals(planes, "serving.admit") == [(90.0, 210.0)]
    # a span's children are charged apart: the prefill an admission ran
    # (150-190) is no part of the admission's own time
    planes[0]["lines"][0]["events"] += [
        ["serving.prefill", 150.0, 40.0,
         {"step": 1, "parent": "serving.admit"}],
        ["serving.prefill.readback", 160.0, 20.0,
         {"step": 1, "parent": "serving.prefill"}]]
    assert xscope.host_intervals(planes, "serving.admit") \
        == [(90.0, 150.0), (190.0, 210.0)]
    assert xscope.host_intervals(planes, "serving.prefill") \
        == [(150.0, 160.0), (180.0, 190.0)]
    assert xscope.idle_under(planes, "serving.admit")[0] \
        == pytest.approx(70e-9)
    assert sum(xscope.idle_under(planes, name)[0] for name in (
        "serving.admit", "serving.prefill", "serving.prefill.readback")) \
        == pytest.approx(110e-9)


def test_span_self_is_the_span_less_what_names_it_as_parent():
    from benchmarks.readers import span_self, span_stat

    def span(name, start, end, **args):
        return {"name": name, "start": start, "end": end, "args": args}

    obs = Observed(window=(0.0, 1.0), spans=[
        span("serving.admit", 0.010, 0.011, step=1, admitted=0),
        span("serving.admit", 0.100, 0.160, step=2, admitted=1),
        span("serving.prefill", 0.105, 0.155, step=2,
             parent="serving.admit"),
        span("serving.prefill.readback", 0.110, 0.150, step=2,
             parent="serving.prefill"),
        span("serving.admit", 0.300, 0.303, step=3, admitted=0)])
    ask = {"span": "serving.admit", "stat": "median"}
    assert span_self.read(ask, obs) == pytest.approx(3.0)
    assert span_self.read(dict(ask, stat="mean"), obs) \
        == pytest.approx((1.0 + 10.0 + 3.0) / 3)
    assert span_self.read(dict(ask, stat="pct_of_window"), obs) \
        == pytest.approx(1.4)
    assert span_stat.read(dict(ask, value="dur_ms", stat="mean"), obs) \
        == pytest.approx((1.0 + 60.0 + 3.0) / 3)
    assert span_self.read({"span": "serving.prefill", "stat": "mean"}, obs) \
        == pytest.approx(10.0)
    assert span_self.read({"span": "no.such.span", "stat": "mean"}, obs) \
        is None


def test_load_reads_the_spans_of_a_trace_taken_here(tmp_path):
    """The wire-format reader against a real ``.xplane.pb``: the program's
    spans are host-plane events with their arguments, and what ``write``
    keeps ``read`` gives back."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.profiler import OpProfiler

    prof = OpProfiler()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with prof.span("serving.decode_step", engine="e0", live=3, step=7):
            jnp.ones(8).sum().block_until_ready()
            with prof.span("serving.decode.readback", step=7,
                           parent="serving.decode_step"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = xplane.newest_xplane(str(tmp_path))
    planes = xscope.load(path, host_names=r"^serving\.")
    assert [p["name"] for p in planes] == ["/host:CPU"]
    events = {e[0]: e for line in planes[0]["lines"] for e in line["events"]}
    assert set(events) == {"serving.decode_step", "serving.decode.readback"}
    outer, inner = (events["serving.decode_step"],
                    events["serving.decode.readback"])
    assert outer[3] == {"engine": "e0", "live": 3, "step": 7}
    assert inner[3] == {"step": 7, "parent": "serving.decode_step"}
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    assert inner[2] >= 2e6
    # the same events as jax's own reader gives, on the same clock
    theirs = {e.name: e for plane in
              jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("serving.")}
    for name, event in events.items():
        assert event[1] == pytest.approx(theirs[name].start_ns)
        assert event[2] == pytest.approx(theirs[name].duration_ns)
    assert len(xscope.load(path)[0]["lines"]) >= 1
    out = tmp_path / "fixture.json"
    xscope.write(planes, str(out))
    assert xscope.read(str(out)) == planes
    # the intervals of a span by name
    assert xscope.host_intervals(planes, "serving.decode.readback") \
        == [(inner[1], inner[1] + inner[2])]
