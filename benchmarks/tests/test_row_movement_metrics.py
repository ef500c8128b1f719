"""The routed-expert layer's work by kind (PR 36): the eight metric files over
the three nested names and JAX's ``rematted_computation``, their entries, the
``bytes_floor_share`` reader, and what they make of a recorded window of the
chip's trace of the routed-expert decoder's step
(``row_movement_fixture.json``: the first expert layer's forward and the
last one's replay and backward, cut from a ``smallthinker-ep4-train-8k
--trace 1`` run; written by ``xscope.write``). Entries are found by name,
never by position."""
import json
import os
import re

import pytest

from benchmarks.lib import xplane, xscope
from benchmarks.lib.observe import Observed
from benchmarks.readers import bytes_floor_share, device_scope, roofline_share

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.dirname(os.path.dirname(__file__))
ROOT = os.path.dirname(BENCH)
NESTED = ["rows_moved", "row_index", "gmm"]
DECODERS = ["smallthinker-ep4-train-8k", "nemotron3-super-tp8ep64-train-8k",
            "lfm2-24b-ep8-train-8k"]
# metric -> (its cells, what it reads, better)
NEW = {
    "experts.rows_moved_ms.moe": (DECODERS, "rows_moved", "lower"),
    "experts.row_index_ms.moe": (DECODERS, "row_index", "lower"),
    "experts.gmm_ms.moe": (DECODERS, "gmm", "lower"),
    "experts.gmm_roofline_pct.moe": (DECODERS, "gmm", "higher"),
    "experts.rows_moved_roofline_pct.moe": (DECODERS[:1], "rows_moved",
                                            "higher"),
    "experts.rows_moved_roofline_pct.hybrid": (DECODERS[1:2], "rows_moved",
                                               "higher"),
    "experts.rows_moved_roofline_pct.conv": (DECODERS[2:], "rows_moved",
                                             "higher"),
    "trainer.replay_ms.moe": (DECODERS, "rematted_computation", "lower"),
}
WIDTH_WHERE_MOVED = {"moe": 2560, "hybrid": 1024, "conv": 2048}
FACTS = {"trace_steps": 1, "experts_rows_per_step": 57_000.0,
         "experts_ffn_flops_per_step": 2.0e12,
         "experts_ffn_bytes_per_step": 1.0e9,
         "peak_flops_per_s": 197e12, "peak_hbm_bytes_per_s": 819e9}


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def _planes():
    return xscope.read(os.path.join(DATA, "row_movement_fixture.json"))


def _observed(planes, monkeypatch, **facts):
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    return Observed(facts=dict(FACTS, **facts), trace=xplane.reduce(planes))


def _events(planes):
    return xplane.device_ops(planes)["/device:TPU:0"]


def _ms_where(planes, holds):
    """The plain sum: device ms of the events whose path holds the scope
    ``holds`` as a component of its own (``/gmm/``, never ``jit(gmm)``)."""
    return sum(dur for _n, _s, dur, path in _events(planes)
               if re.search(rf"[/(]{holds}[/)]", path)) / 1e6


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(DATA, "row_movement_fixture.expected.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_entry_has_its_file_and_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    spec = spec_of(name)
    cells, scope, better = NEW[name]
    assert entry["workloads"] == cells
    assert set(cells) <= {w["name"] for w in manifest["workloads"]}
    assert entry["source"] == "device_trace" and entry["better"] == better
    assert entry["moves"] == spec["moves"] == "train_tokens_per_s"
    for key in ("unit", "layer"):
        assert entry[key] == spec[key]
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] not in NEW}
    assert os.path.exists(os.path.join(
        BENCH, "readers", spec["reader"] + ".py"))
    params = spec["params"]
    assert params["scope"] == scope and params["per"] == "trace_steps"
    assert params["innermost_of"] == (
        [scope] if scope == "rematted_computation" else NESTED)


def test_the_nested_names_are_the_programs_and_nested_in_the_three():
    from deeplearning4j_tpu.models import (
        bert, conv_decoder, hybrid_decoder, moe_decoder)

    assert list(moe_decoder.SCOPES[-3:]) == NESTED
    assert not set(NESTED) & set(bert.SCOPES)
    for family in (hybrid_decoder, conv_decoder):
        assert set(NESTED) < set(family.SCOPES)
    outer = r"moe_dispatch|moe_combine|experts"
    held = [p for _n, _s, _d, p in _events(_planes())
            if set(xscope.names_in(p)) & set(NESTED)]
    assert len(held) >= 30
    for path in held:
        names = xscope.names_in(path)
        first = min(names.index(n) for n in NESTED if n in names)
        assert re.fullmatch(outer, names[first - 1]) or any(
            re.fullmatch(outer, n) for n in names[:first]), path


@pytest.mark.parametrize("scope", NESTED)
def test_a_time_file_reads_its_name_and_nothing_else(
        scope, expected, monkeypatch):
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    ms = device_scope.read(spec_of(f"experts.{scope}_ms.moe")["params"], obs)
    assert ms == pytest.approx(_ms_where(planes, scope))
    assert ms == pytest.approx(expected["ms"][scope])
    assert ms > 0.05
    # per traced step
    two = _observed(planes, monkeypatch, trace_steps=2)
    assert device_scope.read(
        spec_of(f"experts.{scope}_ms.moe")["params"], two) \
        == pytest.approx(ms / 2)


def test_the_three_add_up_under_the_accepted_lumps(expected, monkeypatch):
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    read = {s: device_scope.read(
        spec_of(f"experts.{s}_ms.moe")["params"], obs) for s in NESTED}
    route = device_scope.read(spec_of("experts.route_ms.moe")["params"], obs)
    ffn = device_scope.read(spec_of("experts.ffn_ms.moe")["params"], obs)
    assert 0 < read["rows_moved"] + read["row_index"] < route
    assert 0 < read["gmm"] < ffn
    assert route == pytest.approx(expected["ms"]["route"])
    assert ffn == pytest.approx(expected["ms"]["ffn"])
    # the kernels are under the name, the activation between them is not
    kernels = [(d, p) for n, _s, d, p in _events(planes)
               if re.match(r"t?gmm(\.\d+)? custom-call$", n)]
    assert 0 < sum(d for d, _ in kernels) / 1e6 <= read["gmm"]
    assert all("/gmm/" in p for _d, p in kernels)


def _without_the_nested_names(planes):
    """The same trace as the parent's program would name it."""
    out = json.loads(json.dumps(planes))
    for plane in out:
        for line in plane["lines"]:
            for event in line["events"]:
                event[3] = re.sub(r"/(rows_moved|row_index|gmm)(?=/)", "",
                                  event[3])
    return out


@pytest.mark.parametrize("name", [
    "experts.route_ms.moe", "experts.ffn_ms.moe", "trainer.unscoped_pct.moe",
    "experts.ffn_roofline_pct.moe", "experts.route_ms.hybrid",
    "experts.ffn_ms.conv"])
def test_an_accepted_file_reads_the_same_with_and_without_the_nested_names(
        name, monkeypatch):
    spec = spec_of(name)
    listed = spec["params"].get("innermost_of") \
        or spec["params"]["none_of"]
    assert not set(listed) & set(NESTED)
    reader = roofline_share if spec["reader"] == "roofline_share" \
        else device_scope
    planes = _planes()
    with_names = reader.read(spec["params"], _observed(planes, monkeypatch))
    bare = _without_the_nested_names(planes)
    assert not any(set(xscope.names_in(p)) & set(NESTED[:2])
                   or "/gmm/" in p for _n, _s, _d, p in _events(bare))
    without = reader.read(spec["params"], _observed(bare, monkeypatch))
    assert with_names is not None and with_names == without


def test_the_parents_program_reads_none_of_the_row_movement(monkeypatch):
    """Without the names (the parent, with this PR's files laid over it)
    the files of ``rows_moved`` and ``row_index`` leave their metrics out.
    ``gmm`` also stands in the library's own ``jit(gmm)``, so the parent
    reads the forward product there and not ``tgmm``: a part, which this
    PR's readings are not compared with."""
    bare = _without_the_nested_names(_planes())
    obs = _observed(bare, monkeypatch)
    for name in NEW:
        if "rows_moved" in name or "row_index" in name:
            spec = spec_of(name)
            reader = bytes_floor_share if "roofline" in name \
                else device_scope
            assert reader.read(spec["params"], obs) is None
    part = device_scope.read(spec_of("experts.gmm_ms.moe")["params"], obs)
    whole = device_scope.read(spec_of("experts.gmm_ms.moe")["params"],
                              _observed(_planes(), monkeypatch))
    assert part is None or 0 < part < whole
    old = xscope.read(os.path.join(DATA, "scope_fixture.json"))  # PR 26's
    for name in NEW:
        spec = spec_of(name)
        assert device_scope.read(
            dict(spec["params"], **{"as": "ms_per_unit"}),
            _observed(old, monkeypatch)) is None


@pytest.mark.parametrize("suffix", sorted(WIDTH_WHERE_MOVED))
def test_the_floor_is_rows_times_bytes_over_peak_over_the_scopes_time(
        suffix, monkeypatch):
    spec = spec_of(f"experts.rows_moved_roofline_pct.{suffix}")
    assert spec["reader"] == "bytes_floor_share" and spec["unit"] == "%"
    params = spec["params"]
    # four movements a step, each one read and one write in bfloat16
    assert params["bytes_per_row"] == 4 * 2 * 2 * WIDTH_WHERE_MOVED[suffix]
    assert params["rows"] == "experts_rows_per_step"
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    share = bytes_floor_share.read(params, obs)
    least_s = FACTS["experts_rows_per_step"] * params["bytes_per_row"] \
        / FACTS["peak_hbm_bytes_per_s"]
    assert share == pytest.approx(
        100.0 * least_s / (_ms_where(planes, "rows_moved") / 1e3))
    assert 0 < share < 100
    twice = _observed(planes, monkeypatch,
                      experts_rows_per_step=2 * FACTS["experts_rows_per_step"])
    assert bytes_floor_share.read(params, twice) == pytest.approx(2 * share)


@pytest.mark.parametrize("missing", [
    "experts_rows_per_step", "peak_hbm_bytes_per_s", "trace_steps"])
def test_the_floor_reads_nothing_without_its_facts(missing, monkeypatch):
    params = spec_of("experts.rows_moved_roofline_pct.moe")["params"]
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    del obs.facts[missing]
    assert bytes_floor_share.read(params, obs) is None
    assert bytes_floor_share.read(params, Observed(facts=dict(FACTS))) is None
    assert bytes_floor_share.read(params, Observed()) is None


def test_the_products_roofline_is_the_regions_work_over_the_products_time(
        monkeypatch):
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    spec = spec_of("experts.gmm_roofline_pct.moe")
    whole = spec_of("experts.ffn_roofline_pct.moe")
    for key in ("ops", "bytes", "per"):
        assert spec["params"][key] == whole["params"][key]
    share = roofline_share.read(spec["params"], obs)
    least_s = max(FACTS["experts_ffn_flops_per_step"] / 197e12,
                  FACTS["experts_ffn_bytes_per_step"] / 819e9)
    gmm_ms = _ms_where(planes, "gmm")
    assert share == pytest.approx(100.0 * least_s / (gmm_ms / 1e3))
    ffn_share = roofline_share.read(whole["params"], obs)
    ffn_ms = device_scope.read(spec_of("experts.ffn_ms.moe")["params"], obs)
    assert share == pytest.approx(ffn_share * ffn_ms / gmm_ms)
    assert share > ffn_share


def test_the_replay_reads_every_replayed_operation_whatever_its_scope(
        expected, monkeypatch):
    planes = _planes()
    obs = _observed(planes, monkeypatch)
    ms = device_scope.read(spec_of("trainer.replay_ms.moe")["params"], obs)
    replayed = [(d, p) for _n, _s, d, p in _events(planes)
                if "/rematted_computation/" in p]
    assert ms == pytest.approx(sum(d for d, _ in replayed) / 1e6)
    assert ms == pytest.approx(expected["ms"]["rematted_computation"])
    scopes = {xscope.innermost(p, spec_of(
        "experts.route_ms.moe")["params"]["innermost_of"])
        for _d, p in replayed}
    assert {"moe_dispatch", "experts"} <= scopes and len(scopes) >= 3
    # and the replayed dispatch gather counts in both
    both = [p for _d, p in replayed if "/rows_moved/" in p]
    assert both and ms < xplane.reduce(planes)["busy_s"] * 1e3
