"""The delta-rule / gated attention expert decoder's yardstick: its flops
module at the published sizes of the share, the new metric files over a trace
with the program's scope names, the cell at tiny size, and a program without
the family. Cell, configuration and metrics are found by name, so a later
PR's entries do not move them."""
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import flops_delta_decoder as flops
from benchmarks.lib import model, xplane, xscope
from benchmarks.lib.observe import Observed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, CONFIG = "solar-open2-tp8ep40-train-8k", "solar-open2-250b-tp8ep40"
NEW = ("kda.proj_ms.delta", "kda.conv_ms.delta", "kda.scan_ms.delta",
       "kda.scan_roofline_pct.delta", "experts.route_ms.delta",
       "experts.ffn_ms.delta", "experts.ffn_roofline_pct.delta",
       "experts.shared_ms.delta", "experts.rows_moved_roofline_pct.delta",
       "trainer.unscoped_pct.delta")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config():
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def sizes():
    """The cell's sizes as the runner builds them from the file."""
    return model.sizes(config()[1], False)


def test_operations_per_token_from_sizes_alone():
    s = sizes()
    assert flops.attention_layers(s) == 1 and flops.delta_layers(s) == 3
    assert flops.expected_experts_per_token(s) == 0.2
    assert flops.expert_params(s) == 15_728_640
    assert flops.shared_columns(s) == 160
    assert flops.delta_mixer_matrix_params(s) == (
        3 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
        + 1024 * 4096) == 18_120_704
    assert flops.attention_mixer_matrix_params(s) == 13_631_488
    touched = flops.matmul_params_touched(s)
    assert touched == (
        3 * 18_120_704 + 13_631_488            # the mixers' projections
        + 4 * (4096 * 320 + 3 * 4096 * 160 + 0.2 * 15_728_640)
        + 4096 * 24576)                        # the head over the slice
    # the head is about half of the cut's matmul operations
    assert 4096 * 24576 / touched == pytest.approx(0.518, abs=1e-3)
    T = 8192
    assert flops.attention_flops_per_sequence(s, T) \
        == 12.0 * 128 * 8 * (T * (T + 1) // 2)
    assert flops.recurrence_flops_per_token(s) == 21.0 * 8 * 128 * 128
    per_token = flops.train_flops_per_token(s, T)
    # a share's router runs forward only: 2 operations a parameter, not 6
    assert per_token == 6.0 * touched - 4.0 * 4 * 4096 * 320 \
        + 12.0 * 128 * 8 * (T + 1) / 2 + 3 * 21.0 * 8 * 128 * 128
    assert per_token == pytest.approx(1.2037e9, rel=1e-4)
    whole = dict(s, experts_count=320)
    assert flops.train_flops_per_token(whole, T) \
        == 6.0 * flops.matmul_params_touched(whole) + per_token \
        - 6.0 * touched + 4.0 * 4 * 4096 * 320


def test_the_parameter_count_is_the_pytrees():
    import jax
    from deeplearning4j_tpu.models import DeltaDecoderConfig, init_params

    s = sizes()
    cfg = DeltaDecoderConfig(**s)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == flops.parameters(s) == 785_822_360 \
        == config()[1]["deployment"]["parameters"]
    tiny = model.sizes(config()[1], True)
    shapes = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), DeltaDecoderConfig(**tiny)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == flops.parameters(tiny)


def test_kernel_work_follows_the_rows_the_program_counted():
    s = sizes()
    expected = flops.kernels_per_step(s, 1, 8192)
    rows = 4 * 8192 * 0.2
    assert expected["experts_ffn_flops_per_step"] \
        == pytest.approx(6.0 * rows * 3 * 4096 * 1280)
    counted = flops.kernels_per_step(s, 1, 8192, routed_rows=3 * rows)
    assert counted["experts_ffn_flops_per_step"] \
        == pytest.approx(3 * expected["experts_ffn_flops_per_step"])
    assert counted["experts_ffn_bytes_per_step"] \
        > expected["experts_ffn_bytes_per_step"]
    for kernel in ("attn_stream", "kda_scan"):
        for what in ("flops", "bytes"):
            key = f"{kernel}_{what}_per_step"
            assert counted[key] == expected[key] > 0
    assert expected["attn_stream_flops_per_step"] \
        == flops.attention_flops_per_sequence(s, 8192)
    # q, k, v in bfloat16, g in float32 a channel and beta a head read, o
    # written; again with o's cotangent; five gradients written
    assert expected["kda_scan_bytes_per_step"] \
        == 3 * 8192 * 8 * ((12 * 128 + 4) * 2 + 10 * 128 + 4)
    assert expected["kda_scan_flops_per_step"] \
        == 3 * 8192 * 8 * 21 * 128 * 128
    # bound by its bytes on the v5e, and by the experts' weights theirs
    assert expected["kda_scan_bytes_per_step"] / 819e9 \
        > expected["kda_scan_flops_per_step"] / 197e12
    assert expected["experts_ffn_bytes_per_step"] / 819e9 \
        > expected["experts_ffn_flops_per_step"] / 197e12


def test_the_new_metric_files_list_the_programs_vocabulary():
    from deeplearning4j_tpu.models import delta_decoder, moe_decoder

    files = sorted(glob.glob(os.path.join(BENCH, "metrics", "*.delta.json")))
    assert [os.path.basename(p)[:-len(".json")] for p in files] \
        == sorted(NEW)
    listed = {m["name"]: m for m in manifest()["per_layer"]}
    for name in NEW:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        params = spec["params"]
        vocabulary = params.get("innermost_of") or params.get("none_of")
        # the routed-expert layer's three nested names read apart only in
        # the row-movement files; every other file lists the flat names,
        # so that ``route`` and ``ffn`` hold what they hold in the siblings
        nested = list(moe_decoder.SCOPES[-3:])
        if name == "experts.rows_moved_roofline_pct.delta":
            assert vocabulary == nested
            # four moves a step, each read and written once in bfloat16
            assert params["bytes_per_row"] == 16 * sizes()["hidden"]
        else:
            assert vocabulary == [n for n in delta_decoder.SCOPES
                                  if n not in nested]
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] == spec["layer"]
        assert listed[name]["unit"] == spec["unit"]
        assert listed[name]["moves"] == "train_tokens_per_s"
        assert listed[name]["better"] == (
            "higher" if "roofline" in name else "lower")
        if params.get("scope"):
            assert set(params["scope"].split("|")) <= set(
                delta_decoder.SCOPES)


def test_the_cell_joins_the_accepted_metrics_it_reports():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "clm-b1-t8192" and len(cell["why"]) <= 200
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "traffic", "clm-b2-t8192.json")) as f:
        sibling = json.load(f)
    assert mix["runner"] == "train_causal" and mix["batch"] == 1
    assert {k for k in mix if mix[k] != sibling[k]} \
        == {"batch", "who", "what"}
    joined = {e["name"] for group in ("end_to_end", "per_layer")
              for e in m[group] if CELL in e.get("workloads", [])}
    assert joined == set(NEW) | {
        "train_tokens_per_s", "trainer.step_ms", "trainer.mfu_pct",
        "device.idle_pct.train", "trainer.head_loss_ms.train",
        "kernel.attn_stream_ms.moe", "kernel.attn_stream_roofline_pct.moe",
        "experts.load_max_over_mean.moe", "experts.rows_moved_ms.moe",
        "experts.row_index_ms.moe", "experts.gmm_ms.moe",
        "experts.gmm_roofline_pct.moe", "trainer.replay_ms.moe"}
    entry, cfg = config()
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads", "linear_attn_config",
        "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["deployment"]["shares_a_layer"] == 40
    # the nested group changes its number of heads and nothing else
    held, published = (c["linear_attn_config"]
                       for c in (cfg, cfg["published"]))
    assert {k for k in held if held[k] != published[k]} == {"num_heads"}
    s = sizes()
    assert (s["delta_heads"], s["delta_head_dim"], s["conv_kernel"]) == (
        held["num_heads"], held["head_dim"], held["short_conv_kernel_size"])
    assert s["shared_dim"] == s["expert_dim"] == 1280
    assert set(cfg["assumed"]) >= {
        "short_convolution", "qk_norm", "decay_gate", "beta", "recurrence",
        "output_gate", "chunk", "attention", "router", "expert_activation"}


def _trace(ms_by_scope):
    """One device's operation line with one event a scope, back to back,
    under the paths a traced step gives them."""
    events, at = [], 1e6
    for i, (scope, ms) in enumerate(ms_by_scope.items()):
        path = f"jit(step)/transpose(jvp({scope}))/dot_general:" \
            if scope else ""
        if scope in ("rows_moved", "row_index"):
            path = f"jit(step)/jvp(moe_dispatch)/{scope}/gather:"
        if scope == "gmm":
            path = "jit(step)/jvp(experts)/gmm/pallas_call:"
        events.append([f"fusion.{i} fusion", at, ms * 1e6, path])
        at += ms * 1e6
    return [{"name": "/device:TPU:0",
             "lines": [{"name": xplane.OPS_LINE, "events": events}]}]


def test_each_new_metric_file_reads_its_number_from_a_trace(monkeypatch):
    from deeplearning4j_tpu.models import delta_decoder

    ms = {scope: float(i + 1)
          for i, scope in enumerate(delta_decoder.SCOPES)}
    ms[None] = 30.0
    planes = _trace(ms)
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    steps = 2
    obs = Observed(
        facts=dict(flops.kernels_per_step(sizes(), 1, 8192, 6_500),
                   experts_rows_per_step=6_500.0, trace_steps=steps,
                   peak_flops_per_s=197e12, peak_hbm_bytes_per_s=819e9),
        trace=xplane.reduce(planes))
    busy = sum(ms.values())
    assert obs.trace["busy_s"] == pytest.approx(busy / 1e3)

    def read(name):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = __import__("benchmarks.readers." + spec["reader"],
                            fromlist=["read"])
        return reader.read(spec["params"], obs)

    def together(*scopes):
        return sum(ms[s] for s in scopes) / steps

    assert read("kda.proj_ms.delta") == pytest.approx(
        together("kda_in", "kda_out"))
    assert read("kda.conv_ms.delta") == pytest.approx(together("kda_conv"))
    assert read("kda.scan_ms.delta") == pytest.approx(together("kda_scan"))
    # the nested names read under the layer's three where they sit
    assert read("experts.route_ms.delta") == pytest.approx(
        together("router", "moe_dispatch", "moe_combine", "rows_moved",
                 "row_index"))
    assert read("experts.ffn_ms.delta") == pytest.approx(
        together("experts", "gmm"))
    assert read("experts.shared_ms.delta") == pytest.approx(
        together("moe_shared"))
    assert read("trainer.unscoped_pct.delta") == pytest.approx(
        100.0 * 30.0 / busy)
    f = obs.facts
    scan_least = f["kda_scan_bytes_per_step"] / 819e9           # by bytes
    assert read("kda.scan_roofline_pct.delta") == pytest.approx(
        100.0 * scan_least / (together("kda_scan") / 1e3))
    ffn_least = f["experts_ffn_bytes_per_step"] / 819e9         # by bytes
    assert read("experts.ffn_roofline_pct.delta") == pytest.approx(
        100.0 * ffn_least / (together("experts", "gmm") / 1e3))
    assert read("experts.rows_moved_roofline_pct.delta") == pytest.approx(
        100.0 * 6_500 * 65_536 / 819e9 / (together("rows_moved") / 1e3))
    # a program without the names (the parent of the PR that added them)
    planes[:] = _trace({"ssm_scan": 5.0, None: 1.0})
    for name in NEW:
        if name != "trainer.unscoped_pct.delta":
            assert read(name) is None


def run_cell(*extra, timeout=900, command=("run.py",)):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, command[0]), *command[1:],
         "--tiny", "--seed", str(2**31 + 38), "--seconds", "2", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_runs_at_tiny_and_passes_its_checks(trace):
    done = run_cell("--workload", CELL, "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True
    read = line["rehearsal"]["metrics_read"]
    assert ("train_tokens_per_s" in read) == (trace == 0)
    if trace:       # facts, clocks and counters; the CPU has no device plane
        assert {"trainer.step_ms", "experts.load_max_over_mean.moe"} \
            <= set(read)
    facts = next(ln for ln in done.stderr.splitlines()
                 if ln.startswith("bench: facts"))
    facts = json.loads(facts[len("bench: facts"):])
    assert facts["compiled_inside_window"] == 0
    for kernel in ("experts_ffn", "attn_stream", "kda_scan"):
        assert facts[kernel + "_flops_per_step"] > 0
        assert facts[kernel + "_bytes_per_step"] > 0
    assert facts["experts_rows_per_step"] > 0


def test_the_loss_limit_lies_between_its_readings_and_float8_is_told_apart():
    """``loss_rtol`` is set from two readings on the chip (the file's
    ``tolerances.why``): the sound runs' largest, 4.3e-5 over 29 seeds, and
    the median of the reference with float8 matrices, 1.10e-4, with half as
    much again of room on both sides. The control that read the second is a
    tool; at tiny size its run fails the check too (there by the share of
    differing choices: the tiny loss depends on how many steps two seconds
    held)."""
    tol = config()[1]["tolerances"]
    assert 1.5 * 4.3e-5 <= tol["loss_rtol"] <= 1.10e-4 / 1.5
    done = run_cell("--workload", CELL, "--trace", "0",
                    command=("tools/control_readings.py", "float8"))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is False


def test_a_program_without_the_family_fails_the_cell_at_once(tmp_path):
    """What the parent of the PR that adds a family does with that PR's
    benchmark files: the class the configuration names is not there, and
    the run ends with an error in seconds, before anything compiles."""
    m = manifest()
    _, cfg = config()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    cfg["program_class"] = "FamilyOfALaterPR"
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    entry["file"] = str(path)
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(m))
    start = time.time()
    done = run_cell("--workload", CELL, "--manifest", str(other),
                    timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "FamilyOfALaterPR" in done.stderr
    assert time.time() - start < 60
