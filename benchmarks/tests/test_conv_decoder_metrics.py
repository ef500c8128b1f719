"""The convolution / attention expert decoder's yardstick: its flops module
at the published sizes of the share, the new metric files over a trace with
the program's scope names, the cell at tiny size, and a program without the
family. Cell, configuration and metrics are found by name, so a later PR's
entries do not move them."""
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import flops_conv_decoder as flops
from benchmarks.lib import xplane, xscope
from benchmarks.lib.observe import Observed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, CONFIG = "lfm2-24b-ep8-train-8k", "lfm2-24b-a2b-ep8"
# the published widths; the last dense layer and one period of four expert
# layers; 8 of 64 experts and an eighth of the vocabulary held
SIZES = dict(hidden=2048, layers=5, dense_layers=1, conv_kernel=3,
             mixers=["conv", "full_attention", "conv", "conv", "conv"],
             heads=32, kv_heads=8, head_dim=64, mlp_dim=11776,
             expert_dim=1536, experts_total=64, experts_count=8,
             experts_per_token=4, vocab_size=8192)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def new_files():
    return sorted(glob.glob(os.path.join(BENCH, "metrics", "*.conv.json")))


def test_operations_per_token_from_sizes_alone():
    assert flops.conv_layers(SIZES) == 4 and flops.attention_layers(SIZES) == 1
    assert flops.expert_layers(SIZES) == 4
    assert flops.expected_experts_per_token(SIZES) == 0.5
    assert flops.expert_params(SIZES) == 9_437_184
    touched = flops.matmul_params_touched(SIZES)
    assert touched == (
        4 * 16_777_216 + 10_485_760           # the mixers' projections
        + 72_351_744                          # the dense MLP
        + 4 * 131_072 + 4 * 0.5 * 9_437_184   # routers, routed experts
        + 16_777_216)                         # the head over the slice
    assert touched == pytest.approx(186.1e6, rel=1e-3)
    T = 8192
    assert flops.attention_flops_per_sequence(SIZES, T) \
        == 12.0 * 64 * 32 * (T * (T + 1) // 2)
    # a share's router runs forward only: 2 operations a parameter, not 6
    assert flops.router_params(SIZES) == 4 * 2048 * 64
    per_token = flops.train_flops_per_token(SIZES, T)
    assert per_token == 6.0 * touched - 4.0 * 4 * 2048 * 64 \
        + 12.0 * 64 * 32 * (T + 1) / 2
    assert per_token == pytest.approx(1.2153e9, rel=1e-4)
    whole = dict(SIZES, experts_count=64)
    assert flops.train_flops_per_token(whole, T) \
        == 6.0 * flops.matmul_params_touched(whole) + per_token \
        - 6.0 * touched + 4.0 * 4 * 2048 * 64
    # the dense layer is 39 % of the cut's matmul operations
    assert (16_777_216 + 72_351_744) / (touched - 16_777_216) \
        == pytest.approx(0.526, abs=1e-3)
    assert 72_351_744 / touched == pytest.approx(0.389, abs=1e-3)


def test_kernel_work_follows_the_rows_the_program_counted():
    expected = flops.kernels_per_step(SIZES, 4, 8192)
    rows = 4 * 32768 * 0.5
    assert expected["experts_ffn_flops_per_step"] \
        == 6.0 * rows * 3 * 2048 * 1536
    counted = flops.kernels_per_step(SIZES, 4, 8192, routed_rows=3 * rows)
    assert counted["experts_ffn_flops_per_step"] \
        == 3 * expected["experts_ffn_flops_per_step"]
    assert counted["experts_ffn_bytes_per_step"] \
        > expected["experts_ffn_bytes_per_step"]
    for kernel in ("attn_stream", "conv_gate"):
        for what in ("flops", "bytes"):
            key = f"{kernel}_{what}_per_step"
            assert counted[key] == expected[key] > 0
    assert expected["attn_stream_flops_per_step"] \
        == 4 * flops.attention_flops_per_sequence(SIZES, 8192)
    # B, C, X read and the gated rows written; then B, C, X and the
    # cotangent read and three cotangents written: 11 bfloat16 rows
    assert expected["conv_gate_bytes_per_step"] \
        == 4 * 32768 * 2048 * 11 * 2
    # two gates and three taps' five multiplies and adds, three times over
    assert expected["conv_gate_flops_per_step"] == 4 * 32768 * 2048 * 21
    # bound by its bytes on the v5e
    assert expected["conv_gate_bytes_per_step"] / 819e9 \
        > expected["conv_gate_flops_per_step"] / 197e12


def test_the_new_metric_files_list_the_programs_vocabulary():
    from deeplearning4j_tpu.models import conv_decoder

    files = new_files()
    assert len(files) == 8
    listed = {m["name"]: m for m in manifest()["per_layer"]}
    for path in files:
        name = os.path.basename(path)[:-len(".json")]
        with open(path) as f:
            spec = json.load(f)
        params = spec["params"]
        assert (params.get("innermost_of") or params.get("none_of")) \
            == list(conv_decoder.SCOPES)
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] == spec["layer"]
        assert listed[name]["unit"] == spec["unit"]
        assert listed[name]["moves"] == "train_tokens_per_s"
        if params.get("scope"):
            assert set(params["scope"].split("|")) <= set(
                conv_decoder.SCOPES)


def test_the_cell_joins_the_accepted_metrics_it_reports():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["runner"] == "train_causal" and mix["seq_len"] == 8192
    joined = {e["name"] for group in ("end_to_end", "per_layer")
              for e in m[group] if CELL in e.get("workloads", [])}
    assert joined >= {
        "train_tokens_per_s", "trainer.step_ms", "trainer.mfu_pct",
        "device.idle_pct.train", "trainer.head_loss_ms.train",
        "kernel.attn_stream_ms.moe", "kernel.attn_stream_roofline_pct.moe",
        "experts.load_max_over_mean.moe"}
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    assert set(config["published"]) == set(config["reduced"])
    assert config["deployment"]["shares_a_layer"] == 8


def _trace(ms_by_scope):
    """One device's operation line with one event a scope, back to back,
    under the paths a traced step gives them."""
    events, at = [], 1e6
    for i, (scope, ms) in enumerate(ms_by_scope.items()):
        path = f"jit(step)/transpose(jvp({scope}))/dot_general:" \
            if scope else ""
        events.append([f"fusion.{i} fusion", at, ms * 1e6, path])
        at += ms * 1e6
    return [{"name": "/device:TPU:0",
             "lines": [{"name": xplane.OPS_LINE, "events": events}]}]


def test_each_new_metric_file_reads_its_number_from_a_trace(monkeypatch):
    from deeplearning4j_tpu.models import conv_decoder

    ms = {scope: float(i + 1) for i, scope in enumerate(conv_decoder.SCOPES)}
    ms[None] = 30.0
    planes = _trace(ms)
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    steps = 2
    obs = Observed(
        facts=dict(flops.kernels_per_step(SIZES, 4, 8192, 67_000),
                   trace_steps=steps, peak_flops_per_s=197e12,
                   peak_hbm_bytes_per_s=819e9),
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

    assert read("conv.proj_ms.conv") == pytest.approx(
        together("conv_in", "conv_out"))
    assert read("conv.gate_ms.conv") == pytest.approx(together("conv_gate"))
    assert read("experts.route_ms.conv") == pytest.approx(
        together("router", "moe_dispatch", "moe_combine"))
    assert read("experts.ffn_ms.conv") == pytest.approx(together("experts"))
    assert read("trainer.dense_mlp_ms.conv") == pytest.approx(together("mlp"))
    assert read("trainer.unscoped_pct.conv") == pytest.approx(
        100.0 * 30.0 / busy)
    f = obs.facts
    gate_least = f["conv_gate_bytes_per_step"] / 819e9          # by bytes
    assert gate_least > f["conv_gate_flops_per_step"] / 197e12
    assert read("conv.gate_roofline_pct.conv") == pytest.approx(
        100.0 * gate_least / (together("conv_gate") / 1e3))
    ffn_least = f["experts_ffn_flops_per_step"] / 197e12        # by compute
    assert ffn_least > f["experts_ffn_bytes_per_step"] / 819e9
    assert read("experts.ffn_roofline_pct.conv") == pytest.approx(
        100.0 * ffn_least / (together("experts") / 1e3))
    # a program without the names (the parent of the PR that added them)
    planes[:] = _trace({"ssm_scan": 5.0, None: 1.0})
    for path in new_files():
        name = os.path.basename(path)[:-len(".json")]
        if name != "trainer.unscoped_pct.conv":
            assert read(name) is None


def run_cell(*extra, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--tiny", "--seed",
         str(2**31 + 34), "--seconds", "2", *extra], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


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
    for kernel in ("experts_ffn", "attn_stream", "conv_gate"):
        assert facts[kernel + "_flops_per_step"] > 0
        assert facts[kernel + "_bytes_per_step"] > 0
    assert facts["experts_rows_per_step"] > 0


def test_a_program_without_the_family_fails_the_cell_at_once(tmp_path):
    """What the parent of the PR that adds a family does with that PR's
    benchmark files: the class the configuration names is not there, and
    the run ends with an error in seconds, before anything compiles."""
    m = manifest()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["program_class"] = "FamilyOfALaterPR"
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(m))
    start = time.time()
    done = run_cell("--workload", CELL, "--manifest", str(other),
                    timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "FamilyOfALaterPR" in done.stderr
    assert time.time() - start < 60
