"""The hybrid state-space / expert decoder's yardstick: its flops module at
the published sizes of the share, the new metric files over a trace with
the program's scope names, the cell at tiny size, and a program without the
family."""
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import flops_hybrid_decoder as flops
from benchmarks.lib import xplane, xscope
from benchmarks.lib.observe import Observed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
# the published widths; one period of 11 layers; heads over 8, experts over 64
SIZES = dict(hidden=4096, layers=11, pattern="MEMEMEMEM*E", mamba_heads=16,
             mamba_head_dim=64, mamba_groups=1, state_dim=128, chunk=128,
             heads=4, kv_heads=1, head_dim=128, latent_dim=1024,
             expert_dim=2688, shared_dim=5376, model_share=8,
             experts_total=512, experts_count=8, experts_per_token=22,
             vocab_size=16384)


def new_files():
    return sorted(glob.glob(os.path.join(BENCH, "metrics", "*.hybrid.json")))


def test_operations_per_token_from_sizes_alone():
    assert flops.expected_experts_per_token(SIZES) == 0.34375
    assert flops.mamba_params(SIZES) == 4096 * 2320 + 1024 * 4096
    assert flops.expert_params(SIZES) == 5_505_024
    touched = flops.matmul_params_touched(SIZES)
    assert touched == pytest.approx(
        5 * 13_697_024 + 5_242_880 + 67_108_864
        + 5 * (2_097_152 + 8_388_608 + 5_505_024 + 0.34375 * 5_505_024))
    assert touched == pytest.approx(230.4e6, rel=2e-3)
    T = 8192
    assert flops.attention_flops_per_sequence(SIZES, T) \
        == 12.0 * 128 * 4 * (T * (T + 1) // 2)
    # the chunked scan's interior: the causal half of a chunk, no more
    assert flops.scan_multiply_adds_per_token(SIZES) \
        == 64.5 * 128 + 16 * (64.5 * 64 + 2 * 64 * 128)
    # a share's router runs forward only (the program gives it no
    # gradient): 2 operations a parameter, where the whole model's has 6
    assert flops.router_params(SIZES) == 5 * 4096 * 512
    per_token = flops.train_flops_per_token(SIZES, T)
    assert per_token == 6.0 * touched - 4.0 * 5 * 4096 * 512 \
        + 12.0 * 128 * 4 * (T + 1) / 2 \
        + 6.0 * 5 * flops.scan_multiply_adds_per_token(SIZES)
    assert per_token == pytest.approx(1.3748e9, rel=1e-4)
    whole = dict(SIZES, experts_count=512)
    assert flops.train_flops_per_token(whole, T) \
        == 6.0 * flops.matmul_params_touched(whole) + per_token \
        - 6.0 * touched + 4.0 * 5 * 4096 * 512


def test_kernel_work_follows_the_rows_the_program_counted():
    expected = flops.kernels_per_step(SIZES, 2, 8192)
    rows = 5 * 16384 * 0.34375
    assert expected["experts_ffn_flops_per_step"] \
        == 6.0 * rows * 2 * 1024 * 2688
    counted = flops.kernels_per_step(SIZES, 2, 8192, routed_rows=10 * rows)
    assert counted["experts_ffn_flops_per_step"] \
        == 10 * expected["experts_ffn_flops_per_step"]
    assert counted["experts_ffn_bytes_per_step"] \
        > expected["experts_ffn_bytes_per_step"]
    for kernel in ("attn_stream", "ssm_scan"):
        for what in ("flops", "bytes"):
            key = f"{kernel}_{what}_per_step"
            assert counted[key] == expected[key] > 0
    assert expected["attn_stream_flops_per_step"] \
        == 2 * flops.attention_flops_per_sequence(SIZES, 8192)
    assert expected["ssm_scan_flops_per_step"] \
        == 6.0 * 5 * 16384 * flops.scan_multiply_adds_per_token(SIZES)
    # X in and y out, B and C, delta; then three times as much backward
    assert expected["ssm_scan_bytes_per_step"] == 5 * 16384 * (
        (2 * 2048 + 512 + 64) + (4 * 2048 + 1024 + 128))


def test_the_new_metric_files_list_the_programs_vocabulary():
    from deeplearning4j_tpu.models import hybrid_decoder

    files = new_files()
    assert len(files) == 8
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for path in files:
        name = os.path.basename(path)[:-len(".json")]
        with open(path) as f:
            spec = json.load(f)
        params = spec["params"]
        assert (params.get("innermost_of") or params.get("none_of")) \
            == list(hybrid_decoder.SCOPES)
        assert listed[name]["workloads"] == [manifest["workloads"][-1]["name"]]
        assert listed[name]["layer"] == spec["layer"]
        if "scope" in params and params["scope"]:
            assert set(params["scope"].split("|")) <= set(
                hybrid_decoder.SCOPES)


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
    from deeplearning4j_tpu.models import hybrid_decoder

    ms = {scope: float(i + 1) for i, scope in enumerate(
        hybrid_decoder.SCOPES)}
    ms[None] = 30.0
    planes = _trace(ms)
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    steps = 2
    obs = Observed(
        facts=dict(flops.kernels_per_step(SIZES, 2, 8192, 300_000),
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

    assert read("ssm.scan_ms.hybrid") == pytest.approx(together("ssm_scan"))
    assert read("ssm.proj_conv_ms.hybrid") == pytest.approx(
        together("ssm_in", "ssm_conv", "ssm_out"))
    assert read("experts.route_ms.hybrid") == pytest.approx(
        together("router", "moe_dispatch", "moe_combine"))
    assert read("experts.ffn_ms.hybrid") == pytest.approx(together("experts"))
    assert read("experts.latent_shared_ms.hybrid") == pytest.approx(
        together("moe_latent", "moe_shared"))
    assert read("trainer.unscoped_pct.hybrid") == pytest.approx(
        100.0 * 30.0 / busy)
    f = obs.facts
    scan_least = max(f["ssm_scan_flops_per_step"] / 197e12,
                     f["ssm_scan_bytes_per_step"] / 819e9)
    assert scan_least == f["ssm_scan_bytes_per_step"] / 819e9  # by bytes
    assert read("ssm.scan_roofline_pct.hybrid") == pytest.approx(
        100.0 * scan_least / (together("ssm_scan") / 1e3))
    ffn_least = f["experts_ffn_flops_per_step"] / 197e12        # by compute
    assert read("experts.ffn_roofline_pct.hybrid") == pytest.approx(
        100.0 * ffn_least / (together("experts") / 1e3))
    # a program without the names (the parent of the PR that added them)
    planes[:] = _trace({"mlp": 5.0, None: 1.0})
    for path in new_files():
        name = os.path.basename(path)[:-len(".json")]
        if name != "trainer.unscoped_pct.hybrid":
            assert read(name) is None


def run_cell(*extra, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--tiny", "--seed",
         str(2**31 + 32), "--seconds", "2", *extra], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def new_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"][-1]["name"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_runs_at_tiny_and_passes_its_checks(trace):
    done = run_cell("--workload", new_cell(), "--trace", str(trace))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is True
    read = line["rehearsal"]["metrics_read"]
    assert ("train_tokens_per_s" in read) == (trace == 0)
    facts = next(ln for ln in done.stderr.splitlines()
                 if ln.startswith("bench: facts"))
    facts = json.loads(facts[len("bench: facts"):])
    assert facts["compiled_inside_window"] == 0
    for kernel in ("experts_ffn", "attn_stream", "ssm_scan"):
        assert facts[kernel + "_flops_per_step"] > 0
    assert facts["experts_rows_per_step"] > 0


def test_a_program_without_the_family_fails_the_cell_at_once(tmp_path):
    """What the parent of the PR that adds a family does with that PR's
    benchmark files: the class the configuration names is not there, and
    the run ends with an error in seconds, before anything compiles."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["configs"][-1]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config["program_class"] = "FamilyOfALaterPR"
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(manifest))
    start = time.time()
    done = run_cell("--workload", new_cell(), "--manifest", str(other),
                    timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "FamilyOfALaterPR" in done.stderr
    assert time.time() - start < 60
