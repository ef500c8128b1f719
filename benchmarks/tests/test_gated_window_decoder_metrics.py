"""The window/full-attention expert decoder's yardstick: its flops module at
the published sizes of the share (the in-window pairs against a count by
brute force), the new metric files over a trace with the program's scope
names, an accepted file that does not list the nested ``attn_window``, the
cell at tiny size, and a program without the new terms. Cell, configuration
and metrics are found by name, so a later PR's entries do not move them."""
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import flops_gated_window_decoder as flops
from benchmarks.lib import model, xplane, xscope
from benchmarks.lib.observe import Observed

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, CONFIG = "laguna-s21-tp8ep32-train-8k", "laguna-s-2.1-tp8ep32"
NEW = ("attn.window_ms.gated", "attn.window_roofline_pct.gated",
       "attn.gate_rope_ms.gated", "experts.route_ms.gated",
       "experts.ffn_ms.gated", "experts.shared_ms.gated",
       "trainer.dense_mlp_ms.gated", "trainer.unscoped_pct.gated")


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


def spec_of(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("t,window", [(1, 1), (7, 3), (16, 4), (33, 8),
                                      (40, 64), (64, None)])
def test_the_in_window_pairs_are_a_brute_force_count(t, window):
    """Pairs (i, j) with 0 <= i - j and, with a window, i - j < window:
    the query's own position counts."""
    brute = sum(1 for i in range(t) for j in range(t)
                if 0 <= i - j and (window is None or i - j < window))
    assert flops.visible_pairs(t, window) == brute


def test_operations_per_token_from_sizes_alone():
    s = sizes()
    assert flops.dense_layers(s) == 1 and flops.expert_layers(s) == 4
    assert flops.window_layers(s) == [1, 2, 3]
    assert [flops.heads(s, i) for i in range(5)] == [6, 9, 9, 9, 6]
    assert flops.expected_experts_per_token(s) == 10 * 8 / 256
    assert flops.attention_matrix_params(s, 0) == 5_523_456
    assert flops.attention_matrix_params(s, 1) == 7_891_968
    assert flops.columns(s, "shared_dim") == 128
    assert flops.columns(s, "mlp_dim") == 1536
    T = 8192
    pairs_full = T * (T + 1) // 2
    pairs_window = 512 * 513 // 2 + (T - 512) * 512
    assert flops.attention_flops_per_sequence(s, T) == 12.0 * 128 * (
        2 * 6 * pairs_full + 3 * 9 * pairs_window)
    touched = flops.matmul_params_touched(s)
    assert touched == (2 * 5_523_456 + 3 * 7_891_968
                       + 3 * 3072 * 1536
                       + 4 * (3 * 3072 * 128 + 0.3125 * 3 * 3072 * 1024)
                       + 4 * 3072 * 256 + 3072 * 12544)
    per_token = flops.train_flops_per_token(s, T)
    # a frozen share's router runs forward only: 2 operations a parameter
    assert per_token == 6.0 * touched - 4.0 * 4 * 3072 * 256 \
        + flops.attention_flops_per_sequence(s, T) / T
    assert per_token == pytest.approx(7.2594e8, rel=1e-4)
    # the head over the slice is a third of the cut's operations
    assert 6.0 * 3072 * 12544 / per_token == pytest.approx(0.318, abs=1e-3)
    trained = dict(s, freeze_share_router=False)
    assert flops.train_flops_per_token(trained, T) \
        == per_token + 4.0 * 4 * 3072 * 256


def test_the_parameter_count_is_the_pytrees():
    import jax
    from deeplearning4j_tpu.models import MoEDecoderConfig, init_params

    for tiny in (False, True):
        s = model.sizes(config()[1], tiny)
        shapes = jax.eval_shape(lambda: init_params(
            jax.random.PRNGKey(0), MoEDecoderConfig(**s)))
        assert sum(a.size for a in jax.tree.leaves(shapes)) \
            == flops.parameters(s)
    assert flops.parameters(sizes()) == 435_836_928 \
        == config()[1]["deployment"]["parameters"]


def test_kernel_work_follows_the_rows_and_the_window():
    s = sizes()
    expected = flops.kernels_per_step(s, 2, 8192)
    rows = 4 * 2 * 8192 * 0.3125
    assert expected["experts_ffn_flops_per_step"] \
        == pytest.approx(6.0 * rows * 3 * 3072 * 1024)
    counted = flops.kernels_per_step(s, 2, 8192, routed_rows=2 * rows)
    assert counted["experts_ffn_flops_per_step"] \
        == pytest.approx(2 * expected["experts_ffn_flops_per_step"])
    window = 2 * 12.0 * 128 * 9 * 3 * flops.visible_pairs(8192, 512)
    assert expected["attn_window_flops_per_step"] == window
    # q, k, v read and o written, then q, k, v, o and do read and dq, dk,
    # dv written, in bfloat16, on the three window layers
    assert expected["attn_window_bytes_per_step"] \
        == 2 * 3 * 2 * 8192 * 128 * (6 * 9 + 6 * 1)
    assert expected["attn_stream_flops_per_step"] \
        == 2 * flops.attention_flops_per_sequence(s, 8192)
    assert expected["attn_stream_bytes_per_step"] \
        > expected["attn_window_bytes_per_step"]
    # the band is bound by its operations on the v5e
    assert window / 197e12 > expected["attn_window_bytes_per_step"] / 819e9


def test_the_new_metric_files_list_the_programs_vocabulary():
    from deeplearning4j_tpu.models import moe_decoder

    files = sorted(glob.glob(os.path.join(BENCH, "metrics", "*.gated.json")))
    assert [os.path.basename(p)[:-len(".json")] for p in files] \
        == sorted(NEW)
    listed = {m["name"]: m for m in manifest()["per_layer"]}
    nested = list(moe_decoder.SCOPES[-3:])
    for name in NEW:
        spec = spec_of(name)
        params = spec["params"]
        vocabulary = params.get("innermost_of") or params.get("none_of")
        assert vocabulary == [n for n in moe_decoder.GATED_SCOPES
                              if n not in nested]
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["layer"] == spec["layer"]
        assert listed[name]["unit"] == spec["unit"]
        assert listed[name]["moves"] == "train_tokens_per_s"
        assert listed[name]["better"] == (
            "higher" if "roofline" in name else "lower")
        if params.get("scope"):
            assert set(params["scope"].split("|")) <= set(
                moe_decoder.GATED_SCOPES)


def test_the_cell_joins_the_accepted_metrics_it_reports():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "clm-b2-t8192" and len(cell["why"]) <= 200
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
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_attention_heads",
        "num_key_value_heads", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["deployment"]["shares_a_layer"] == 32
    assert set(cfg["assumed"]) >= {
        "router", "router_input", "expert_activation", "shared_expert",
        "gate", "qk_norm", "rope", "window_counts_own_position",
        "output_head"}


def _trace(ms_by_scope):
    """One device's operation line with one event a scope, back to back,
    under the paths a traced step gives them: ``attn_window`` inside
    ``attention``, the expert layer's nested names inside theirs."""
    events, at = [], 1e6
    for i, (scope, ms) in enumerate(ms_by_scope.items()):
        path = f"jit(step)/transpose(jvp({scope}))/dot_general:" \
            if scope else ""
        if scope == "attn_window":
            path = "jit(step)/jvp(attention)/attn_window/flash_fwd/" \
                "pallas_call:"
        if scope in ("rows_moved", "row_index"):
            path = f"jit(step)/jvp(moe_dispatch)/{scope}/gather:"
        if scope == "gmm":
            path = "jit(step)/jvp(experts)/gmm/pallas_call:"
        events.append([f"fusion.{i} fusion", at, ms * 1e6, path])
        at += ms * 1e6
    return [{"name": "/device:TPU:0",
             "lines": [{"name": xplane.OPS_LINE, "events": events}]}]


def _read(name, obs):
    spec = spec_of(name)
    reader = __import__("benchmarks.readers." + spec["reader"],
                        fromlist=["read"])
    return reader.read(spec["params"], obs)


def test_each_new_metric_file_reads_its_number_from_a_trace(monkeypatch):
    from deeplearning4j_tpu.models import moe_decoder

    ms = {scope: float(i + 1)
          for i, scope in enumerate(moe_decoder.GATED_SCOPES)}
    ms[None] = 30.0
    planes = _trace(ms)
    monkeypatch.setattr(xscope, "traced", lambda: planes)
    steps = 2
    obs = Observed(
        facts=dict(flops.kernels_per_step(sizes(), 2, 8192, 20_000),
                   experts_rows_per_step=20_000.0, trace_steps=steps,
                   peak_flops_per_s=197e12, peak_hbm_bytes_per_s=819e9),
        trace=xplane.reduce(planes))
    busy = sum(ms.values())
    assert obs.trace["busy_s"] == pytest.approx(busy / 1e3)

    def together(*scopes):
        return sum(ms[s] for s in scopes) / steps

    assert _read("attn.window_ms.gated", obs) == pytest.approx(
        together("attn_window"))
    least = obs.facts["attn_window_flops_per_step"] / 197e12  # by operations
    share = _read("attn.window_roofline_pct.gated", obs)
    assert share == pytest.approx(
        100.0 * least / (together("attn_window") / 1e3))
    assert _read("attn.gate_rope_ms.gated", obs) == pytest.approx(
        together("attn_gate", "rope"))
    # the nested names read under the layer's three where they sit
    assert _read("experts.route_ms.gated", obs) == pytest.approx(
        together("router", "moe_dispatch", "moe_combine", "rows_moved",
                 "row_index"))
    assert _read("experts.ffn_ms.gated", obs) == pytest.approx(
        together("experts", "gmm"))
    assert _read("experts.shared_ms.gated", obs) == pytest.approx(
        together("moe_shared"))
    assert _read("trainer.dense_mlp_ms.gated", obs) == pytest.approx(
        together("mlp"))
    assert _read("trainer.unscoped_pct.gated", obs) == pytest.approx(
        100.0 * 30.0 / busy)
    # a program without the new names (the parent of the PR that adds
    # them) reads nothing in the files of the new terms
    planes[:] = _trace({"attention": 5.0, "router": 2.0, None: 1.0})
    for name in ("attn.window_ms.gated", "attn.window_roofline_pct.gated",
                 "attn.gate_rope_ms.gated", "experts.shared_ms.gated",
                 "trainer.dense_mlp_ms.gated"):
        assert _read(name, obs) is None


@pytest.mark.parametrize("name", [
    "kernel.attn_stream_ms.moe", "trainer.unscoped_pct.moe",
    "experts.route_ms.moe", "experts.ffn_ms.moe", "trainer.head_loss_ms.train",
    "experts.rows_moved_ms.moe"])
def test_an_accepted_file_reads_the_same_with_and_without_attn_window(
        monkeypatch, name):
    """SmallThinker's window layers carry ``attn_window`` inside
    ``attention`` since this name came: an accepted file, which does not
    list it, counts those operations under ``attention`` as before."""
    base = {"attn_qkv": 3.0, "rope": 1.0, "router": 2.0, "experts": 4.0,
            "moe_dispatch": 1.5, "lm_head": 6.0, None: 0.5}

    def planes(nested):
        out = _trace(base)
        line = out[0]["lines"][0]["events"]
        at = line[-1][1] + line[-1][2]
        for i, (kernel, ms) in enumerate((("flash_fwd", 7.0),
                                          ("flash_bwd_dkv", 9.0))):
            inner = "attn_window/" if nested else ""
            line.append([f"{kernel}.{i} custom-call", at, ms * 1e6,
                         f"jit(step)/jvp(attention)/{inner}{kernel}/"
                         "pallas_call:"])
            at += ms * 1e6
        return out

    read = []
    for nested in (False, True):
        p = planes(nested)
        monkeypatch.setattr(xscope, "traced", lambda p=p: p)
        obs = Observed(facts={"trace_steps": 2, "attn_stream_flops_per_step":
                              1e12, "attn_stream_bytes_per_step": 1e9,
                              "peak_flops_per_s": 197e12,
                              "peak_hbm_bytes_per_s": 819e9},
                       trace=xplane.reduce(p))
        read.append(_read(name, obs))
    assert read[0] == read[1]
    assert read[0] is not None or name == "experts.rows_moved_ms.moe"


def run_cell(*extra, timeout=900, command=("run.py",)):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, command[0]), *command[1:],
         "--tiny", "--seed", str(2**31 + 41), "--seconds", "2", *extra],
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
    facts = next(ln for ln in done.stderr.splitlines()
                 if ln.startswith("bench: facts"))
    facts = json.loads(facts[len("bench: facts"):])
    assert facts["compiled_inside_window"] == 0
    for kernel in ("experts_ffn", "attn_stream", "attn_window"):
        assert facts[kernel + "_flops_per_step"] > 0
        assert facts[kernel + "_bytes_per_step"] > 0
    assert facts["experts_rows_per_step"] > 0


def test_the_limits_lie_between_their_readings_and_float8_is_told_apart():
    """Each limit of ``tolerances`` is set from readings on the chip (the
    file's ``tolerances.why``): the sound runs' largest over thirteen seeds
    and the smallest of four of the reference with float8 matrices. The
    loss and the share of differing choices lie between the two with room
    on both sides; the logits and the margins, where the two readings
    meet, are room only, above twice the sound runs' largest and under
    every left-out term's smallest logits. The control that read the
    second is a tool; at tiny size its run fails the check too."""
    tol = config()[1]["tolerances"]
    told_apart = {"loss_rtol": (7.7e-5, 4.87e-4),
                  "flipped_share_max": (0.0201, 0.189)}
    for key, (system, control) in told_apart.items():
        assert 2 * system <= tol[key] < control / 1.25, key
    room = {"logit_abs": (0.837, 0.828), "margin_max": (0.288, 0.283)}
    for key, (system, control) in room.items():
        assert control <= system and 2 * system <= tol[key], key
    assert tol["logit_abs"] < 2.34          # the window layers made causal
    done = run_cell("--workload", CELL, "--trace", "0",
                    command=("tools/control_readings.py", "float8"))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["rehearsal"]["checks_passed"] is False


def test_a_program_without_the_terms_fails_the_cell_at_once(tmp_path):
    """What the parent of the PR that adds the Laguna terms does with that
    PR's benchmark files: its configuration class takes none of the new
    fields, and the run ends with an error in seconds, before anything
    compiles."""
    m = manifest()
    _, cfg = config()
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    cfg["program"]["a_field_of_a_later_pr"] = 1
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    entry["file"] = str(path)
    other = tmp_path / "BENCHMARK.json"
    other.write_text(json.dumps(m))
    start = time.time()
    done = run_cell("--workload", CELL, "--manifest", str(other),
                    timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "a_field_of_a_later_pr" in done.stderr
    assert time.time() - start < 60
