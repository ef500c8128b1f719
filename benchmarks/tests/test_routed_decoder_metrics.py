"""The routed-expert decoder's yardstick: its flops module at the published
sizes, the roofline reader, and the metric files' vocabulary."""
import glob
import json
import os

import pytest

from benchmarks.lib import flops_routed_decoder as flops
from benchmarks.lib.observe import Observed
from benchmarks.readers import roofline_share

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the published widths, four layers, a quarter of the experts and vocabulary
SIZES = dict(hidden=2560, head_dim=128, heads=28, kv_heads=4,
             experts_total=64, experts_count=16, experts_per_token=6,
             expert_dim=768, layers=4, vocab_size=37984, window=4096,
             window_layout=[0, 1, 1, 1] * 13)


def test_operations_per_token_from_sizes_alone():
    assert flops.expected_experts_per_token(SIZES) == 1.5
    assert flops.matmul_params_touched(SIZES) == 217_169_920
    # a global layer sees every causal pair, a window layer 75 % of them
    T, W = 8192, 4096
    full = T * (T + 1) // 2
    band = W * (W + 1) // 2 + (T - W) * W
    assert flops.visible_pairs(SIZES, T) == full + 3 * band
    assert band / full == pytest.approx(0.75, abs=1e-3)
    per_token = flops.train_flops_per_token(SIZES, T)
    assert per_token == pytest.approx(1.8756e9, rel=1e-4)
    # at T <= window every layer is plain causal attention
    assert flops.visible_pairs(SIZES, 1024) == 4 * (1024 * 1025 // 2)


def test_kernel_work_follows_the_rows_the_program_counted():
    expected = flops.kernels_per_step(SIZES, 2, 8192)
    rows = 4 * 16384 * 1.5
    assert expected["experts_ffn_flops_per_step"] \
        == 6.0 * rows * 3 * 2560 * 768
    counted = flops.kernels_per_step(SIZES, 2, 8192, routed_rows=2 * rows)
    assert counted["experts_ffn_flops_per_step"] \
        == 2 * expected["experts_ffn_flops_per_step"]
    assert counted["attn_stream_flops_per_step"] \
        == expected["attn_stream_flops_per_step"] \
        == 2 * flops.attention_flops_per_sequence(SIZES, 8192)
    assert all(v > 0 for v in counted.values())


def _observed(seconds):
    obs = Observed()
    obs.facts.update(ops=197e12 * 0.010, bytes=819e9 * 0.004, trace_steps=5,
                     peak_flops_per_s=197e12, peak_hbm_bytes_per_s=819e9)
    obs.trace = {"ops_s": {"flash_fwd.3 custom-call": seconds * 5 * 0.25,
                           "flash_bwd_dq.1 custom-call": seconds * 5 * 0.75,
                           "fusion.7 fusion": 1.0}}
    return obs


def test_roofline_share_is_least_time_over_device_time():
    params = {"ops": "ops", "bytes": "bytes", "per": "trace_steps",
              "pattern": r"^flash_(fwd|bwd_dq|bwd_dkv)(\.\d+)? custom-call$"}
    # 10 ms of compute at peak, 4 ms of bytes: compute bounds it
    assert roofline_share.read(params, _observed(0.040)) \
        == pytest.approx(25.0)
    assert roofline_share.read(params, _observed(0.010)) \
        == pytest.approx(100.0)
    obs = _observed(0.040)
    obs.facts["bytes"] = 819e9 * 0.020          # now the bytes bound it
    assert roofline_share.read(params, obs) == pytest.approx(50.0)
    # nothing to read: no trace, no such operation, no fact
    obs.trace = None
    assert roofline_share.read(params, obs) is None
    assert roofline_share.read(dict(params, pattern="^nothing$"),
                               _observed(0.04)) is None
    obs = _observed(0.04)
    del obs.facts["ops"]
    assert roofline_share.read(params, obs) is None


def test_the_new_metric_files_list_the_programs_vocabulary():
    from deeplearning4j_tpu.models import moe_decoder

    files = sorted(glob.glob(os.path.join(BENCH, "metrics", "*.moe.json")))
    assert len(files) == 7
    for path in files:
        with open(path) as f:
            params = json.load(f)["params"]
        names = params.get("innermost_of") or params.get("none_of")
        if names:
            assert names == list(moe_decoder.SCOPES)
