"""Bench variant for BASELINE config #4 THROUGH the import path: a frozen
BERT-base GraphDef is imported into SameDiff and fine-tuned under whole-graph
jit (the benchmark's cell ``bert-base-train`` trains the hand-written
transformer).

Run manually: python tools/bench_tf_import.py
Prints one JSON line, ``device`` (platform, device_kind, count) included.
``vs_baseline`` is MFU against the 35% north-star gate. Off a TPU the run is
a toy-size smoke of the control flow: another metric name, no MFU, no
``vs_baseline``.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    import argparse
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.modelimport.tensorflow import TensorflowFrameworkImporter
    from tools.tf_bert import build_frozen_bert
    from deeplearning4j_tpu.profiler.profiler import device_record
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    # HALF is the default: the import-time mixed-precision rewrite
    # (TrainingConfig.computeDtype) is the whole-graph-compile payoff this
    # config exists to show (fp32 numbers stay reproducible via --dtype FLOAT)
    ap.add_argument("--dtype", default="HALF", choices=["FLOAT", "HALF"])
    # representative configuration (round-5 verdict #2): a score listener
    # attached the way reference users run sd.fit — must stay within ~5%
    # of the listener-free number now that SameDiff.fit fuses through
    # listeners via requiresModelAtIteration chunking
    ap.add_argument("--listener", action="store_true",
                    help="attach ScoreIterationListener(10) during timing")
    ap.add_argument("--fuse-attention", action="store_true",
                    help="run sd.fuseAttention() before training (collapse "
                    "imported matmul/scale/softmax/matmul chains onto the "
                    "Pallas-backed fused attention op)")
    args = ap.parse_args()

    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        L, H, A, V, T, inter = 12, 768, 12, 30522, 128, 3072
        # steps/warmup sized to the fused fit path: warmup covers one full
        # fuseSteps chunk PLUS leftovers so both the multi-step scan and the
        # single-step executable compile before the timing window.
        # fuseSteps=32: at these small steps per-dispatch host latency is
        # a large share of a chunk, so deeper chunks amortize it
        B, steps, warmup = 32, 64, 34
    else:
        L, H, A, V, T, inter = 2, 64, 4, 256, 16, 128
        B, steps, warmup = 4, 3, 1

    gd, in_name, out_name, _ = build_frozen_bert(L=L, H=H, A=A, V=V, T=T,
                                                 intermediate=inter)
    sd = TensorflowFrameworkImporter.runImport(gd)
    sd.convertAllConstantsToVariables()
    if on_tpu:
        sd.fuseSteps = 32  # see comment above
    if args.fuse_attention:
        nf = sd.fuseAttention()
        print(f"# fuseAttention: {nf} sites", file=sys.stderr)
    n_param = sum(int(np.prod(v.shape)) for v in sd.variables()
                  if v.varType == "VARIABLE" and v.shape)

    # MLM head over the imported encoder output
    hidden = sd.getVariable(out_name)
    lm_w = sd.var("lm_head", (H, V), weightInit="XAVIER")
    logits = sd.linalg.matmul(hidden, lm_w)
    targets = sd.placeHolder("targets", shape=(B, T), dtype=jnp.int32)
    loss = sd.loss.sparseMcxent(targets, logits)
    sd.setLossVariables(loss.name)
    sd.setTrainingConfig(TrainingConfig(
        updater=Adam(1e-4),
        computeDtype="HALF" if args.dtype == "HALF" else None))

    if args.listener:
        from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener
        sd.listeners = [ScoreIterationListener(printIterations=10)]

    rng = np.random.default_rng(0)
    batch = {in_name: rng.integers(0, V, (B, T)).astype(np.int32),
             "targets": rng.integers(0, V, (B, T)).astype(np.int32)}
    # ONE fit call per timing window: fit() bulk-syncs its loss history once
    # at the end, so steps inside a call pipeline asynchronously — a
    # fit-per-step loop pays a device->host round trip every step
    sd.fit([batch] * warmup)
    # median of 3 timing windows: the first post-warmup
    # fit window can pay a one-off transient — a single window reports the
    # transient, the median reports steady state. Each fit() returns its
    # loss history as host floats, so a window ends after the device does.
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        hist = sd.fit([batch] * steps)
        dts.append(time.perf_counter() - t0)
        assert len(hist) == steps
    dt = sorted(dts)[1]

    tokens_per_sec = B * T * steps / dt
    result = {
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec",
        "device": device_record(),
        "dtype": args.dtype,
        "listener": bool(args.listener),
    }
    if on_tpu:
        from deeplearning4j_tpu.profiler.profiler import (
            MFU_BASIS, mfu as _mfu, peak_flops, transformer_flops_per_token)
        n_emb = V * H + T * H
        flops_per_token = transformer_flops_per_token(
            n_param - n_emb + H * V, L, H, T)
        mfu = _mfu(tokens_per_sec, flops_per_token,
                   peak_flops(jax.devices()[0]))
        result.update(
            metric="bert_base_tf_import_finetune_tokens_per_sec_per_chip",
            mfu=round(mfu, 4), mfu_basis=MFU_BASIS,
            vs_baseline=round(mfu / 0.35, 4))
    else:
        result.update(
            metric=f"toy_tf_import_finetune_tokens_per_sec_"
                   f"{result['device']['platform']}_smoke",
            note="control-flow smoke off the TPU on a toy graph: not a "
                 "device measurement; MFU and vs_baseline not measured")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
