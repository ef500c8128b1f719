"""BERT-scale loss parity: TF-imported fine-tune under computeDtype=HALF
(bf16 compute / fp32 masters) vs FLOAT, identical data and init.

The round-2 verdict's done-criterion for config #4: "parity vs fp32 within
loss tolerance at B=32/T=128". Run on the TPU:

    python tools/check_import_parity.py [--steps 30]

Prints per-step losses for both dtypes and the max |rel diff|, then a
PASS/FAIL against --rtol (default 0.02: bf16 matmul rounding accumulates
~1e-3/step on this workload; 2% headroom keeps the check meaningful without
flaking).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np


def run(dtype: str, steps: int):
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.train import Adam
    from deeplearning4j_tpu.modelimport.tensorflow import TensorflowFrameworkImporter
    from tools.tf_bert import build_frozen_bert

    L, H, A, V, T, inter = 12, 768, 12, 30522, 128, 3072
    B = 32
    gd, in_name, out_name, _ = build_frozen_bert(L=L, H=H, A=A, V=V, T=T,
                                                 intermediate=inter)
    sd = TensorflowFrameworkImporter.runImport(gd)
    sd.convertAllConstantsToVariables()
    hidden = sd.getVariable(out_name)
    lm_w = sd.var("lm_head", (H, V), weightInit="XAVIER")
    logits = sd.linalg.matmul(hidden, lm_w)
    targets = sd.placeHolder("targets", shape=(B, T), dtype=jnp.int32)
    loss = sd.loss.sparseMcxent(targets, logits)
    sd.setLossVariables(loss.name)
    sd.setTrainingConfig(TrainingConfig(
        updater=Adam(1e-4),
        computeDtype="BFLOAT16" if dtype == "HALF" else None))

    rng = np.random.default_rng(7)
    batches = [{in_name: rng.integers(0, V, (B, T)).astype(np.int32),
                "targets": rng.integers(0, V, (B, T)).astype(np.int32)}
               for _ in range(steps)]
    return sd.fit(batches)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--rtol", type=float, default=0.02)
    args = ap.parse_args()

    h32 = np.asarray(run("FLOAT", args.steps))
    h16 = np.asarray(run("HALF", args.steps))
    rel = np.abs(h16 - h32) / np.maximum(np.abs(h32), 1e-9)
    out = {
        "steps": args.steps,
        "fp32_first_last": [round(float(h32[0]), 5), round(float(h32[-1]), 5)],
        "bf16_first_last": [round(float(h16[0]), 5), round(float(h16[-1]), 5)],
        "max_rel_diff": round(float(rel.max()), 5),
        "rtol": args.rtol,
        "pass": bool(rel.max() < args.rtol),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
