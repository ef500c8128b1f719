"""Per-component HBM-traffic / step-time breakdown of the flagship bench step.

VERDICT r3 task #1 demands either >=160k tok/s or "a committed per-op
HBM-traffic breakdown proving sustained bandwidth at the roofline". This
script produces that evidence two ways:

1. **XLA cost analysis** of the compiled train step (flops, bytes accessed)
   -> sustained HBM bandwidth = bytes / measured step time.
2. **Ablation timings**: recompile the step with one component neutered at a
   time (loss head -> mean(hidden); attention -> identity; fp32 softmax; no
   AdamW; fwd-only). The step-time delta attributes wall-clock to components
   far more honestly than eyeballing HLO, because it includes every fusion
   side effect.

Usage:  python tools/profile_flagship.py [--steps 10] [--out BASELINE_r4_profile.json]
Writes a JSON artifact (committed to the repo as the roofline proof).
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")


def _build(variant: str):
    """Return (step, params, opt_state, batch) for a named step variant."""
    import optax
    from deeplearning4j_tpu.models import (
        TransformerConfig, init_params)
    from deeplearning4j_tpu.models import bert as bert_mod

    # baseline == the shipped bench.py config (packed VMEM attention
    # kernel, fp32 softmax default) — keep these two in lockstep so the
    # committed artifact attributes the config the bench actually runs
    cfg = TransformerConfig(remat=False, attention_impl="flash")
    B, T = 96, 512
    if variant == "xla_attention":
        # round-3 shipped config: XLA fused attention, bf16 softmax
        cfg = TransformerConfig(remat=False, softmax_dtype=jnp.bfloat16)
    elif variant == "xla_softmax_fp32":
        # XLA attention with fp32 softmax — vs xla_attention isolates the
        # softmax dtype on the einsum path (attention impl held constant)
        cfg = TransformerConfig(remat=False, softmax_dtype=jnp.float32)
    elif variant == "kernel_softmax_bf16":
        # packed kernel with bf16 probabilities — vs baseline isolates
        # p_dtype on the kernel path (attention impl held constant)
        cfg = TransformerConfig(remat=False, attention_impl="flash",
                                softmax_dtype=jnp.bfloat16)

    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    tx = optax.adamw(1e-4, weight_decay=0.01)
    opt_state = tx.init(params)

    def ident_block(bp, x):
        # qkv + out-proj matmuls kept (FLOPs preserved), score matmuls +
        # softmax removed: isolates the (T,T) attention-interior cost
        h = bert_mod._layernorm(x, bp["ln1"])
        qkv = h @ bp["qkv"]["kernel"].astype(h.dtype) \
            + bp["qkv"]["bias"].astype(h.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        o = q + k + v
        x = x + o @ bp["attn_out"]["kernel"].astype(o.dtype) \
            + bp["attn_out"]["bias"].astype(o.dtype)
        h = bert_mod._layernorm(x, bp["ln2"])
        h = h @ bp["mlp_in"]["kernel"].astype(h.dtype) \
            + bp["mlp_in"]["bias"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=True)
        return x + h @ bp["mlp_out"]["kernel"].astype(h.dtype) \
            + bp["mlp_out"]["bias"].astype(h.dtype)

    def loss_fn(p, batch):
        # ablations reuse bert.encode/loss_from_logits so they cannot
        # desynchronize from the real forward/loss
        if variant == "no_losshead":
            x = bert_mod.encode(p, batch["tokens"], cfg, None)
            return x.astype(jnp.float32).mean()
        if variant == "no_attention":
            x = bert_mod.encode(p, batch["tokens"], cfg, None,
                                block_fn=ident_block)
            with jax.default_matmul_precision("default"):
                logits = x @ p["lm_head"].astype(x.dtype)
            return bert_mod.loss_from_logits(logits, batch)
        return bert_mod.lm_loss(p, batch, cfg, None)

    if variant == "fwd_only":
        def step(p, s, batch):
            return p, s, loss_fn(p, batch)
    elif variant == "no_adamw":
        def step(p, s, batch):
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            # sgd in place of adamw: isolates optimizer-state traffic
            p = jax.tree.map(lambda a, g: a - 1e-4 * g, p, grads)
            return p, s, loss
    else:
        def step(p, s, batch):
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            updates, s = tx.update(grads, s, p)
            import optax as _o
            p = _o.apply_updates(p, updates)
            return p, s, loss

    # analysis: ok recompile-risk — standalone bench/profiling harness: mints its own executables by design, never on a serving dispatch path
    jstep = jax.jit(step, donate_argnums=(0, 1))

    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
        "weights": jnp.ones((B, T), jnp.float32),
    }
    # analytic flops/token from the LIVE param pytree + the actual T (the
    # same shared helpers as bench.py — derived, not hand-expanded, so it
    # cannot drift from the step _build actually runs)
    from deeplearning4j_tpu.profiler.profiler import (
        non_embedding_params, transformer_flops_per_token)
    fpt = transformer_flops_per_token(
        non_embedding_params(params, cfg), cfg.layers, cfg.hidden, T)
    return jstep, params, opt_state, batch, B * T, fpt


def _time_variant(variant: str, steps: int, warmup: int = 3):
    jstep, params, opt_state, batch, ntok, fpt = _build(variant)
    lowered = jstep.lower(params, opt_state, batch)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))

    for _ in range(warmup):
        params, opt_state, loss = jstep(params, opt_state, batch)
    jax.block_until_ready(loss)
    # median of 3 windows, mirroring bench.py: per-window noise would
    # otherwise masquerade as variant deltas
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = jstep(params, opt_state, batch)
        jax.block_until_ready(loss)
        dts.append((time.perf_counter() - t0) / steps)
    dt = sorted(dts)[1]
    # both MFU bases side by side (round-5 verdict #5): the headline uses
    # the analytic basis (profiler.MFU_BASIS, same as bench.py, computed
    # from the live params in _build); mfu_xla divides XLA's implementation-
    # flop count by peak — a few points lower is expected, not a discrepancy
    from deeplearning4j_tpu.profiler.profiler import mfu as _mfu, peak_flops
    peak = peak_flops(jax.devices()[0])
    row = {
        "variant": variant,
        "step_ms": round(dt * 1e3, 2),
        "tokens_per_sec": round(ntok / dt, 0),
        "xla_flops": flops,
        "xla_bytes_accessed": bytes_acc,
        "sustained_gbps": round(bytes_acc / dt / 1e9, 1),
        "achieved_tflops": round(flops / dt / 1e12, 2),
        "mfu_xla": round(flops / dt / peak, 4),
    }
    if variant in ("baseline", "xla_attention", "xla_softmax_fp32",
                   "kernel_softmax_bf16"):
        # analytic MFU only where the variant runs the FULL train step —
        # ablated steps do fewer model flops than the analytic count assumes
        row["mfu_analytic"] = round(_mfu(ntok / dt, fpt, peak), 4)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--variants", default="baseline,xla_attention,fwd_only,no_losshead,no_attention,no_adamw,xla_softmax_fp32,kernel_softmax_bf16")
    args = ap.parse_args()
    from deeplearning4j_tpu.profiler.profiler import device_record
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = []
    for v in args.variants.split(","):
        r = _time_variant(v.strip(), args.steps)
        results.append(r)
        print(json.dumps(r), flush=True)

    base = next((r for r in results if r["variant"] == "baseline"), None)
    summary = {"device": device_record(), "results": results}
    if base:
        deltas = {}
        for r in results:
            if r["variant"] != "baseline":
                deltas[r["variant"]] = {
                    "step_ms_delta": round(base["step_ms"] - r["step_ms"], 2),
                    "bytes_delta_gb": round(
                        (base["xla_bytes_accessed"] - r["xla_bytes_accessed"]) / 1e9, 2),
                }
        summary["deltas_vs_baseline"] = deltas
    print(json.dumps(summary.get("deltas_vs_baseline", {}), indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)


if __name__ == "__main__":
    main()
