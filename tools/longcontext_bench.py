"""Long-context streamed flash-attention benchmark (the round-5 A/B harness).

Methodology (held constant across every variant so deltas are causal): 4
serially-chained layer applications inside ONE jit executable (output feeds
the next layer's q — residuals carry grad through the whole chain), grad
through the chain, T=8192 causal bf16, B=2 / H=12 / D=64 (the long-context
configuration). The chain amortizes per-dispatch host latency the same way
tools/attention_roofline.py does.

Prints one JSON report that names its device.
Usage: python tools/longcontext_bench.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

B, H, T, D = 2, 12, 8192, 64
CHAIN = 4
STEPS, WARMUP = 5, 2


def _time(fn, *args):
    for _ in range(WARMUP):
        out = fn(*args)
    jax.block_until_ready(out)
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = fn(*args)
        jax.block_until_ready(out)
        dts.append((time.perf_counter() - t0) / (STEPS * CHAIN))
    return sorted(dts)[1]


def _flops(fwd_bwd: bool) -> float:
    # causal halves the score volume; fwd = QK^T + PV = 4*B*H*T^2*D*0.5;
    # bwd recomputes s and adds dv/dp/ds->dq/dk dots ~ 2.5x fwd
    f = 4 * B * H * T * T * D * 0.5
    return f * 3.5 if fwd_bwd else f


def main():
    from deeplearning4j_tpu.profiler.profiler import device_record
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, JAX found {device_record()}")
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.1, jnp.bfloat16)
               for _ in range(3))
    g = jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.1, jnp.bfloat16)
    report = {"device": device_record(), "B": B, "H": H, "T": T, "D": D,
              "chain": CHAIN, "results": []}

    def add(name, sec, fwd_bwd):
        tf = _flops(fwd_bwd) / sec / 1e12
        report["results"].append(
            {"variant": name, "ms_per_layer": round(sec * 1e3, 3),
             "achieved_tflops": round(tf, 2)})
        print(f"  {name}: {sec*1e3:.2f} ms/layer  ->  {tf:.1f} TF/s",
              flush=True)

    def chain(apply):
        def fn(q, k, v):
            def body(i, acc):
                return apply(acc, k, v)
            return jax.lax.fori_loop(0, CHAIN, body, q)
        # analysis: ok recompile-risk — standalone bench/profiling harness: mints its own executables by design, never on a serving dispatch path
        return jax.jit(fn)

    def chain_grad(apply):
        def loss(q, k, v):
            def body(i, acc):
                return apply(acc, k, v)
            out = jax.lax.fori_loop(0, CHAIN, body, q)
            return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))
        # analysis: ok recompile-risk — standalone bench/profiling harness: mints its own executables by design, never on a serving dispatch path
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    for bq, bk in ((128, 128), (256, 256), (512, 512), (1024, 1024),
                   (1024, 512), (512, 1024), (2048, 512)):
        apply = lambda q, k, v, a=bq, b=bk: flash_attention(
            q, k, v, True, a, b)
        tag = f"bq{bq}_bk{bk}"
        add(f"streamed_fwd_{tag}", _time(chain(apply), q, k, v), False)
        add(f"streamed_fwdbwd_{tag}", _time(chain_grad(apply), q, k, v), True)

    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
