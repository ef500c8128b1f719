"""Headline benchmark: BERT-base masked-LM training throughput on one chip,
plus a continuous-batching decode leg (serving/generation.py).

Mirrors BASELINE.json's metric ("SameDiff BERT-base tokens/sec/chip"): the
reference runs this workload through the SameDiff op-by-op JVM interpreter;
here it is one fused XLA executable (fwd+bwd+AdamW, bf16 compute, no remat —
activations fit HBM at bench shapes and recompute cost ~15% throughput).

Prints ONE JSON line: {"metric", "value", "unit", "device", "vs_baseline",
"decode", "availability", ...}. ``device`` names what the run used
(platform, ``device_kind``, count) and covers every leg in the line.
``vs_baseline`` is measured MFU / 0.35 (the north-star gate from
BASELINE.json) since the reference publishes no in-tree numbers (SURVEY.md
§6, BASELINE "published": {}). Off a TPU the run is a smoke of the control
flow on a toy model: it prints under another metric name, with no MFU and
no ``vs_baseline`` — a CPU number is never a per-chip number. ``decode``
reports the GenerationEngine's steady-state numbers: decode tokens/sec across all
slots, median time-to-first-token, slot occupancy at steady state, the
compiled-signature count (must stay ≤ prefill ladder + 1), the paged
KV-cache capacity roll-up (HBM bytes per resident stream vs the
contiguous layout, block utilization) and the ``shared_prefix``
scenario (N streams over one registered prefix — one prefill total).
``availability`` is the resilience leg: success rate and p99 latency under
a fixed seeded FaultPlan injecting 5% transient dispatch failures.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from deeplearning4j_tpu.models import (
        TransformerConfig, init_params, make_train_step)
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # BERT-base 12L/768H/12 heads/512 seq. remat off: activations fit a
        # single chip's HBM at B=48 and recompute costs ~15% throughput
        # (measured: 117k tok/s no-remat vs 100k dots-remat vs 96k full).
        # attention_impl='flash' routes to the packed whole-head VMEM Pallas
        # kernel (fwd+bwd on-chip, no (T,T) HBM traffic, no head
        # transposes) — the round-4 lever that broke the round-2/3 HBM
        # plateau (tools/profile_flagship.py: the XLA attention score path
        # was 67 ms of the 182 ms step). softmax stays fp32: the kernel's
        # bf16 p_dtype saves VPU time standalone but the full step hides it
        # under DMA (measured parity), so exactness is free. B=96: with the
        # kernel, throughput rises past the old B=48 plateau (B sweep:
        # 48 -> 163k, 96 -> 172k, 128 -> 160k).
        cfg = TransformerConfig(remat=False, attention_impl="flash")
        B, T, steps, warmup = 96, 512, 10, 3
    else:               # smoke of the control flow on a toy model: its
        #                 numbers are labelled as such below, never per-chip
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2, heads=4,
                                mlp_dim=512, max_seq=128, dtype=jnp.float32,
                                remat=False)
        B, T, steps, warmup = 8, 128, 3, 1

    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    init_state, step = make_train_step(cfg, learning_rate=1e-4)
    opt_state = init_state(params)

    rng = np.random.default_rng(0)
    def make_batch():
        return {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            "weights": jnp.ones((B, T), jnp.float32),
        }

    batch = make_batch()
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)

    # median of 3 timing windows, each closed by block_until_ready
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        dts.append(time.perf_counter() - t0)
    dt = sorted(dts)[1]

    tokens_per_sec = B * T * steps / dt

    from deeplearning4j_tpu.profiler.profiler import device_record

    headline = {"value": round(tokens_per_sec, 2), "unit": "tokens/sec",
                "device": device_record()}
    if on_tpu:
        # MFU on the repo-wide single basis (profiler.MFU_BASIS): analytic
        # model flops, no remat recompute at bench config, against the
        # published peak of this device_kind (unknown kind raises).
        # XLA-counted flops for the same step are tools/profile_flagship.py's
        # mfu_xla.
        from deeplearning4j_tpu.profiler.profiler import (
            MFU_BASIS, mfu as _mfu, non_embedding_params, peak_flops,
            transformer_flops_per_token)
        flops_per_token = transformer_flops_per_token(
            non_embedding_params(params, cfg), cfg.layers, cfg.hidden, T)
        mfu = _mfu(tokens_per_sec, flops_per_token,
                   peak_flops(jax.devices()[0]))
        headline.update(
            metric="bert_base_mlm_train_tokens_per_sec_per_chip",
            mfu=round(mfu, 4), mfu_basis=MFU_BASIS,
            vs_baseline=round(mfu / 0.35, 4),
            vs_baseline_basis="mfu / 0.35 north-star gate (BASELINE.json)")
    else:
        headline.update(
            metric=f"toy_transformer_train_tokens_per_sec_"
                   f"{headline['device']['platform']}_smoke",
            note="control-flow smoke off the TPU on a 2-layer toy model: "
                 "not a device measurement; MFU and vs_baseline not "
                 "measured")

    print(json.dumps({
        **headline,
        "decode": decode_leg(on_tpu),
        "availability": availability_leg(on_tpu),
        "observability": observability_leg(on_tpu),
        "fairness": fairness_leg(on_tpu),
        "cluster": cluster_leg(on_tpu),
        "soak": soak_leg(on_tpu),
    }))


def decode_leg(on_tpu: bool) -> dict:
    """Continuous-batching decode throughput: saturate every slot of one
    GenerationEngine with staggered prompts (the ORCA regime — admissions
    and retirements interleave with decode iterations) and report the
    scheduler's sustained rate. Decode tokens/sec is summed across slots:
    one decode_step samples a token for EVERY live slot, which is exactly
    why iteration-level scheduling wins over request-level batching.

    The KV roll-up is the paged-cache capacity story (vLLM SOSP'23): a
    resident stream holds ceil((len+max_new)/block) blocks instead of a
    worst-case max_len row, so at the contiguous layout's HBM budget the
    pool seats `resident_streams_at_contiguous_budget` streams — the
    chat-shaped prompt mix (lengths well under max_len) is where paging
    earns its keep. `shared_prefix` is the CoW scenario: N streams over
    one 256-token registered prefix, ONE prefix prefill total."""
    from deeplearning4j_tpu.models import (
        TransformerConfig, init_params)
    from deeplearning4j_tpu.serving import GenerationEngine

    if on_tpu:
        cfg = TransformerConfig(causal=True, remat=False,
                                attention_impl="flash")
        slots, max_len, n_requests, max_new = 16, 512, 48, 64
    else:                                   # CPU smoke (driver runs TPU)
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                heads=4, mlp_dim=512, max_seq=128,
                                dtype=jnp.float32, causal=True, remat=False)
        slots, max_len, n_requests, max_new = 4, 64, 8, 12

    params = init_params(jax.random.PRNGKey(0), cfg)
    with GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                          queue_capacity=n_requests + slots) as eng:
        stats, paged_stream_bytes = _run_decode_mix(eng, cfg, n_requests,
                                                    max_new)
        from deeplearning4j_tpu.serving import kv_bytes_per_token
        itemsize = jnp.dtype(cfg.dtype).itemsize
        contig_stream_bytes = max_len * kv_bytes_per_token(
            cfg.layers, cfg.heads, cfg.head_dim, "float32", itemsize)
        measured = paged_stream_bytes is not None
        return {
            **stats,
            "slots": slots,
            "requests": n_requests,
            "max_new_tokens": max_new,
            "block_size": eng.block_size,
            "kv_blocks_total": eng._allocator.capacity,
            "steady_state_block_utilization": round(
                stats["steady_state_blocks_in_use"]
                / eng._allocator.capacity, 4),
            "kv_bytes_per_stream_contiguous": contig_stream_bytes,
            "kv_bytes_per_stream_ratio": round(
                paged_stream_bytes / contig_stream_bytes, 4)
                if measured else None,
            "resident_streams_at_contiguous_budget": int(
                slots * contig_stream_bytes // paged_stream_bytes)
                if measured else None,
            "paged_grid": paged_decode_grid(on_tpu),
            "speculative": speculative_grid(on_tpu),
            "shared_prefix": shared_prefix_scenario(on_tpu),
            "occupancy": occupancy_leg(on_tpu),
        }


def _run_decode_mix(eng, cfg, n_requests: int, max_new: int):
    """THE decode measurement harness, shared by :func:`decode_leg` and
    every :func:`paged_decode_grid` cell so the two can never drift:
    warm the engine, reset metrics (warmup's samples include the
    one-time XLA compiles, which would swamp the steady-state numbers —
    the engine is idle here, so the swap cannot race a live stream),
    submit the seeded chat-shaped mix (prompts well under max_len: the
    regime where block-granular storage beats worst-case reservation),
    sample the occupancy/block gauges while the backlog drains (sampling
    at submit time would race the scheduler's admissions; first sample
    unconditional — on a device fast enough to drain before the first
    5 ms poll the loop body would never run and the capacity numbers
    would be built from nothing), and join every stream.

    Returns ``(stats, stream_bytes)`` — the common steady-state dict
    plus HBM bytes per resident stream, ``None`` when unmeasured (all
    samples post-drain): better no number than a 0-byte stream or an
    absurd streams-at-budget figure."""
    from deeplearning4j_tpu.serving import ServingMetrics

    eng.warmup()
    eng.metrics = ServingMetrics()
    eng.metrics.kv_blocks_total.set(eng._allocator.capacity)
    rng = np.random.default_rng(0)       # same mix for every caller
    t0 = time.perf_counter()
    handles = []
    for _ in range(n_requests):
        n = int(rng.integers(4, max(5, eng.max_len // 4)))
        handles.append(eng.submit(
            rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=max_new))
    occ_samples, blk_samples = [], []
    while True:
        occ_samples.append(eng.metrics.slot_occupancy.value)
        blk_samples.append(eng.metrics.kv_blocks_in_use.value)
        if handles[-1].future.done():
            break
        time.sleep(0.005)
    for h in handles:
        h.result(timeout=600)
    wall_s = time.perf_counter() - t0
    m = eng.metrics
    occ = float(np.median(occ_samples))
    blocks_in_use = float(np.median(blk_samples))
    resident = occ * eng.slots
    measured = blocks_in_use > 0 and resident > 0
    stream_bytes = (blocks_in_use * eng.kv_block_bytes / resident
                    if measured else None)
    stats = {
        "decode_tokens_per_sec": round(m.decode_tokens_per_sec(), 2),
        "end_to_end_tokens_per_sec": round(
            n_requests * max_new / wall_s, 2),
        "ttft_ms_p50": round(m.ttft_ms.quantile(0.5), 3),
        "decode_step_ms_p50": round(m.decode_step_ms.quantile(0.5), 3),
        "steady_state_slot_occupancy": round(occ, 3),
        "compiled_signatures": eng.compiled_signatures(),
        "signature_bound": len(eng.buckets) + 1,
        "steady_state_blocks_in_use": round(blocks_in_use, 1),
        "kv_hbm_bytes_per_resident_stream":
            round(stream_bytes) if measured else None,
    }
    return stats, stream_bytes


def paged_decode_grid(on_tpu: bool) -> dict:
    """The decode hot-path grid (ROADMAP 1b/1c + 3b/3c): the SAME
    staggered prompt mix through {gather, fused} attention x {float32,
    int8} KV storage. ``gather`` materializes pool[tables] in HBM every
    step (the PR 6 route); ``fused`` streams blocks through VMEM via the
    Pallas paged-attention kernel, never building the (slots, L) view.
    int8 quantizes on write / dequantizes in the read, shrinking the
    per-stream KV footprint — ``resident_streams_at_contiguous_budget``
    is the capacity headline: how many streams fit the contiguous
    full-precision layout's HBM budget *in the model's cache dtype*
    (the int8 cells compound the dtype ratio — ~3.8x vs fp32 storage,
    ~1.9x vs bf16 — with block granularity, which is how the >=2x ISSUE
    acceptance gate clears under either storage dtype). Tokens/sec and
    TTFT p50 are reported at the fixed occupancy the shared mix
    produces, so the four cells are directly comparable."""
    from deeplearning4j_tpu.models import TransformerConfig, init_params
    from deeplearning4j_tpu.serving import (
        GenerationEngine, kv_bytes_per_token)

    if on_tpu:
        cfg = TransformerConfig(causal=True, remat=False,
                                attention_impl="flash")
        slots, max_len, n_requests, max_new = 16, 512, 32, 64
    else:                                   # CPU smoke (driver runs TPU)
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                heads=4, mlp_dim=512, max_seq=128,
                                dtype=jnp.float32, causal=True, remat=False)
        slots, max_len, n_requests, max_new = 2, 64, 4, 6

    params = init_params(jax.random.PRNGKey(0), cfg)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    contig_stream_bytes = max_len * kv_bytes_per_token(
        cfg.layers, cfg.heads, cfg.head_dim, "float32", itemsize)

    def cell(kv_dtype: str, paged_attention: str) -> dict:
        with GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                              kv_dtype=kv_dtype,
                              paged_attention=paged_attention,
                              queue_capacity=n_requests + slots) as eng:
            stats, stream_bytes = _run_decode_mix(eng, cfg, n_requests,
                                                  max_new)
            return {
                "kv_dtype": kv_dtype,
                "paged_attention": paged_attention,
                **stats,
                "kv_block_bytes": eng.kv_block_bytes,
                "resident_streams_at_contiguous_budget": int(
                    slots * contig_stream_bytes // stream_bytes)
                    if stream_bytes is not None else None,
            }

    grid = [cell(kv, pa) for kv in ("float32", "int8")
            for pa in ("gather", "fused")]
    return {
        "slots": slots, "max_len": max_len, "requests": n_requests,
        "max_new_tokens": max_new,
        "kv_bytes_per_stream_contiguous_fp": contig_stream_bytes,
        "cells": grid,
    }


def speculative_grid(on_tpu: bool) -> dict:
    """Speculative decoding tokens/sec vs k (ISSUE 17): the SAME staggered
    mix as :func:`paged_decode_grid`, through k in {0, 2, 4, 8} x {gather,
    fused} x {float32, int8}. k=0 is the plain engine (``speculative=
    None``) — the per-(route, dtype) baseline the k>0 cells must beat.

    The draft is a 1-layer model at half the target's width, so its
    per-proposal cost is a fraction of a target decode step — the real
    deployment economics. To pin the acceptance regime the grid zeroes
    ``lm_head`` in BOTH models: logits are identically 0, greedy sampling
    picks the same argmax on both sides, and acceptance is deterministically
    1.0 — the ceiling cells show the pure scheduling win (one verify
    commits k tokens), while ``acceptance_rate`` in each cell keeps the
    headline honest about the regime it was measured in. Determinism means
    the grid needs no warm-up repetitions to be reproducible."""
    from deeplearning4j_tpu.models import TransformerConfig, init_params
    from deeplearning4j_tpu.serving import GenerationEngine, SpecConfig

    if on_tpu:
        cfg = TransformerConfig(causal=True, remat=False,
                                attention_impl="flash")
        dcfg = TransformerConfig(hidden=cfg.hidden // 2, layers=1,
                                 heads=cfg.heads, mlp_dim=cfg.mlp_dim // 2,
                                 vocab_size=cfg.vocab_size,
                                 max_seq=cfg.max_seq, causal=True,
                                 remat=False, attention_impl="flash")
        slots, max_len, n_requests, max_new = 16, 512, 32, 64
    else:                                   # CPU smoke (driver runs TPU)
        # the draft/target cost gap is the whole economics: a 1-layer
        # thin draft against a deep target, so k cheap proposals replace
        # k expensive decode dispatches with ONE (k+1)-position verify
        cfg = TransformerConfig(vocab_size=1024, hidden=256, layers=4,
                                heads=4, mlp_dim=1024, max_seq=128,
                                dtype=jnp.float32, causal=True, remat=False)
        dcfg = TransformerConfig(vocab_size=1024, hidden=32, layers=1,
                                 heads=2, mlp_dim=64, max_seq=128,
                                 dtype=jnp.float32, causal=True,
                                 remat=False)
        slots, max_len, n_requests, max_new = 2, 64, 4, 24

    params = init_params(jax.random.PRNGKey(0), cfg)
    dparams = init_params(jax.random.PRNGKey(1), dcfg)
    # acceptance-1.0 regime: identical (zero) logits on both sides
    params = {**params, "lm_head": jnp.zeros_like(params["lm_head"])}
    dparams = {**dparams, "lm_head": jnp.zeros_like(dparams["lm_head"])}

    def cell(k: int, kv_dtype: str, paged_attention: str) -> dict:
        spec = SpecConfig(dparams, dcfg, k=k) if k > 0 else None
        with GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                              kv_dtype=kv_dtype,
                              paged_attention=paged_attention,
                              queue_capacity=n_requests + slots,
                              speculative=spec) as eng:
            stats, _ = _run_decode_mix(eng, cfg, n_requests, max_new)
            m = eng.metrics
            return {
                "k": k, "kv_dtype": kv_dtype,
                "paged_attention": paged_attention,
                "tokens_per_sec": stats["end_to_end_tokens_per_sec"],
                "decode_steps_total": m.decode_steps_total.value,
                "acceptance_rate": round(m.spec_acceptance_rate.value, 4)
                    if k > 0 else None,
                "compiled_signatures": stats["compiled_signatures"],
                "signature_bound": len(eng.buckets) + (2 if k > 0 else 1),
                "draft_compiled_signatures":
                    eng.draft_compiled_signatures(),
            }

    grid = [cell(k, kv, pa) for kv in ("float32", "int8")
            for pa in ("gather", "fused") for k in (0, 2, 4, 8)]
    # the ISSUE acceptance gate: at least one k>0 cell beats its own
    # (route, dtype) k=0 baseline on tokens/sec at high acceptance
    base = {(c["kv_dtype"], c["paged_attention"]): c["tokens_per_sec"]
            for c in grid if c["k"] == 0}
    speedups = [round(c["tokens_per_sec"]
                      / base[(c["kv_dtype"], c["paged_attention"])], 3)
                for c in grid if c["k"] > 0]
    return {
        "slots": slots, "max_len": max_len, "requests": n_requests,
        "max_new_tokens": max_new,
        "draft": {"hidden": dcfg.hidden, "layers": dcfg.layers,
                  "mlp_dim": dcfg.mlp_dim},
        "cells": grid,
        "best_speedup_vs_k0": max(speedups) if speedups else None,
    }


def occupancy_leg(on_tpu: bool) -> dict:
    """KV occupancy → 1.0 (ISSUE 13): the SAME chat-shaped mix — a
    shared system prompt plus short unique suffixes, generation budgets
    well past the prompt — through ``allocate="reserve"`` (worst-case
    reservation up front, the pre-existing default) and
    ``allocate="on_demand"`` + the automatic prefix cache (lazy
    per-boundary allocation, QoS-aware preemption with
    recompute-on-resume, retired full blocks reused with no API
    opt-in). Both cells run int8 KV storage, so the on-demand cell
    COMPOUNDS with the PR 9 dtype lever: ``kv_reservation_slack`` is
    the idle tail reserve pays and on-demand recovers,
    ``preemptions_per_1k_tokens`` the recompute price of running the
    pool near occupancy 1.0, ``prefix_cache_hit_rate`` the free
    admissions shared system prompts get, and
    ``resident_streams_at_contiguous_budget`` the capacity headline on
    the same contiguous-fp32-budget basis as the decode grid (the ISSUE
    acceptance gate: >= 1.5x the grid's int8 reserve figure)."""
    from deeplearning4j_tpu.models import TransformerConfig, init_params
    from deeplearning4j_tpu.serving import (
        GenerationEngine, ServingMetrics, blocks_for_tokens,
        kv_bytes_per_token)

    if on_tpu:
        cfg = TransformerConfig(causal=True, remat=False,
                                attention_impl="flash")
        slots, max_len, block, n_requests = 16, 512, 16, 48
        sys_len, sfx_hi, max_new, cache_blocks = 64, 16, 192, 64
    else:                                   # CPU smoke (driver runs TPU)
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                heads=4, mlp_dim=512, max_seq=128,
                                dtype=jnp.float32, causal=True, remat=False)
        slots, max_len, block, n_requests = 4, 64, 8, 16
        sys_len, sfx_hi, max_new, cache_blocks = 16, 8, 24, 8
    # pool deliberately SMALLER than slots * worst-case: reserve can
    # only seat slots-1 streams at once, on_demand seats every slot and
    # preempts when the pool runs dry — the occupancy-1.0 regime under
    # test, where preemptions/1k-tokens prices the recompute debt
    num_blocks = (slots - 1) * blocks_for_tokens(
        sys_len + sfx_hi + max_new, block) + 1

    params = init_params(jax.random.PRNGKey(0), cfg)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    contig_stream_bytes = max_len * kv_bytes_per_token(
        cfg.layers, cfg.heads, cfg.head_dim, "float32", itemsize)

    def cell(allocate: str, prefix_cache_blocks: int) -> dict:
        with GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                              block_size=block, num_blocks=num_blocks,
                              kv_dtype="int8", allocate=allocate,
                              prefix_cache_blocks=prefix_cache_blocks,
                              queue_capacity=n_requests + slots) as eng:
            eng.warmup()
            eng.metrics = ServingMetrics()  # exclude warmup compiles
            eng.metrics.kv_blocks_total.set(eng._allocator.capacity)
            rng = np.random.default_rng(0)  # same mix in both cells
            sysp = rng.integers(0, cfg.vocab_size, sys_len)
            handles = []
            t0 = time.perf_counter()
            for _ in range(n_requests):
                sfx = rng.integers(0, cfg.vocab_size,
                                   int(rng.integers(2, sfx_hi)))
                handles.append(eng.submit(
                    np.concatenate([sysp, sfx]).astype(np.int32),
                    max_new_tokens=max_new, eos_id=None))
            occ, blk, slack, socc, cblk = [], [], [], [], []
            steady = []
            while True:
                sample = (eng.metrics.kv_block_occupancy.value,
                          eng.metrics.kv_blocks_in_use.value,
                          eng.metrics.kv_reservation_slack.value,
                          eng.metrics.slot_occupancy.value,
                          eng.metrics.prefix_cache_blocks.value)
                for xs, v in zip((occ, blk, slack, socc, cblk), sample):
                    xs.append(v)
                if eng.queue_depth > 0 and sample[3] > 0:
                    # TRUE steady state: every seat contested (a backlog
                    # exists) — drain-edge samples with idling slots
                    # would skew the per-stream footprint
                    steady.append(sample)
                if handles[-1].future.done():
                    break
                time.sleep(0.005)
            if len(steady) >= 3:
                occ, blk, slack, socc, cblk = (list(x)
                                               for x in zip(*steady))
            for h in handles:
                h.result(timeout=600)
            wall_s = time.perf_counter() - t0
            m = eng.metrics
            blocks_in_use = float(np.median(blk))
            resident = float(np.median(socc)) * slots
            tokens_out = m.generated_tokens_total.value
            # per-stream attribution excludes blocks held ONLY by the
            # automatic prefix cache: they are reclaimable-on-demand
            # shared capacity (evicted the moment a stream needs them),
            # not residency — the same reason kv_blocks_usable ignores
            # them in the heartbeat
            stream_blocks = max(0.0, blocks_in_use - float(np.median(cblk)))
            stream_bytes = None
            if stream_blocks > 0 and resident > 0:
                stream_bytes = stream_blocks * eng.kv_block_bytes \
                    / resident
            return {
                "allocate": allocate,
                "prefix_cache_blocks": prefix_cache_blocks,
                "steady_state_pool_occupancy": round(
                    float(np.median(occ)), 4),
                "steady_state_blocks_in_use": round(blocks_in_use, 1),
                "kv_reservation_slack_blocks": round(
                    float(np.median(slack)), 1),
                "preemptions": int(m.preemptions_total.value),
                "preemptions_per_1k_tokens": round(
                    1e3 * m.preemptions_total.value / tokens_out, 3)
                    if tokens_out else None,
                "prefix_cache_hits": int(m.prefix_cache_hits_total.value),
                "prefix_cache_hit_rate": round(
                    m.prefix_cache_hits_total.value / n_requests, 3),
                "decode_tokens_per_sec": round(
                    m.decode_tokens_per_sec(), 2),
                "end_to_end_tokens_per_sec": round(
                    n_requests * max_new / wall_s, 2),
                "kv_hbm_bytes_per_resident_stream":
                    round(stream_bytes) if stream_bytes else None,
                "resident_streams_at_contiguous_budget": int(
                    slots * contig_stream_bytes // stream_bytes)
                    if stream_bytes else None,
                "compiled_signatures": eng.compiled_signatures(),
                "signature_bound": len(eng.buckets) + 1,
            }

    reserve = cell("reserve", 0)
    on_demand = cell("on_demand", cache_blocks)
    r0 = reserve.get("resident_streams_at_contiguous_budget")
    r1 = on_demand.get("resident_streams_at_contiguous_budget")
    return {
        "slots": slots, "max_len": max_len, "block_size": block,
        "requests": n_requests, "system_prompt_tokens": sys_len,
        "max_new_tokens": max_new,
        "kv_bytes_per_stream_contiguous_fp": contig_stream_bytes,
        "reserve": reserve,
        "on_demand": on_demand,
        "on_demand_vs_reserve_streams_ratio": (
            round(r1 / r0, 3) if r0 and r1 else None),
    }


def shared_prefix_scenario(on_tpu: bool) -> dict:
    """Copy-on-write prefix reuse: N streams share ONE registered
    prefix (a 256-token system prompt at TPU scale). The prefix is
    prefilled exactly once — every stream references its pinned blocks
    (the partial tail block via CoW) and feeds only its short suffix
    through the decode executable, so TTFT stops paying the long-prefix
    prefill N times and the pool stops storing it N times."""
    from deeplearning4j_tpu.models import (
        TransformerConfig, init_params)
    from deeplearning4j_tpu.serving import GenerationEngine, ServingMetrics

    if on_tpu:
        cfg = TransformerConfig(causal=True, remat=False,
                                attention_impl="flash")
        slots, max_len, n_streams = 16, 512, 48
        prefix_len, suffix_len, max_new = 256, 8, 32
    else:                                   # CPU smoke (driver runs TPU)
        cfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                heads=4, mlp_dim=512, max_seq=128,
                                dtype=jnp.float32, causal=True, remat=False)
        slots, max_len, n_streams = 8, 128, 32
        prefix_len, suffix_len, max_new = 64, 4, 8

    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    with GenerationEngine(params, cfg, slots=slots, max_len=max_len,
                          queue_capacity=n_streams + slots) as eng:
        eng.warmup()
        eng.metrics = ServingMetrics()      # exclude warmup compiles
        t0 = time.perf_counter()
        pid = eng.register_prefix(prefix)
        handles = [eng.submit(
            rng.integers(0, cfg.vocab_size, suffix_len).astype(np.int32),
            prefix_id=pid, max_new_tokens=max_new)
            for _ in range(n_streams)]
        for h in handles:
            h.result(timeout=600)
        wall_s = time.perf_counter() - t0
        m = eng.metrics
        itemsize = jnp.dtype(cfg.dtype).itemsize
        kv_unit = cfg.layers * 2 * cfg.heads * cfg.head_dim * itemsize
        return {
            "streams": n_streams,
            "prefix_tokens": prefix_len,
            "suffix_tokens": suffix_len,
            "max_new_tokens": max_new,
            "prefix_prefills": int(m.prefix_prefills_total.value),
            "stream_prefills": int(m.prefills_total.value),
            "one_prefill_for_all_streams":
                int(m.prefix_prefills_total.value) == 1
                and int(m.prefills_total.value) == 0,
            "prefix_hits": int(m.prefix_hits_total.value),
            "cow_copies": int(m.kv_cow_copies_total.value),
            "ttft_ms_p50": round(m.ttft_ms.quantile(0.5), 3),
            "end_to_end_tokens_per_sec": round(
                n_streams * max_new / wall_s, 2),
            "prefix_kv_bytes_stored_once": prefix_len * kv_unit,
            "prefix_kv_bytes_without_sharing":
                n_streams * prefix_len * kv_unit,
        }


def _tiny_mlp_adapter():
    """Tiny jitted row-wise model shared by the availability and
    observability legs: both measure the serving machinery around the
    dispatch, not the network."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import ModelAdapter

    class _Mlp(ModelAdapter):
        def __init__(self):
            import jax
            super().__init__(model=None)
            w = jax.random.normal(jax.random.PRNGKey(0), (16, 16),
                                  jnp.float32)
            self._fn = jax.jit(lambda x: jnp.tanh(x @ w))

        def infer(self, x):
            return np.asarray(self._fn(jnp.asarray(x, jnp.float32)))

    return _Mlp()


def availability_leg(on_tpu: bool) -> dict:
    """Availability under injected faults: drive the batching engine with a
    fixed seeded FaultPlan failing 5% of ``engine.dispatch`` calls
    transiently, and report the success rate and p99 latency the retry
    layer sustains. The plan is seeded, so this leg is the same fault
    schedule on every run — a regression here is a resilience regression,
    not noise. (The train/decode legs above run with NO plan installed,
    which is the FaultPlan-inactive overhead condition: one global read
    per dispatch.)"""
    from deeplearning4j_tpu.serving import (
        FaultPlan, InferenceEngine, RetryPolicy)

    n_requests = 400 if on_tpu else 120
    fault_rate = 0.05
    # 5% Bernoulli background failures PLUS fixed early call indices: the
    # dispatch count varies with coalescing, so the at= anchors guarantee
    # the retry path is exercised every run (>=15 dispatches at
    # max_batch_size=8 for 120 single-row requests)
    plan = (FaultPlan(seed=0)
            .fail("engine.dispatch", rate=fault_rate)
            .fail("engine.dispatch", at=(1, 3, 7, 11)))
    with InferenceEngine(
            _tiny_mlp_adapter(), max_batch_size=8, max_wait_ms=1.0,
            retry_policy=RetryPolicy(max_attempts=4, base_delay_ms=0.5,
                                     max_delay_ms=8.0, seed=0),
            name="availability") as eng:
        eng.warmup(np.zeros(16, np.float32))
        from deeplearning4j_tpu.serving import ServingMetrics
        eng.metrics = ServingMetrics()   # exclude warmup compiles from p99
        rng = np.random.default_rng(0)
        ok = 0
        with plan:
            futures = [eng.submit(
                rng.standard_normal((1, 16)).astype(np.float32))
                       for _ in range(n_requests)]
            for f in futures:
                try:
                    f.result(timeout=120)
                    ok += 1
                except Exception:
                    pass
        m = eng.metrics
        return {
            "injected_fault_rate": fault_rate,
            "injection_point": "engine.dispatch",
            "requests": n_requests,
            "success_rate": round(ok / n_requests, 4),
            "latency_ms_p99": round(m.latency_ms.quantile(0.99), 3),
            "retries": int(m.retries_total.value),
            "faults_fired": len(plan.fired()),
            "breaker_state": eng.breaker.state,
        }


def observability_leg(on_tpu: bool) -> dict:
    """Tracing overhead: the same seeded traffic through one batching
    engine with request tracing OFF (the default — the zero-allocation
    NULL_TRACE fast path) and again at 100% tail-sampling retention, so
    the "zero cost when off / cheap when on" claim is a tracked number.
    Reports throughput and p99 latency for both conditions plus the
    throughput delta; ``overhead_pct_throughput`` should sit within noise
    of zero for the off condition to hold (it is measured against the
    SAME workload as the PR 3 availability leg, minus the fault plan)."""
    from deeplearning4j_tpu.serving import (
        InferenceEngine, ServingMetrics, Tracer)

    n_requests = 400 if on_tpu else 120

    def run(tracer):
        # median of 3 windows per condition, and max_wait_ms=0 (greedy
        # batch sealing): with a batching window, tiny producer-side
        # timing shifts change how requests coalesce and the window
        # lottery swamps the ~10 us/request tracing cost this leg exists
        # to measure
        with InferenceEngine(
                _tiny_mlp_adapter(), max_batch_size=8, max_wait_ms=0.0,
                queue_capacity_rows=n_requests + 8, tracer=tracer,
                name="observability") as eng:
            eng.warmup(np.zeros(16, np.float32))
            rng = np.random.default_rng(0)
            xs = [rng.standard_normal((1, 16)).astype(np.float32)
                  for _ in range(n_requests)]
            dts = []
            for _ in range(3):
                eng.metrics = ServingMetrics()  # exclude warmup compiles
                t0 = time.perf_counter()
                futures = [eng.submit(x) for x in xs]
                for f in futures:
                    f.result(timeout=120)
                dts.append(time.perf_counter() - t0)
            dt = sorted(dts)[1]
            return {
                "requests_per_sec": round(n_requests / dt, 2),
                "latency_ms_p99": round(
                    eng.metrics.latency_ms.quantile(0.99), 3),
            }

    # alternate conditions and keep each condition's best window: the
    # first engine of the process pays one-time thread/allocator warmup
    # that would otherwise be billed to whichever condition ran first
    tracer = Tracer(sample_rate=1.0, capacity=3 * n_requests)
    off, on = run(None), run(tracer)
    off2, on2 = run(None), run(tracer)
    if off2["requests_per_sec"] > off["requests_per_sec"]:
        off = off2
    if on2["requests_per_sec"] > on["requests_per_sec"]:
        on = on2
    return {
        "requests": n_requests,
        "sampling_off": off,
        "sampling_100": on,
        "overhead_pct_throughput": round(
            (off["requests_per_sec"] - on["requests_per_sec"])
            / off["requests_per_sec"] * 100.0, 2),
        "traces_retained": tracer.stats()["retained"],
        "cross_host": _cross_host_tracing_cell(n_requests),
        "planner_cost_model": _planner_cost_model_cell(),
    }


def _cross_host_tracing_cell(n_requests: int) -> dict:
    """Cross-host stitched tracing overhead (ISSUE 19): the same seeded
    traffic through a 2-host loopback cluster front door with tracing
    OFF (the default — no trace context even built) and at 100%
    sampling with per-host tracers, wire-v3 context propagation, and
    the aggregator's stitched view. ``overhead_us_per_request`` should
    hold the single-host ~10 us/request envelope plus the one
    dict-kwarg hop per dispatch; the off condition must sit within
    noise of the plain engine path (it IS the plain path: NULL_TRACE
    means zero extra kwargs touch the wire)."""
    from deeplearning4j_tpu.serving import (
        ClusterDirectory, ClusterFrontDoor, ClusterStatsAggregator,
        HeartbeatPump, InferenceEngine, LoopbackHost, LoopbackTransport,
        Tracer)

    def run(traced):
        cap = 3 * n_requests
        fd_tracer = Tracer(sample_rate=1.0, capacity=cap) if traced \
            else None
        d = ClusterDirectory(heartbeat_timeout_s=60.0)
        engines, hosts = [], []
        for i in range(2):
            ekw = ({"tracer": Tracer(sample_rate=1.0, capacity=cap)}
                   if traced else {})
            eng = InferenceEngine(
                _tiny_mlp_adapter(), max_batch_size=8, max_wait_ms=0.0,
                queue_capacity_rows=n_requests + 8,
                name=f"xhost-{'on' if traced else 'off'}{i}", **ekw)
            eng.warmup(np.zeros(16, np.float32))
            h = LoopbackHost(i, engine=eng, **ekw)
            d.join(h)
            HeartbeatPump(h, LoopbackTransport(d)).pump_once()
            engines.append(eng)
            hosts.append(h)
        fd = ClusterFrontDoor(d, tracer=fd_tracer)
        try:
            rng = np.random.default_rng(0)
            xs = [rng.standard_normal((1, 16)).astype(np.float32)
                  for _ in range(n_requests)]
            dts = []
            for _ in range(3):
                t0 = time.perf_counter()
                for f in [fd.submit(x) for x in xs]:
                    f.result(timeout=120)
                dts.append(time.perf_counter() - t0)
            dt = sorted(dts)[1]
            out = {"requests_per_sec": round(n_requests / dt, 2)}
            if traced:
                agg = ClusterStatsAggregator(d, hosts=hosts)
                agg.estimate_clock_offsets()
                stitched = agg.stitched_traces()
                out["stitched_traces"] = len(stitched)
                out["multi_span"] = sum(
                    1 for s in stitched if s["span_count"] >= 2)
            return out, dt
        finally:
            for h in hosts:
                h.shutdown()

    (off, dt_off), (on, dt_on) = run(False), run(True)
    return {
        "requests": n_requests,
        "hosts": 2,
        "sampling_off": off,
        "sampling_100_stitched": on,
        "overhead_us_per_request": round(
            (dt_on - dt_off) / n_requests * 1e6, 2),
        "single_host_envelope_us": 10.0,
    }


def _planner_cost_model_cell() -> dict:
    """Cost-model fit quality (ISSUE 19 / ROADMAP 4b): seeded synthetic
    fleet telemetry with a KNOWN tokens/sec curve plus noise, fitted by
    ``fit_cost_models`` exactly the way the elasticity planner does —
    headline numbers are the recovered full-occupancy rate vs ground
    truth and whether the planner's decision log cites the fitted
    cost-per-token (the join/drain unit-economics citation)."""
    from deeplearning4j_tpu.serving import (
        ElasticityPlanner, TimeSeriesStore, config_key)

    true_at_full = 80.0    # rate = 100 - 20*occ
    rng = np.random.default_rng(0)
    ts = TimeSeriesStore()
    for i in range(64):
        occ = float(rng.uniform(0.05, 1.0))
        ts.record(0, {
            "t": float(i),
            "slot_occupancy": occ,
            "tokens_per_sec": 100.0 - 20.0 * occ
            + float(rng.normal(0.0, 2.0)),
            "host_class": "decode",
        })
    planner = ElasticityPlanner(timeseries=ts)
    dec = planner.observe({
        "fleet": {"hosts": 1, "alive": 1, "draining": 0,
                  "slots": 8, "free_slots": 4},
        "hosts": {}, "front_doors": []})
    key = config_key("decode", None)
    m = dec["cost_model"]["models"][key]
    return {
        "samples": 64,
        "true_tokens_per_sec_at_full": true_at_full,
        "fitted_tokens_per_sec_at_full": round(
            m["tokens_per_sec_at_full"], 2),
        "fit_error_pct": round(
            abs(m["tokens_per_sec_at_full"] - true_at_full)
            / true_at_full * 100.0, 2),
        "r2": round(m["r2"], 4),
        "cost_per_token_host_s": m["cost_per_token"],
        "decision_cites_cost_per_token":
            "fitted cost/token" in dec["reason"],
    }


def fairness_leg(on_tpu: bool) -> dict:
    """Multi-tenant QoS under contention (serving/qos.py), three scenarios:

    - ``noisy_neighbor``: one flooding batch-class tenant + one
      interactive tenant against a max_batch_size=1 engine (every
      dispatch serves exactly one request, so QUEUE order is the whole
      story). With QoS off the victim's requests sit behind the flood
      (FIFO); with QoS on the interactive class strictly overtakes.
      Reports the victim's p99 and per-tenant goodput both ways.
    - ``weighted_share``: two batch-class tenants at weights 3:1 drain a
      pre-loaded queue; the first-40-completions split is the measured
      goodput ratio (the ISSUE acceptance number: ~3x +/- 20%).
    - ``retry_storm``: a seeded FaultPlan fails 40% of dispatches
      transiently; amplification = (dispatches incl. retries) /
      dispatches, with and without a deployment RetryBudget — the budget
      caps the storm near 1 + ratio while the un-budgeted run amplifies
      toward the retry limit."""
    import threading

    from deeplearning4j_tpu.serving import (
        FaultPlan, InferenceEngine, QosPolicy, RetryBudget, RetryPolicy,
        TenantPolicy)

    row = np.zeros((1, 16), np.float32)

    # ---------------------------------------------------- noisy neighbor
    def run_noisy(qos):
        """One flooding batch tenant keeps a 256-request queue saturated
        for the whole measurement; the interactive victim submits
        blocking requests THROUGH the contention. FIFO makes each victim
        request drain the whole backlog first; QoS lets it overtake."""
        victim_n = 30
        backlog = 128
        stop = threading.Event()
        with InferenceEngine(
                _tiny_mlp_adapter(), max_batch_size=1, max_wait_ms=0.0,
                queue_capacity_rows=2 * backlog, qos=qos,
                name="fairness") as eng:
            eng.warmup(np.zeros(16, np.float32))

            def flood():
                # keep `backlog` requests queued at all times (half the
                # capacity, so the victim's own submit always admits and
                # the comparison isolates QUEUE ORDER, not entry races)
                outstanding = []
                while not stop.is_set():
                    outstanding = [f for f in outstanding if not f.done()]
                    while len(outstanding) < backlog:
                        try:
                            outstanding.append(
                                eng.submit(row, tenant="noisy",
                                           priority="batch"))
                        except Exception:
                            break
                    time.sleep(0.0005)
                for f in outstanding:
                    try:
                        f.result(timeout=300)
                    except Exception:
                        pass

            ft = threading.Thread(target=flood)
            ft.start()
            time.sleep(0.05)   # flood reaches steady saturation
            lat = []
            t_run = time.perf_counter()
            for _ in range(victim_n):
                t0 = time.perf_counter()
                eng.submit(row, tenant="victim",
                           priority="interactive").result(timeout=120)
                lat.append((time.perf_counter() - t0) * 1e3)
            stop.set()
            ft.join(timeout=300)
            dt = time.perf_counter() - t_run
            lat.sort()
            qs = eng.metrics.qos_snapshot()
            served = {t: d["served"] for t, d in qs["tenants"].items()}
            return {
                "victim_p50_ms": round(lat[len(lat) // 2], 3),
                "victim_p99_ms": round(lat[-1], 3),
                # run durations differ (the victim finishes ~25x sooner
                # with QoS on), so goodput is rate-normalized
                "goodput_per_sec": {t: round(v / dt, 1)
                                    for t, v in served.items()},
                "served": served,
            }

    noisy_policy = QosPolicy({
        "noisy": TenantPolicy(weight=1.0, priority="batch"),
        "victim": TenantPolicy(weight=1.0, priority="interactive")})
    noisy = {"qos_off": run_noisy(None), "qos_on": run_noisy(noisy_policy)}

    # ---------------------------------------------------- weighted share
    heavy_w, light_w = 3.0, 1.0
    pol = QosPolicy({"heavy": TenantPolicy(weight=heavy_w, priority="batch"),
                     "light": TenantPolicy(weight=light_w, priority="batch")})
    order = []
    with InferenceEngine(_tiny_mlp_adapter(), max_batch_size=1,
                         max_wait_ms=0.0, queue_capacity_rows=4096,
                         qos=pol, name="wfq") as eng:
        eng.warmup(np.zeros(16, np.float32))
        plan = FaultPlan(seed=0).delay("engine.dispatch", ms=120, at=(0,))
        with plan:
            futs = [eng.submit(row, tenant="light")]   # wedges dispatch 0
            time.sleep(0.03)
            for _ in range(60):
                for t in ("heavy", "light"):
                    f = eng.submit(row, tenant=t)
                    f.add_done_callback(
                        lambda _f, t=t: order.append(t))
                    futs.append(f)
            for f in futs:
                f.result(timeout=300)
    head = order[:40]
    n_heavy, n_light = head.count("heavy"), head.count("light")
    weighted = {
        "weights": {"heavy": heavy_w, "light": light_w},
        "first_40_completions": {"heavy": n_heavy, "light": n_light},
        "goodput_ratio": round(n_heavy / max(n_light, 1), 3),
    }

    # ------------------------------------------------------- retry storm
    def run_storm(budget):
        n = 120
        plan = (FaultPlan(seed=7)
                .fail("engine.dispatch", rate=0.4))
        with InferenceEngine(
                _tiny_mlp_adapter(), max_batch_size=1, max_wait_ms=0.0,
                queue_capacity_rows=n + 8,
                retry_policy=RetryPolicy(max_attempts=4, base_delay_ms=0.2,
                                         max_delay_ms=2.0, seed=0),
                retry_budget=budget, name="storm") as eng:
            eng.warmup(np.zeros(16, np.float32))
            ok = 0
            with plan:
                futs = [eng.submit(row) for _ in range(n)]
                for f in futs:
                    try:
                        f.result(timeout=120)
                        ok += 1
                    except Exception:
                        pass
            m = eng.metrics
            batches = m.batches_total.value + m.failed_total.value
            retries = m.retries_total.value
            return {
                "requests": n,
                "success_rate": round(ok / n, 4),
                "retries": int(retries),
                "amplification": round((batches + retries)
                                       / max(batches, 1), 4),
                "retry_budget_exhausted":
                    int(m.retry_budget_exhausted_total.value),
            }

    storm = {
        "injected_fault_rate": 0.4,
        "budget_off": run_storm(None),
        "budget_on": run_storm(RetryBudget(ratio=0.1, burst=5.0)),
    }

    return {"noisy_neighbor": noisy, "weighted_share": weighted,
            "retry_storm": storm}


def cluster_leg(on_tpu: bool) -> dict:
    """Pod-slice control-plane leg (serving/cluster.py): (a) 1-host vs
    3-host loopback throughput scaling through the ClusterFrontDoor —
    dispatch cost is a simulated per-batch device time so host
    parallelism, not numpy, is what scales; (b) routed TTFT p50 for
    generation streams fanned over a 3-host loopback cluster (submit ->
    first token through the front door, routing overhead included);
    (c) shed-reason mix under a one-host-degraded scenario: host 0's
    deployment breaker trips and its heartbeat dies, the fleet keeps
    serving via the survivors, and forced sheds type as
    cluster_capacity/host_unavailable in the front door's counters."""
    import time as _time

    from deeplearning4j_tpu.serving import (
        ClusterDirectory, ClusterFrontDoor, HeartbeatPump, InferenceEngine,
        LoopbackHost, LoopbackTransport, ModelAdapter)

    class _SimDevice(ModelAdapter):
        """Fixed 2 ms per dispatched batch (sleep releases the GIL), so
        N hosts serve N batches concurrently — the scaling signal."""

        def __init__(self):
            super().__init__(model=None)
            self.w = np.linspace(-1, 1, 16, dtype=np.float32).reshape(16, 1)

        def infer(self, x):
            _time.sleep(0.002)
            return np.asarray(x) @ self.w

    def make_fleet(n, queue_capacity_rows=4096):
        d = ClusterDirectory(heartbeat_timeout_s=5.0)
        hosts, pumps, engines = [], [], []
        for i in range(n):
            eng = InferenceEngine(_SimDevice(), max_batch_size=8,
                                  max_wait_ms=0.0,
                                  queue_capacity_rows=queue_capacity_rows,
                                  name=f"bench-h{i}")
            h = LoopbackHost(i, engine=eng)
            d.join(h)
            pumps.append(HeartbeatPump(h, LoopbackTransport(d)))
            hosts.append(h)
            engines.append(eng)
        for p in pumps:
            p.pump_once()
        return d, hosts, pumps, engines

    def run_throughput(n_hosts, n_requests=300):
        d, hosts, pumps, engines = make_fleet(n_hosts)
        try:
            fd = ClusterFrontDoor(d)
            x = np.ones((8, 16), np.float32)   # one full bucket per req
            fd.output(x)                        # warm the path
            t0 = _time.perf_counter()
            futs = [fd.submit(x) for _ in range(n_requests)]
            for f in futs:
                f.result(timeout=120)
            dt = _time.perf_counter() - t0
            return n_requests / dt
        finally:
            for h in hosts:
                h.shutdown()

    rps1 = run_throughput(1)
    rps3 = run_throughput(3)

    # ---- routed TTFT p50: generation streams over a 3-host fleet ------
    from deeplearning4j_tpu.models import TransformerConfig, init_params
    from deeplearning4j_tpu.serving import GenerationEngine

    if on_tpu:
        gcfg = TransformerConfig(causal=True, remat=False,
                                 attention_impl="flash")
        slots, max_len, n_streams, max_new = 8, 512, 24, 32
    else:
        gcfg = TransformerConfig(vocab_size=1024, hidden=128, layers=2,
                                 heads=4, mlp_dim=512, max_seq=128,
                                 dtype=jnp.float32, causal=True,
                                 remat=False)
        slots, max_len, n_streams, max_new = 2, 64, 9, 8

    gparams = init_params(jax.random.PRNGKey(0), gcfg)
    d = ClusterDirectory(heartbeat_timeout_s=5.0)
    ghosts, gpumps = [], []
    for i in range(3):
        g = GenerationEngine(gparams, gcfg, slots=slots, max_len=max_len,
                             queue_capacity=n_streams + slots,
                             name=f"bench-g{i}")
        h = LoopbackHost(i, generation=g)
        d.join(h)
        gpumps.append(HeartbeatPump(h, LoopbackTransport(d)))
        ghosts.append(h)
    for p in gpumps:
        p.pump_once()
    try:
        fd = ClusterFrontDoor(d)
        rng = np.random.default_rng(0)
        # warm every host's executables out of the TTFT measurement
        warm = [fd.submit_generate(
            rng.integers(1, gcfg.vocab_size, 8).astype(np.int32),
            max_new_tokens=2, host=i) for i in range(3)]
        for h in warm:
            h.result(timeout=600)
        ttfts = []
        handles = []
        for _ in range(n_streams):
            first = {"t": None}
            t0 = _time.perf_counter()

            def on_token(_tok, first=first, t0=t0):
                if first["t"] is None:
                    first["t"] = (_time.perf_counter() - t0) * 1e3

            handles.append((first, fd.submit_generate(
                rng.integers(1, gcfg.vocab_size, 12).astype(np.int32),
                max_new_tokens=max_new, on_token=on_token)))
        for first, h in handles:
            h.result(timeout=600)
            if first["t"] is not None:
                ttfts.append(first["t"])
        routed_ttft_p50 = float(np.median(ttfts)) if ttfts else None
        gen_routed = fd.routed_by_host.to_dict()
    finally:
        for h in ghosts:
            h.shutdown()

    # ---- one-host-degraded shed mix -----------------------------------
    clock = [0.0]
    d = ClusterDirectory(heartbeat_timeout_s=1.0, probe_interval_s=100.0,
                         clock=lambda: clock[0])
    hosts, pumps, engines = [], [], []
    for i in range(3):
        eng = InferenceEngine(_SimDevice(), max_batch_size=8,
                              max_wait_ms=0.0, queue_capacity_rows=1024,
                              name=f"deg-h{i}")
        h = LoopbackHost(i, engine=eng)
        d.join(h)
        pumps.append(HeartbeatPump(h, LoopbackTransport(d)))
        hosts.append(h)
        engines.append(eng)
    for p in pumps:
        p.pump_once()
    try:
        fd = ClusterFrontDoor(d)
        # degrade host 0: breaker OPEN + heartbeat death
        for _ in range(engines[0].breaker.failure_threshold):
            engines[0].breaker.record_failure()
        clock[0] += 2.0
        for p in pumps[1:]:
            p.pump_once()
        ok = shed = 0
        x = np.ones((8, 16), np.float32)
        futs = []
        for i in range(120):
            try:
                # a third of the burst is pinned to the dead host — the
                # traffic that WOULD have landed there sheds typed
                futs.append(fd.submit(x, host=0 if i % 3 == 0 else None))
            except Exception:
                shed += 1
        for f in futs:
            try:
                f.result(timeout=120)
                ok += 1
            except Exception:
                shed += 1
        degraded = {
            "requests": 120,
            "served": ok,
            "shed": shed,
            "shed_reasons": fd.metrics.rejections_by_reason.to_dict(),
            "routed_by_host": fd.routed_by_host.to_dict(),
            "survivor_share": round(
                (fd.routed_by_host.get("h1")
                 + fd.routed_by_host.get("h2")) / max(ok, 1), 4),
        }
    finally:
        for h in hosts:
            h.shutdown()

    return {
        "throughput_rps_1host": round(rps1, 2),
        "throughput_rps_3host": round(rps3, 2),
        "scaling_3host": round(rps3 / rps1, 4) if rps1 else None,
        "routed_ttft_p50_ms": round(routed_ttft_p50, 3)
            if routed_ttft_p50 is not None else None,
        "gen_routed_by_host": gen_routed,
        "one_host_degraded": degraded,
        "rpc": rpc_subleg(on_tpu, gcfg, gparams, slots, max_len),
        "recovery": recovery_subleg(on_tpu, gcfg, gparams),
        "disagg": disagg_subleg(on_tpu, gcfg, gparams, slots, max_len),
    }


def recovery_subleg(on_tpu: bool, gcfg, gparams) -> dict:
    """Recovery sub-leg (ISSUE 15 — make host loss and preemption
    cheap), two claims measured:

    (a) **resume vs replay.** A lost stream re-dispatched with its
    delivered-so-far watermark costs ONE recompute prefill plus only
    the REMAINING decode steps; a from-zero replay re-decodes
    everything. Measured as the same request finished from its halfway
    watermark vs restarted cold.

    (b) **swap vs recompute preemption.** The identical QoS preemption
    scenario (batch victim evicted for an interactive aggressor) run on
    two otherwise-identical engines: swap disabled (victim re-prefills
    on resume) vs ``swap_threshold_blocks=0`` (victim's KV blocks ride
    host RAM and are copied back in). Victim completion latency and the
    swap counters are the crossover evidence behind the threshold
    default."""
    import time as _time

    from deeplearning4j_tpu.serving import GenerationEngine, QosPolicy

    max_new = 24 if on_tpu else 12
    p = np.random.default_rng(5).integers(
        1, gcfg.vocab_size, 8).astype(np.int32)

    # ---- (a) resume-from-watermark vs full replay ---------------------
    with GenerationEngine(gparams, gcfg, slots=2, max_len=64,
                          block_size=8, name="rec-bench") as eng:
        full = eng.generate(p, max_new_tokens=max_new, eos_id=None,
                            timeout=600)           # warm + the oracle
        w = max_new // 2
        # warm the resume path's prefill bucket (prompt + watermark
        # tokens ride one feed) so compile time stays out of the timing
        eng.submit(p, max_new_tokens=max_new, eos_id=None,
                   resume_tokens=np.asarray(full[:w], np.int32),
                   resume_step=w).result(timeout=600)
        t0 = _time.perf_counter()
        replay = eng.generate(p, max_new_tokens=max_new, eos_id=None,
                              timeout=600)
        replay_ms = (_time.perf_counter() - t0) * 1e3
        t0 = _time.perf_counter()
        resumed = eng.submit(p, max_new_tokens=max_new, eos_id=None,
                             resume_tokens=np.asarray(full[:w], np.int32),
                             resume_step=w).result(timeout=600)
        resume_ms = (_time.perf_counter() - t0) * 1e3
        # bitwise: the resumed handle delivers exactly the REMAINING
        # tokens (nothing already delivered is re-decoded)
        assert replay == full and list(resumed) == list(full[w:])

    # ---- (b) preempt-resume: recompute vs swap-to-host ----------------
    qos = QosPolicy(tenants={"fast": {"priority": "interactive"},
                             "slow": {"priority": "batch"}})

    def preempt_run(**swap_kw):
        with GenerationEngine(gparams, gcfg, slots=2, max_len=32,
                              block_size=8, num_blocks=5,
                              allocate="on_demand", qos=qos,
                              queue_capacity=8, name="rec-bench-p",
                              **swap_kw) as eng:
            t0 = _time.perf_counter()
            hv = eng.submit(p, max_new_tokens=20, eos_id=None,
                            tenant="slow")
            ha = eng.submit(np.random.default_rng(6).integers(
                1, gcfg.vocab_size, 4).astype(np.int32),
                max_new_tokens=20, eos_id=None, tenant="fast")
            victim = hv.result(timeout=600)
            victim_ms = (_time.perf_counter() - t0) * 1e3
            ha.result(timeout=600)
            return victim, victim_ms, {
                "preemptions": int(eng.metrics.preemptions_total.value),
                "kv_swapped_blocks": int(
                    eng.metrics.kv_swapped_blocks.value),
                "kv_swap_bytes_out": int(
                    eng.metrics.kv_swap_bytes_out.value),
            }

    v_rec, recompute_ms, rec_stats = preempt_run()
    v_swap, swap_ms, swap_stats = preempt_run(swap_threshold_blocks=0,
                                              swap_capacity_blocks=64)
    assert v_rec == v_swap        # bitwise across both resume paths

    return {
        "stream_replay_ms": round(replay_ms, 3),
        "stream_resume_ms": round(resume_ms, 3),
        "resume_speedup": round(replay_ms / resume_ms, 4)
            if resume_ms else None,
        "resume_watermark": w,
        "preempt_victim_ms_recompute": round(recompute_ms, 3),
        "preempt_victim_ms_swap": round(swap_ms, 3),
        "preempt_stats_recompute": rec_stats,
        "preempt_stats_swap": swap_stats,
    }


def rpc_subleg(on_tpu: bool, gcfg, gparams, slots: int,
               max_len: int) -> dict:
    """RPC data-plane sub-leg (serving/rpc.py — ISSUE 12): (a) per-
    dispatch overhead of the HTTP HostHandle vs the loopback direct
    call (same engine, same rows — the wire's round-trip tax); (b)
    routed TTFT p50 for generation streams fanned over a 3-host HTTP
    fleet (every hop crosses a real socket); (c) hedged vs unhedged
    stream-latency p99 under a seeded 5% ``rpc.dispatch`` latency-spike
    plan — the Tail-at-Scale claim measured: with hedging off a spiked
    dispatch stalls its whole stream for the spike, with hedging on the
    stall monitor opens a backup attempt and the tail collapses."""
    import time as _time

    from deeplearning4j_tpu.serving import (
        ClusterDirectory, ClusterFrontDoor, FaultPlan, GenerationEngine,
        HeartbeatPump, HedgePolicy, HostRpcServer, InferenceEngine,
        LoopbackHost, LoopbackTransport, ModelAdapter, RemoteHost)

    class _Mlp(ModelAdapter):
        def __init__(self):
            super().__init__(model=None)
            self.w = np.linspace(-1, 1, 16, dtype=np.float32).reshape(16, 1)

        def infer(self, x):
            return np.asarray(x) @ self.w

    # ---- (a) loopback vs HTTP dispatch overhead -----------------------
    eng = InferenceEngine(_Mlp(), max_batch_size=8, max_wait_ms=0.0,
                          name="rpc-bench-e")
    local = LoopbackHost(0, engine=eng)
    srv = HostRpcServer(local)
    remote = RemoteHost(0, srv.url)
    x = np.ones((8, 16), np.float32)
    try:
        def p50_dispatch(host, n=80, warm=10):
            for _ in range(warm):
                host.submit_infer(x).result(timeout=60)
            lats = []
            for _ in range(n):
                t0 = _time.perf_counter()
                host.submit_infer(x).result(timeout=60)
                lats.append((_time.perf_counter() - t0) * 1e3)
            return float(np.median(lats))

        loop_p50 = p50_dispatch(local)
        http_p50 = p50_dispatch(remote)
    finally:
        srv.stop()
        local.shutdown()

    # ---- (b) + (c): a 3-host HTTP generation fleet --------------------
    n_streams, max_new = (24, 16) if on_tpu else (30, 4)
    d = ClusterDirectory(heartbeat_timeout_s=30.0)
    servers, locals_, remotes = [], [], []
    for i in range(3):
        g = GenerationEngine(gparams, gcfg, slots=slots, max_len=max_len,
                             queue_capacity=n_streams + slots,
                             name=f"rpc-bench-g{i}")
        lh = LoopbackHost(i, generation=g)
        sv = HostRpcServer(lh)
        rm = RemoteHost(i, sv.url, poll_wait_ms=25.0)
        d.join(rm)
        HeartbeatPump(rm, LoopbackTransport(d)).pump_once()
        servers.append(sv)
        locals_.append(lh)
        remotes.append(rm)
    rng = np.random.default_rng(0)

    def run_streams(fd, n, plan=None):
        """Sequential streams (isolates per-stream latency from slot
        contention); returns (ttfts_ms, latencies_ms)."""
        from contextlib import nullcontext

        ttfts, lats = [], []
        ctx = plan if plan is not None else nullcontext()
        with ctx:
            for _ in range(n):
                first = {"t": None}
                t0 = _time.perf_counter()

                def on_token(_tok, first=first, t0=t0):
                    if first["t"] is None:
                        first["t"] = (_time.perf_counter() - t0) * 1e3

                h = fd.submit_generate(
                    rng.integers(1, gcfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=max_new, on_token=on_token)
                h.result(timeout=600)
                lats.append((_time.perf_counter() - t0) * 1e3)
                if first["t"] is not None:
                    ttfts.append(first["t"])
        return ttfts, lats

    spike_ms = 400.0

    def spike_plan():
        return FaultPlan(seed=7).delay("rpc.dispatch", spike_ms, rate=0.05)

    try:
        # warm every host's executables out of the measurements
        for i in range(3):
            ClusterFrontDoor(d, name=f"warm{i}").submit_generate(
                rng.integers(1, gcfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=2, host=i).result(timeout=600)

        fd_clean = ClusterFrontDoor(d, name="rpc-clean",
                                    hedge=HedgePolicy(hedge_after_ms=None))
        ttfts, _ = run_streams(fd_clean, n_streams)
        routed = fd_clean.routed_by_host.to_dict()

        fd_unhedged = ClusterFrontDoor(
            d, name="rpc-unhedged", hedge=HedgePolicy(hedge_after_ms=None))
        _, lats_unhedged = run_streams(fd_unhedged, n_streams,
                                       plan=spike_plan())

        fd_hedged = ClusterFrontDoor(
            d, name="rpc-hedged",
            hedge=HedgePolicy(hedge_after_ms=80.0, max_attempts=3,
                              poll_wait_ms=25.0))
        _, lats_hedged = run_streams(fd_hedged, n_streams,
                                     plan=spike_plan())
        hedge_mix = fd_hedged.hedges.to_dict()
    finally:
        for sv in servers:
            sv.stop()
        for lh in locals_:
            lh.shutdown()

    return {
        "loopback_dispatch_p50_ms": round(loop_p50, 3),
        "http_dispatch_p50_ms": round(http_p50, 3),
        "http_overhead_p50_ms": round(http_p50 - loop_p50, 3),
        "routed_ttft_p50_ms_http": round(float(np.median(ttfts)), 3)
            if ttfts else None,
        "gen_routed_by_host": routed,
        "hedge_spike_plan": {"point": "rpc.dispatch", "rate": 0.05,
                             "delay_ms": spike_ms, "seed": 7},
        "stream_p99_ms_unhedged": round(
            float(np.percentile(lats_unhedged, 99)), 3),
        "stream_p99_ms_hedged": round(
            float(np.percentile(lats_hedged, 99)), 3),
        "hedges": hedge_mix,
    }


def disagg_subleg(on_tpu: bool, gcfg, gparams, slots: int,
                  max_len: int) -> dict:
    """Disaggregated serving sub-leg (ISSUE 16 — serving/disagg.py):
    the same fixed 2-host fleet run mixed (both hosts ``host_class=
    "mixed"``, no policy) and disaggregated (1 prefill + 1 decode
    behind :class:`DisaggPolicy`), same prompt schedule. Reports TTFT
    p50 and ITL p99 for both placements, plus the migration-path
    numbers only the disaggregated run has: migrations vs degrade
    fallbacks, KV bytes migrated per stream, and the fleet prefix hit
    rate (wave 2 repeats wave 1's prompts, so the radix-routed decode
    host already holds their cached prefixes)."""
    import time as _time

    from deeplearning4j_tpu.serving import (
        ClusterDirectory, ClusterFrontDoor, DisaggPolicy, GenerationEngine,
        HeartbeatPump, LoopbackHost, LoopbackTransport)

    n_prompts, prompt_len, max_new = 4, 12, 16
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, gcfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_prompts)]

    def run_fleet(disaggregated: bool) -> dict:
        classes = ("prefill", "decode") if disaggregated \
            else ("mixed", "mixed")
        d = ClusterDirectory(heartbeat_timeout_s=5.0)
        engines, hosts, pumps = [], [], []
        for i, cls in enumerate(classes):
            g = GenerationEngine(gparams, gcfg, slots=slots,
                                 max_len=max_len, prefix_cache_blocks=8,
                                 name=f"disagg-{cls}{i}")
            h = LoopbackHost(i, generation=g, host_class=cls)
            d.join(h)
            pumps.append(HeartbeatPump(h, LoopbackTransport(d)))
            engines.append(g)
            hosts.append(h)
        for p in pumps:
            p.pump_once()
        fd = ClusterFrontDoor(
            d, disagg=DisaggPolicy() if disaggregated else None)
        try:
            # warm both hosts' executables out of the measurement
            for i in range(len(hosts)):
                fd.submit_generate(prompts[0], max_new_tokens=2,
                                   host=i).result(timeout=600)
            ttfts, itls = [], []

            def run_wave():
                handles = []
                for toks in prompts:
                    stamps = []
                    t0 = _time.perf_counter()
                    handles.append((stamps, t0, fd.submit_generate(
                        toks, max_new_tokens=max_new,
                        on_token=lambda _t, s=stamps:
                            s.append(_time.perf_counter()))))
                for stamps, t0, h in handles:
                    h.result(timeout=600)
                    if stamps:
                        ttfts.append((stamps[0] - t0) * 1e3)
                    itls.extend((b - a) * 1e3
                                for a, b in zip(stamps, stamps[1:]))

            run_wave()
            # wave 1's retired streams fill the decode-side prefix
            # cache; the next heartbeats advertise it, so wave 2's
            # repeat prompts can radix-route to the host holding them
            deadline = _time.time() + 10
            while (disaggregated and _time.time() < deadline
                   and len(engines[1]._prefix_cache or ()) == 0):
                _time.sleep(0.02)
            for p in pumps:
                p.pump_once()
            run_wave()

            out = {
                "ttft_p50_ms": round(float(np.median(ttfts)), 3),
                "itl_p99_ms": round(float(np.percentile(itls, 99)), 3),
            }
            if disaggregated:
                streams = 2 * n_prompts
                out.update({
                    "migrations": int(
                        fd.metrics.kv_migrations_total.value),
                    "migrate_fallbacks": int(
                        fd.metrics.kv_migrate_fallbacks_total.value),
                    "migrated_bytes_per_stream": round(
                        engines[1].metrics.kv_migrate_bytes_in.value
                        / streams, 1),
                    "prefix_route_hits": int(
                        fd.metrics.prefix_route_hits_total.value),
                    "fleet_prefix_hit_rate": round(
                        fd.metrics.prefix_route_hits_total.value
                        / n_prompts, 4),
                })
            return out
        finally:
            for h in hosts:
                h.shutdown()

    return {
        "fleet": {"hosts": 2, "slots_per_host": slots,
                  "prompts": 2 * n_prompts, "max_new_tokens": max_new},
        "mixed": run_fleet(False),
        "disaggregated": run_fleet(True),
    }


def soak_leg(on_tpu: bool) -> dict:
    """Fleet chaos soak (ISSUE 18): three real HTTP hosts over the RPC
    plane take the seeded trace mix (chat/rag/batch over an on/off
    arrival process) while the seeded episode schedule fires kill,
    drain, preemption-storm, swap-pressure and rpc-fault episodes.

    The headline numbers: sustained tokens/sec over the whole soak,
    p99 latency DURING chaos-episode windows vs BETWEEN them (the tail
    price of chaos), worst recovery-time-to-SLO after a kill/drain, and
    the ledger verdict — True means every block, swap entry, op and
    thread returned to its post-warmup baseline. Seeded end to end:
    same seed, same episodes, same trace, so a drift here is a
    robustness regression, not noise."""
    from tools.soak import run_soak

    seed = 3
    duration_s = 16.0 if on_tpu else 14.0
    report = run_soak(seed=seed, duration_s=duration_s, n_hosts=3,
                      rate_rps=3.0, mean_gap_s=3.0)
    d = report.to_dict()
    load = d["load"]
    rec = d["recovery_to_slo_s"]
    return {
        "seed": seed,
        "duration_s": duration_s,
        "episodes_fired": d["episodes_fired"],
        "episode_kinds": sorted({r.episode.kind
                                 for r in report.episodes}),
        "requests": load["requests"],
        "ok": load["ok"],
        "stuck_streams": load["stuck_streams"],
        "tokens_per_sec": load["tokens_per_sec"],
        "watermark_clean": load["watermark_clean"],
        "latency_p99_during_episodes_ms":
            round(load["latency_p99_during_episodes_ms"], 3)
            if load["latency_p99_during_episodes_ms"] is not None
            else None,
        "latency_p99_between_episodes_ms":
            round(load["latency_p99_between_episodes_ms"], 3)
            if load["latency_p99_between_episodes_ms"] is not None
            else None,
        "recovery_to_slo_s": rec,
        "max_recovery_to_slo_s": d["max_recovery_to_slo_s"],
        "ledger_clean": d["ledger_clean"],
        "ledger_violations": d["ledger_violations"],
    }


if __name__ == "__main__":
    main()
