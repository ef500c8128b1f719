"""Quickest proof that the system still starts on the chip.

``python chip_smoke.py`` needs one TPU and drives the two main paths once,
through the entry points a user calls, at BERT-base width with random
weights made from a seed:

- train: ``init_params`` -> ``make_train_step`` -> a few steps at B=96,
  T=512 with the packed Pallas attention kernel in the program, checked
  against the plain-einsum model on a slice of the same batch;
- serve: ``ModelRegistry.deploy(CausalLMAdapter)`` -> ``generation_engine``
  -> ``warmup`` -> greedy requests of mixed prompt length, for the gather
  and the fused paged-attention routes over float and int8 pools, checked
  against plain ``forward()`` on the same tokens.

``python chip_smoke.py --chips 4`` runs only the sharded train step on the
two meshes ``__graft_entry__.dryrun_multichip(4)`` exercises, each against
the same steps on one device, and one ``ParallelWrapper`` fit.

There is no CPU branch: where JAX finds no TPU the script says so and exits
non-zero. Any check that fails raises, and the script exits non-zero. The
last line of a passing run is one JSON object that names the device. Lines
before it marked ``info`` (seconds, bytes) are informational, not results.
One process, no subprocess: a chip belongs to one process at a time. The
package is imported inside the phases, after the device check.
"""
import argparse
import dataclasses
import json
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

SEED = 0
EOS_ID = 102          # [SEP] in BERT's vocabulary
# Greedy tokens are compared through the plain model's own logits: a token
# the engine emitted must score within this much of the plain model's best
# token at that position. Logits here have a spread of about 0.5, bf16
# carries 8 bits, and int8 pools add a per-token quantisation step of 1/254
# of each head's range, so ties closer than this are not decidable.
LOGIT_TOL = {"float32": 0.08, "int8": 0.12}
LOSS_RTOL = 1e-2          # bf16 matmuls, reductions in another order


def info(msg: str) -> None:
    print(f"info  {msg}", flush=True)


def assert_kernel_in(program_text: str, what: str) -> None:
    assert "tpu_custom_call" in program_text, \
        f"{what}: the compiled program holds no Pallas kernel"


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ train

def fixed_batch(cfg, B, T):
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    return {"tokens": tokens, "targets": tokens.copy(),
            "weights": np.ones((B, T), np.float32)}


def train_steps(cfg, B, T, steps, mesh=None):
    """``steps`` updates on one fixed batch through make_train_step; returns
    (losses, compiled step, final params)."""
    from deeplearning4j_tpu.models import init_params, make_train_step
    from deeplearning4j_tpu.models.bert import batch_pspec, place_params

    params = init_params(jax.random.PRNGKey(SEED), cfg)
    batch = jax.device_put(fixed_batch(cfg, B, T))
    if mesh is not None:
        params = place_params(params, cfg, mesh)
        bsh = NamedSharding(mesh, batch_pspec(mesh))
        batch = {k: jax.device_put(v, bsh) for k, v in batch.items()}
    init_state, step = make_train_step(cfg, mesh, learning_rate=1e-4)
    opt_state = init_state(params)
    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, batch)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    lower_s, compile_s = t1 - t0, time.perf_counter() - t1
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(time.perf_counter() - t0)
    where = "one device" if mesh is None else f"mesh {dict(mesh.shape)}"
    info(f"train {cfg.attention_impl} on {where}: B={B} T={T} "
         f"trace+lower {lower_s:.1f} s, compile {compile_s:.1f} s, step seconds "
         f"{[round(s, 4) for s in step_s]}")
    print(f"train {cfg.attention_impl} on {where}: losses "
          f"{[round(l, 4) for l in losses]}", flush=True)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return losses, compiled, params


def kernel_agrees_with_einsum(cfg):
    """The packed kernel's forward and backward against the plain einsum
    model on the same weights and a slice of the batch."""
    from deeplearning4j_tpu.models import init_params
    from deeplearning4j_tpu.models.bert import lm_loss

    params = init_params(jax.random.PRNGKey(SEED), cfg)
    batch = fixed_batch(cfg, 8, cfg.max_seq)
    out = {}
    for impl in ("flash", "full"):
        c = dataclasses.replace(cfg, attention_impl=impl)
        loss, grads = jax.jit(
            lambda p, b, c=c: jax.value_and_grad(lm_loss)(p, b, c))(
                params, batch)
        flat = jnp.concatenate([g.ravel() for g in jax.tree.leaves(grads)])
        out[impl] = (float(loss), jax.block_until_ready(flat))
    (lk, gk), (le, ge) = out["flash"], out["full"]
    cos = float(jnp.vdot(gk, ge) / (jnp.linalg.norm(gk) * jnp.linalg.norm(ge)))
    print(f"train kernel vs einsum at B=8: loss {lk:.5f} vs {le:.5f}, "
          f"gradient cosine {cos:.6f}", flush=True)
    assert abs(lk - le) <= LOSS_RTOL * abs(le), (lk, le)
    assert cos > 0.999, cos


def train_phase(device):
    from deeplearning4j_tpu.models import TransformerConfig

    cfg = TransformerConfig(remat=False, attention_impl="flash")
    for B in (96, 64, 48, 32):
        try:
            compiled = train_steps(cfg, B, cfg.max_seq, steps=4)[1]
            break
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"train: B={B} does not fit this runtime, lowering B "
                  f"({str(e).splitlines()[0][:200]})", flush=True)
    else:
        raise SystemExit("train: no batch size fits the device")
    assert_kernel_in(compiled.as_text(), "train step")
    print(f"train: B={B}, tpu_custom_call present in the compiled step",
          flush=True)
    info(f"train peak device bytes {peak_bytes(device)}")
    kernel_agrees_with_einsum(cfg)


# ------------------------------------------------------------------ serve

def paged_kernel_agrees_with_gather_reference():
    """paged_decode_attention on the chip against its gather reference at
    the engine's shapes (16 slots, 12 heads of 64, 16-token blocks)."""
    from deeplearning4j_tpu.models.bert import quantize_kv
    from deeplearning4j_tpu.ops.pallas_kernels import (
        paged_decode_attention, paged_decode_attention_reference)

    S, H, D, B, nb = 16, 12, 64, 16, 32
    rng = np.random.default_rng(SEED)
    NB = S * nb + 1
    tables = rng.permutation(np.arange(1, NB)).reshape(S, nb).astype(np.int32)
    pos = rng.integers(0, nb * B, (S,)).astype(np.int32)
    pos[0], pos[1] = 0, nb * B - 1
    k = rng.standard_normal((NB, B, H, D)).astype(np.float32)
    v = rng.standard_normal((NB, B, H, D)).astype(np.float32)
    q = rng.standard_normal((S, H, D)).astype(np.float32)
    for name in ("float32", "bfloat16", "int8"):
        kw = {}
        if name == "int8":
            qd = jnp.bfloat16
            kp, ks = quantize_kv(jnp.asarray(k))
            vp, vs = quantize_kv(jnp.asarray(v))
            kw = dict(k_scale=ks, v_scale=vs)
        else:
            qd = jnp.dtype(name)
            kp, vp = jnp.asarray(k, qd), jnp.asarray(v, qd)
        args = (jnp.asarray(q, qd), kp, vp, jnp.asarray(tables),
                jnp.asarray(pos))
        got = paged_decode_attention(*args, block_size=B, **kw)
        want = paged_decode_attention_reference(*args, block_size=B, **kw)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        print(f"serve paged kernel vs gather reference, {name} pool: "
              f"max abs difference {err:.2e}", flush=True)
        assert got.shape == (S, H, D) and err < 2e-2, (name, err)


def run_engine(registry, plain_scores, prompts, max_new, kv_dtype, route):
    """One engine configuration: warm up, one stream alone, all streams
    co-scheduled, every token held to the plain model's logits."""
    t0 = time.perf_counter()
    eng = registry.generation_engine(
        "bert-base-causal", slots=16, max_len=512, eos_id=EOS_ID,
        kv_dtype=kv_dtype, paged_attention=route)
    try:
        eng.warmup()
        warm_s = time.perf_counter() - t0
        probe = 2
        t0 = time.perf_counter()
        alone = eng.generate(prompts[probe], max_new_tokens=max_new,
                             timeout=600.0)
        alone_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        streams = [list(h.stream(timeout=600.0)) if i == probe
                   else h.result(timeout=600.0)
                   for i, h in enumerate(handles)]
        mixed_s = time.perf_counter() - t0
        reasons = [h.finish_reason for h in handles]
        signatures = eng.compiled_signatures()
    finally:
        eng.shutdown()
    tag = f"serve {kv_dtype}/{route}"
    info(f"{tag}: warmup (compiles {signatures} programs) {warm_s:.1f} s, "
         f"alone {alone_s / max(len(alone), 1):.4f} s/token, co-scheduled "
         f"{mixed_s / sum(map(len, streams)):.4f} s/token over "
         f"{len(streams)} streams")
    assert all(r in ("eos", "max_tokens") for r in reasons), reasons
    assert all(1 <= len(s) <= max_new for s in streams), \
        [len(s) for s in streams]
    assert streams[probe] == alone, \
        f"{tag}: stream alone {alone} != co-scheduled {streams[probe]}"
    scores = plain_scores(prompts, streams)
    worst = max(float(gap.max()) for gap, _ in scores)
    first = max(float(gap[0]) for gap, _ in scores)
    print(f"{tag}: {len(streams)} requests ended {sorted(set(reasons))}, "
          f"alone == co-scheduled, plain-forward logit gap of emitted "
          f"tokens: first position {first:.4f}, worst {worst:.4f} "
          f"(tolerance {LOGIT_TOL[kv_dtype]})", flush=True)
    assert worst <= LOGIT_TOL[kv_dtype], (tag, worst)
    return streams, [logit for _, logit in scores]


def serve_phase(device):
    from deeplearning4j_tpu.models import (
        TransformerConfig, forward, init_params)
    from deeplearning4j_tpu.serving import CausalLMAdapter, ModelRegistry

    paged_kernel_agrees_with_gather_reference()
    cfg = TransformerConfig(causal=True, remat=False, attention_impl="flash")
    plain = dataclasses.replace(cfg, attention_impl="full")
    params = init_params(jax.random.PRNGKey(SEED + 1), cfg)
    rng = np.random.default_rng(SEED + 1)
    max_new = 24
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 23, 64, 130, 300, 480)]

    @jax.jit
    def gap_to_plain_best(params, seqs, starts, emitted):
        # logits of the plain einsum model at the positions that predict
        # each emitted token, teacher-forced on the emitted stream
        logits = forward(params, seqs, plain)
        at = starts[:, None] - 1 + jnp.arange(emitted.shape[1])[None, :]
        rows = jnp.take_along_axis(logits, at[:, :, None], axis=1)
        took = jnp.take_along_axis(rows, emitted[:, :, None], axis=2)[..., 0]
        return rows.max(-1) - took, took

    def plain_scores(prompts, streams):
        """Per stream: (gap to the plain model's best token, plain logit)
        of every emitted token."""
        seqs = np.zeros((len(prompts), cfg.max_seq), np.int32)
        emitted = np.zeros((len(prompts), max_new), np.int32)
        for i, (p, s) in enumerate(zip(prompts, streams)):
            seqs[i, :len(p)] = p
            seqs[i, len(p):len(p) + len(s)] = s
            emitted[i, :len(s)] = s
        starts = np.asarray([len(p) for p in prompts], np.int32)
        gaps, took = gap_to_plain_best(params, seqs, starts, emitted)
        return [(g[:len(s)], t[:len(s)]) for g, t, s
                in zip(np.asarray(gaps), np.asarray(took), streams)]

    registry = ModelRegistry()
    registry.deploy("bert-base-causal", CausalLMAdapter(params, cfg))
    try:
        for kv_dtype in ("float32", "int8"):
            (gather, g_logit), (fused, f_logit) = (
                run_engine(registry, plain_scores, prompts, max_new,
                           kv_dtype, route) for route in ("gather", "fused"))
            flips = 0
            for i, (g, f) in enumerate(zip(gather, fused)):
                if g == f:
                    continue
                # a near-tie flipped one token; what follows it differs by
                # construction, so hold the two tokens at the flip to the
                # tolerance under the plain model (each stream's logits
                # are teacher-forced on itself, and equal history up to j)
                j = next(n for n, (a, b) in enumerate(zip(g, f)) if a != b)
                lg, lf = float(g_logit[i][j]), float(f_logit[i][j])
                flips += 1
                print(f"serve {kv_dtype}: request {i} gather and fused "
                      f"differ first at token {j}: {g[j]} (plain logit "
                      f"{lg:.4f}) vs {f[j]} (plain logit {lf:.4f})",
                      flush=True)
                assert abs(lg - lf) <= LOGIT_TOL[kv_dtype], (i, j, lg, lf)
            same = len(prompts) - flips
            print(f"serve {kv_dtype}: gather and fused emit the same greedy "
                  f"tokens on {same} of {len(prompts)} requests, {flips} "
                  f"near-tie flips within tolerance", flush=True)
    finally:
        registry.shutdown()
    info(f"serve peak device bytes {peak_bytes(device)}")


# --------------------------------------------------------------- four chips

def assert_really_split(params, cfg, mesh, when):
    """Every parameter lives on all the mesh's devices, in shards of the
    shape its PartitionSpec asks for (code that has only seen virtual
    devices may have put everything on device 0)."""
    from deeplearning4j_tpu.models.bert import param_pspecs

    specs = jax.tree.leaves(param_pspecs(cfg),
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = jax.tree.leaves(params)
    assert len(specs) == len(leaves)
    per_device, split = {}, 0
    for leaf, spec in zip(leaves, specs):
        want, ways = list(leaf.shape), 1
        for dim, axis in enumerate(spec):
            if axis in mesh.axis_names:
                want[dim] //= mesh.shape[axis]
                ways *= mesh.shape[axis]
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat), \
            (leaf.shape, [s.device for s in shards])
        assert all(s.data.shape == tuple(want) for s in shards), \
            (leaf.shape, spec, [s.data.shape for s in shards])
        assert len({str(s.index) for s in shards}) == ways, (leaf.shape, spec)
        split += ways > 1
        for s in shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) \
                + s.data.nbytes
    total = sum(l.nbytes for l in leaves)
    assert split > 0 and max(per_device.values()) < total
    print(f"sharded {dict(mesh.shape)} {when}: {split} of {len(leaves)} "
          f"parameters split, bytes per device "
          f"{sorted(per_device.values())} of {total} whole", flush=True)


def parallel_wrapper_fit(devices):
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.train import Sgd

    def net():
        return MultiLayerNetwork(
            NeuralNetConfiguration.Builder().seed(SEED).updater(Sgd(0.05))
            .list()
            .layer(DenseLayer(nIn=784, nOut=2048, activation="RELU"))
            .layer(DenseLayer(nIn=2048, nOut=2048, activation="RELU"))
            .layer(OutputLayer(nIn=2048, nOut=10, lossFunction="MCXENT"))
            .build()).init()

    rng = np.random.default_rng(SEED)
    x = rng.standard_normal((2048, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 2048)]

    def batches():
        return ListDataSetIterator([DataSet(x, y)], batch_size=512)

    single = net()
    single.fit(batches(), epochs=2)
    wrapped = net()
    pw = ParallelWrapper(wrapped, mesh=make_mesh({"data": 4}, devices))
    shards = pw._shard_batch(x[:512]).addressable_shards
    assert len({s.device for s in shards}) == 4 \
        and all(s.data.shape == (128, 784) for s in shards), \
        [(s.device, s.data.shape) for s in shards]
    pw.fit(batches(), epochs=2)
    for leaf in jax.tree.leaves(wrapped._params):
        assert leaf.sharding.device_set == set(devices) \
            and leaf.sharding.is_fully_replicated, leaf.sharding
    ds = DataSet(x[:512], y[:512])
    a, b = single.score(ds), wrapped.score(ds)
    print(f"ParallelWrapper over data=4: batch of 512 split into 4 x 128, "
          f"parameters on 4 devices, loss {b:.5f} vs unwrapped {a:.5f}",
          flush=True)
    assert np.isfinite(a) and abs(a - b) <= LOSS_RTOL * abs(a), (a, b)


def sharded_phase(devices):
    from __graft_entry__ import _factor_mesh
    from deeplearning4j_tpu.models import TransformerConfig
    from deeplearning4j_tpu.parallel import make_mesh

    B, steps = 16, 3
    for shape, impl in ((_factor_mesh(4), "ring"),
                        ({"data": 2, "model": 2}, "flash")):
        cfg = TransformerConfig(remat=False, attention_impl=impl)
        want = train_steps(cfg, B, cfg.max_seq, steps)[0]
        mesh = make_mesh(shape, devices)
        got, compiled, params = train_steps(cfg, B, cfg.max_seq, steps, mesh)
        assert_kernel_in(compiled.as_text(), f"sharded step {shape}/{impl}")
        assert_really_split(params, cfg, mesh, f"after {steps} steps")
        diff = max(abs(g - w) / abs(w) for g, w in zip(got, want))
        print(f"sharded {shape} {impl}: losses agree with one device "
              f"within {diff:.2e} relative (tolerance {LOSS_RTOL})",
              flush=True)
        assert diff <= LOSS_RTOL, (got, want)
    parallel_wrapper_fit(devices)
    info(f"sharded peak device bytes "
         f"{[peak_bytes(d) for d in devices]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its one-device "
                         "comparison")
    chips = ap.parse_args().chips

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chip_smoke: needs {chips} TPU device(s); JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}). There is no CPU path.",
              file=sys.stderr)
        return 1
    devices = devices[:chips]
    # a deprecated JAX API on these paths should stop the run, not scroll by
    warnings.filterwarnings("error", message=r".*\bjax\b",
                            category=DeprecationWarning)

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    info(f"compile cache at {enable_compile_cache()}")
    t0 = time.perf_counter()
    if chips == 4:
        sharded_phase(devices)
    else:
        train_phase(devices[0])
        serve_phase(devices[0])
    info(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
