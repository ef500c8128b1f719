"""BERT-class transformer encoder / causal LM — the flagship model.

Reference parity: the SameDiff BERT-base fine-tune workload (BASELINE configs
#4/#5; ref: dl4j-examples BERT via `nd4j/samediff-import-tensorflow`, executed
by `org.nd4j.autodiff.samediff.internal.TrainingSession` op-by-op). The
TPU-native redesign compiles the ENTIRE training step — forward, masked/causal
LM loss, backward, AdamW update — into one XLA executable over a
``(data, model, context)`` mesh:

- **data**    — batch sharding; gradient psum inserted by GSPMD.
- **model**   — tensor parallelism: attention heads + MLP hidden sharded
  (Megatron layout: column-parallel in-projections, row-parallel
  out-projections → one all-reduce per block half).
- **context** — sequence parallelism: ring attention (K/V blocks rotating
  over ICI via ppermute with online-softmax accumulation) from
  ``deeplearning4j_tpu.parallel.sequence_parallel`` — Pallas-backed
  (``ring_flash_attention``: per-pair streamed kernels, second-ring-pass
  backward) whenever the local shard fits the kernel envelope.

Params are fp32; matmul compute is bf16 (MXU-native); layernorm/softmax in
fp32. Everything is a plain pytree of jnp arrays — no framework object graph.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import types
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS, tree_shardings)
from deeplearning4j_tpu.parallel.sequence_parallel import (
    ring_attention, ring_flash_attention, ulysses_attention)

_log = logging.getLogger(__name__)
_flash_fallback_warned: set = set()


def _warn_flash_fallback(reason: str) -> None:
    """One-time notice when attention_impl='flash' routes to the XLA einsum
    path anyway — a silent perf cliff otherwise (round-4 advisor finding)."""
    if reason not in _flash_fallback_warned:
        _flash_fallback_warned.add(reason)
        _log.warning(
            "attention_impl='flash' falling back to the XLA einsum path: %s",
            reason)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_seq: int = 512
    dropout: float = 0.0
    causal: bool = False            # False = BERT (bidirectional MLM); True = GPT-style LM
    dtype: Any = jnp.bfloat16       # compute dtype (params stay fp32)
    attention_impl: str = "full"    # 'full' | 'ring' | 'ulysses' (ring/ulysses need context axis)
    remat: bool = True              # jax.checkpoint each block (HBM <-> FLOPs trade)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


BERT_BASE = TransformerConfig()


def init_params(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree (truncated-normal 0.02, BERT-style)."""
    def dense(k, fan_in, shape):
        return jax.random.normal(k, shape, jnp.float32) * 0.02

    keys = jax.random.split(key, 4 + cfg.layers)
    params: Dict[str, Any] = {
        "tok_emb": dense(keys[0], cfg.vocab_size, (cfg.vocab_size, cfg.hidden)),
        "pos_emb": dense(keys[1], cfg.max_seq, (cfg.max_seq, cfg.hidden)),
        "ln_f": {"scale": jnp.ones((cfg.hidden,), jnp.float32),
                 "bias": jnp.zeros((cfg.hidden,), jnp.float32)},
        "lm_head": dense(keys[2], cfg.hidden, (cfg.hidden, cfg.vocab_size)),
        "blocks": [],
    }
    for i in range(cfg.layers):
        bk = jax.random.split(keys[4 + i], 4)
        params["blocks"].append({
            "ln1": {"scale": jnp.ones((cfg.hidden,), jnp.float32),
                    "bias": jnp.zeros((cfg.hidden,), jnp.float32)},
            "qkv": {"kernel": dense(bk[0], cfg.hidden, (cfg.hidden, 3 * cfg.hidden)),
                    "bias": jnp.zeros((3 * cfg.hidden,), jnp.float32)},
            "attn_out": {"kernel": dense(bk[1], cfg.hidden, (cfg.hidden, cfg.hidden)),
                         "bias": jnp.zeros((cfg.hidden,), jnp.float32)},
            "ln2": {"scale": jnp.ones((cfg.hidden,), jnp.float32),
                    "bias": jnp.zeros((cfg.hidden,), jnp.float32)},
            "mlp_in": {"kernel": dense(bk[2], cfg.hidden, (cfg.hidden, cfg.mlp_dim)),
                       "bias": jnp.zeros((cfg.mlp_dim,), jnp.float32)},
            "mlp_out": {"kernel": dense(bk[3], cfg.mlp_dim, (cfg.mlp_dim, cfg.hidden)),
                        "bias": jnp.zeros((cfg.hidden,), jnp.float32)},
        })
    return params


def param_pspecs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Megatron-style tensor-parallel PartitionSpecs over the 'model' axis.

    Column-parallel (shard output features): qkv, mlp_in. Row-parallel (shard
    input features): attn_out, mlp_out — GSPMD inserts the block all-reduce.
    Embeddings shard the vocab dim; layernorms replicate.
    """
    ln = {"scale": P(), "bias": P()}
    block = {
        "ln1": ln, "ln2": ln,
        "qkv": {"kernel": P(None, MODEL_AXIS), "bias": P(MODEL_AXIS)},
        "attn_out": {"kernel": P(MODEL_AXIS, None), "bias": P()},
        "mlp_in": {"kernel": P(None, MODEL_AXIS), "bias": P(MODEL_AXIS)},
        "mlp_out": {"kernel": P(MODEL_AXIS, None), "bias": P()},
    }
    return {
        "tok_emb": P(MODEL_AXIS, None),
        "pos_emb": P(),
        "ln_f": ln,
        "lm_head": P(None, MODEL_AXIS),
        "blocks": [block for _ in range(cfg.layers)],
    }


def _layernorm(x, p, eps=1e-12):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# The one vocabulary of ``jax.named_scope`` names in the programs this module
# builds: flat, with no layer index (the blocks are an unrolled Python loop,
# and one name summed over the layers is what a metric wants). A scope is
# metadata on the operations traced inside it: the device trace carries the
# path (``jit(step)/transpose(jvp(mlp))/dot_general``), the compiled program
# is the same. The first nine make up the train step; the serving programs
# reuse them and add the next three. The last, ``head_rows``, is only ever
# nested inside ``lm_head``: what the one-chip masked-LM head spends on
# compacting its rows (``_head_loss``), which a metric file that lists the
# flat names alone reads under ``lm_head``. PERF.md section 3 lists what
# reads each.
SCOPES = ("embed", "attn_qkv", "attention", "attn_out", "mlp", "final_ln",
          "lm_head", "loss", "optimizer", "kv_write", "kv_gather", "sample",
          "head_rows")
# The compacted head's buffer holds a quarter of a batch's positions (rounded
# up to whole sublanes): masked-LM traffic weights 15-20 % of them, and a
# batch that weights more takes the dense head at run time.
HEAD_ROWS_DIVISOR = 4


def _qkv(bp, x):
    """Pre-attention layernorm and the fused q/k/v projection, split."""
    with jax.named_scope("attn_qkv"):
        h = _layernorm(x, bp["ln1"])
        qkv = h @ bp["qkv"]["kernel"].astype(h.dtype) \
            + bp["qkv"]["bias"].astype(h.dtype)
        return jnp.split(qkv, 3, axis=-1)


def _attn_out_mlp(bp, x, o):
    """The block after attention: output projection with its residual, then
    the layernorm, MLP and second residual."""
    with jax.named_scope("attn_out"):
        x = x + o @ bp["attn_out"]["kernel"].astype(o.dtype) \
            + bp["attn_out"]["bias"].astype(o.dtype)
    with jax.named_scope("mlp"):
        h = _layernorm(x, bp["ln2"])
        h = h @ bp["mlp_in"]["kernel"].astype(h.dtype) \
            + bp["mlp_in"]["bias"].astype(h.dtype)
        h = jax.nn.gelu(h, approximate=True)
        return x + h @ bp["mlp_out"]["kernel"].astype(h.dtype) \
            + bp["mlp_out"]["bias"].astype(h.dtype)


def _embed(params, tokens, cfg, positions=None):
    """Token plus position embeddings. ``positions`` is an index array
    shaped like ``tokens``; left out, (B, T) tokens sit at 0..T-1."""
    with jax.named_scope("embed"):
        tok = params["tok_emb"][tokens].astype(cfg.dtype)
        pos = params["pos_emb"]
        pos = pos[:tokens.shape[1]][None] if positions is None \
            else pos[positions]
        return tok + pos.astype(cfg.dtype)


def _logits(params, x, length=None):
    """Final layernorm and the output head of the serving programs: float32
    logits for every row of ``x``, or, given a prefill's (1, T, hidden)
    activations and its prompt ``length``, for the last real position only."""
    with jax.named_scope("final_ln"):
        x = _layernorm(x, params["ln_f"])
        if length is not None:
            x = lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                         keepdims=False)
    with jax.named_scope("lm_head"):
        return (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)


def _slot_attention(q, k, v, lc, pos, cfg):
    """One decode position per slot against the contiguous cache: q/k/v
    (S, hidden), ``lc["k"]``/``["v"]`` (S, L, heads, D), ``pos`` (S,) the
    write position (== current length, clamped). The new K/V land at
    ``pos``, the query attends positions 0..pos inclusive — a per-slot
    causal mask. Returns the (S, hidden) attention output and the updated
    layer cache."""
    S, H = q.shape
    L = lc["k"].shape[1]
    q = q.reshape(S, cfg.heads, cfg.head_dim)
    with jax.named_scope("kv_write"):
        rows = jnp.arange(S)
        ck = lc["k"].at[rows, pos].set(
            k.reshape(S, cfg.heads, cfg.head_dim).astype(lc["k"].dtype))
        cv = lc["v"].at[rows, pos].set(
            v.reshape(S, cfg.heads, cfg.head_dim).astype(lc["v"].dtype))
    with jax.named_scope("attention"):
        scale = 1.0 / np.sqrt(cfg.head_dim)
        s = jnp.einsum("shd,slhd->shl", q, ck.astype(q.dtype)) * scale
        mask = jnp.arange(L)[None, :] <= pos[:, None]          # (S, L)
        s = jnp.where(mask[:, None, :], s, jnp.finfo(s.dtype).min)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        o = jnp.einsum("shl,slhd->shd", p, cv.astype(p.dtype)).reshape(S, H)
    return o, {"k": ck, "v": cv}


def _slot_block(bp, x, lc, pos, cfg):
    """One transformer block of the contiguous-cache decode and draft
    steps: x (S, hidden), one position per slot."""
    q, k, v = _qkv(bp, x)
    o, lc = _slot_attention(q, k, v, lc, pos, cfg)
    return _attn_out_mlp(bp, x, o), lc


def _full_attention(q, k, v, causal: bool):
    # q,k,v: (B, H, T, D)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _attention(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh]):
    """Dispatch: full attention, the Pallas flash kernel, or sequence-parallel
    ring/Ulysses via shard_map over the 'context' axis when the mesh has one."""
    impl = cfg.attention_impl
    if impl == "flash":
        # Streamed long-context kernel (T > 1024 — shorter sequences never
        # reach here; _block routes them to the packed whole-head VMEM
        # kernel via _use_packed_kernel before the head transpose). Under a
        # dp/tp mesh the kernel runs per-device via shard_map — batch over
        # 'data', heads over 'model' (embarrassingly parallel, zero extra
        # collectives); a sequence-sharded ('context') mesh falls through to
        # ring/Ulysses below, which own that regime.
        B, nh, T, _ = q.shape
        mesh_spec = None
        if mesh is not None:
            ok = not (CONTEXT_AXIS in mesh.axis_names
                      and mesh.shape[CONTEXT_AXIS] > 1) \
                and B % mesh.shape.get(DATA_AXIS, 1) == 0 \
                and nh % mesh.shape.get(MODEL_AXIS, 1) == 0
            if ok:
                mesh_spec = P(
                    DATA_AXIS if DATA_AXIS in mesh.axis_names else None,
                    MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None,
                    None, None)
        interpret = jax.default_backend() != "tpu"
        from deeplearning4j_tpu.ops.pallas_kernels import (
            flash_attention, flash_envelope_ok)
        # flash_envelope_ok: the auto block must be 8-sublane aligned and
        # VMEM-safe — unaligned whole-T blocks do compile (Mosaic masks
        # partial tiles, verified on v5e), but that envelope is unswept
        # for perf, so odd-T sequences stay on the known-good einsum path
        if flash_envelope_ok(T) \
                and (mesh is None or mesh_spec is not None):

            def _local(ql, kl, vl):
                return flash_attention(ql, kl, vl, cfg.causal, None, None,
                                       None, interpret)

            if mesh is None:
                return _local(q, k, v)
            return shard_map(_local, mesh=mesh,
                             in_specs=(mesh_spec,) * 3, out_specs=mesh_spec,
                             check_vma=False)(q, k, v)
        # T has no usable power-of-2 block divisor, or the mesh shards the
        # sequence/doesn't divide batch+heads — fall through (ring/Ulysses
        # when a context axis exists, XLA einsum otherwise)
        if mesh is None or CONTEXT_AXIS not in mesh.axis_names \
                or mesh.shape[CONTEXT_AXIS] == 1:
            _warn_flash_fallback(
                f"streamed kernel unavailable for T={T} under mesh "
                f"{dict(mesh.shape) if mesh is not None else None}")
            return _full_attention(q, k, v, cfg.causal)
    if impl == "full" or mesh is None \
            or CONTEXT_AXIS not in mesh.axis_names \
            or mesh.shape[CONTEXT_AXIS] == 1:
        return _full_attention(q, k, v, cfg.causal)
    # 'ring' and sequence-sharded 'flash' both take the ppermute ring —
    # ring attention IS flash attention's online-softmax recurrence with
    # k/v blocks arriving over ICI instead of from HBM. When the local
    # shard fits the streamed kernel's envelope (same gate as the
    # single-device streamed route), the per-pair block attention runs in
    # Pallas with a second-ring-pass custom backward (O(T_local) memory
    # both directions); otherwise the einsum ring serves as fallback.
    if impl == "ulysses":
        fn = ulysses_attention
    else:
        T_local = q.shape[2] // mesh.shape[CONTEXT_AXIS]
        from deeplearning4j_tpu.ops.pallas_kernels import flash_envelope_ok
        fn = ring_flash_attention if flash_envelope_ok(T_local) \
            else ring_attention
    # heads sharded over 'model', sequence over 'context'
    spec = P(DATA_AXIS if DATA_AXIS in mesh.axis_names else None,
             MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None,
             CONTEXT_AXIS, None)
    mapped = shard_map(
        functools.partial(fn, axis_name=CONTEXT_AXIS, causal=cfg.causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return mapped(q, k, v)


def _packed_mesh_spec(cfg: TransformerConfig, mesh: Mesh, B: int):
    """PartitionSpec + local head count for running the packed VMEM kernel
    under ``mesh`` via shard_map — batch rides the 'data' axis and heads ride
    the 'model' axis (both embarrassingly parallel: per-device pallas_call,
    zero extra collectives). Returns None when the kernel cannot partition
    over this mesh (sequence sharded over 'context', heads or batch not
    divisible) and the einsum/ring paths must serve instead."""
    if CONTEXT_AXIS in mesh.axis_names and mesh.shape[CONTEXT_AXIS] > 1:
        return None  # sequence is sharded — ring/Ulysses own that regime
    dp = mesh.shape.get(DATA_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if B % dp or cfg.heads % tp:
        return None
    spec = P(DATA_AXIS if DATA_AXIS in mesh.axis_names else None, None,
             MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None)
    return spec, cfg.heads // tp


def _use_packed_kernel(cfg: TransformerConfig, mesh: Optional[Mesh],
                       B: int, T: int) -> bool:
    """True when attention routes to the packed-layout Pallas kernel: the
    (B, T, H*D) projections feed the kernel directly, so the (B, H, T, D)
    head transposes (6 physical copies per layer, ~5 GB/step at bench
    shapes) never materialize. Under a mesh the kernel runs per-device via
    shard_map over the (data, model) axes (round-5; a monolithic pallas_call
    over sharded operands would have forced GSPMD all-gathers, which is why
    round 4 disabled it under any mesh)."""
    if cfg.attention_impl != "flash":
        return False
    from deeplearning4j_tpu.ops.pallas_kernels import packed_kernel_shape_ok
    if not packed_kernel_shape_ok(T):
        return False
    if mesh is not None and _packed_mesh_spec(cfg, mesh, B) is None:
        # no warning here: _attention still serves this — ring/Ulysses for
        # sequence-sharded meshes, and ITS einsum fallback warns accurately
        return False
    return True


def _block(params, x, cfg: TransformerConfig, mesh: Optional[Mesh],
           return_kv: bool = False):
    B, T, H = x.shape
    q, k, v = _qkv(params, x)
    if return_kv:
        # (B, T, heads, head_dim) — the KV-cache layout. The packed (B, T,
        # H*D) projection is head-contiguous, so this reshape is free and
        # identical whichever attention impl serves below (prefill captures
        # these for the generation cache without forking the forward).
        kv_out = (k.reshape(B, T, cfg.heads, cfg.head_dim),
                  v.reshape(B, T, cfg.heads, cfg.head_dim))
    with jax.named_scope("attention"):
        o = _block_attention(q, k, v, cfg, mesh)
    x = _attn_out_mlp(params, x, o)
    if return_kv:
        return x, kv_out[0], kv_out[1]
    return x


def _block_attention(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh]):
    """Attention over the packed (B, T, H*D) projections, back in the same
    layout: the packed kernel where it applies, else ``_attention``."""
    B, T, H = q.shape
    if _use_packed_kernel(cfg, mesh, B, T):
        from deeplearning4j_tpu.ops.pallas_kernels import mha_attention_packed
        interp = jax.default_backend() != "tpu"
        if mesh is None:
            return mha_attention_packed(q, k, v, cfg.heads, cfg.causal, None,
                                        interp)
        # Per-device kernel under shard_map: batch over 'data', heads
        # over 'model' (the qkv projection is column-parallel, so the
        # packed H*D dim is already laid out head-contiguous per shard).
        # Attention never mixes batch elements or heads, so in==out
        # specs and no collectives; scale is per-head (1/sqrt(D)) and D
        # is shard-invariant.
        spec, local_heads = _packed_mesh_spec(cfg, mesh, B)

        def _local(ql, kl, vl):
            return mha_attention_packed(ql, kl, vl, local_heads,
                                        cfg.causal, None, interp)

        return shard_map(_local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    def heads(t):  # (B,T,H) -> (B,heads,T,D)
        return t.reshape(B, T, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)
    o = _attention(heads(q), heads(k), heads(v), cfg, mesh)
    return o.transpose(0, 2, 1, 3).reshape(B, T, H)


def encode(params, token_ids, cfg: TransformerConfig,
           mesh: Optional[Mesh] = None):
    """Embeddings + transformer stack + final layernorm (no lm_head)."""
    B, T = token_ids.shape
    # The package pins jax_default_matmul_precision="highest" so fp32 models
    # get exact fp32 GEMMs (reference semantics). This model casts operands
    # to bf16 explicitly — precision emulation has nothing to add, but
    # "highest" still steers XLA:TPU to a slower dot algorithm (measured
    # ~5% tokens/sec on the bench). Scope the fast default back in here.
    with jax.default_matmul_precision("default"):
        x = _embed(params, token_ids, cfg)
        blk = functools.partial(_block, cfg=cfg, mesh=mesh)
        if cfg.remat:
            blk = jax.checkpoint(
                blk, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        for bp in params["blocks"]:
            x = blk(bp, x)
        with jax.named_scope("final_ln"):
            return _layernorm(x, params["ln_f"])


def _forward_raw(params, token_ids, cfg: TransformerConfig,
                 mesh: Optional[Mesh] = None):
    """Logits of EVERY position in the COMPUTE dtype (bf16): the dense head.
    ``loss_from_logits`` consumes these directly, so the (B, T, vocab) tensor
    is never materialized in fp32 (3 GB in bf16 at the benchmark's B=96/T=512,
    twice that in fp32). This is what serving, ``forward`` and the dense
    route of ``lm_loss`` (causal models, any mesh) run; the one-chip
    masked-LM loss multiplies only the weighted rows (``_head_loss``)."""
    x = encode(params, token_ids, cfg, mesh)
    with jax.default_matmul_precision("default"), jax.named_scope("lm_head"):
        return x @ params["lm_head"].astype(x.dtype)


def forward(params, token_ids, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) fp32."""
    return _forward_raw(params, token_ids, cfg, mesh).astype(jnp.float32)


def loss_from_logits(logits, batch):
    """Weighted LM cross-entropy from compute-dtype logits, as
    logsumexp(logits) - logits[target] with fp32 accumulation: XLA fuses the
    reduction, so no (B, T, vocab) log-prob tensor is ever written to HBM
    (the log_softmax formulation materialized one in fp32)."""
    with jax.named_scope("loss"):
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(
            logits, batch["targets"][..., None],
            axis=-1)[..., 0].astype(jnp.float32)
        w = batch["weights"]
        return ((lse - tgt) * w).sum() / jnp.maximum(w.sum(), 1.0)


def _head_rows(positions: int) -> int:
    """Rows of the compacted head's buffer for a batch of ``positions``."""
    return -(-(positions // HEAD_ROWS_DIVISOR) // 8) * 8


def _dense_head_loss(x, head, targets, weights):
    """The head over every position: ``_forward_raw``'s last step."""
    return loss_from_logits(x @ head.astype(x.dtype),
                            {"targets": targets, "weights": weights})


def _compact_head_loss(x, head, targets, weights):
    """The head over the buffer's rows only. A stable sort of ``w == 0``
    puts the weighted positions first, in order, and fills the buffer with
    unweighted ones: every index is distinct (so the gather's transpose is a
    scatter without collisions) and a fill row carries its own weight, 0.
    Taken only where the weighted positions fit, so the rows left out weigh
    nothing and the gathered weights sum to the whole batch's."""
    with jax.named_scope("head_rows"):
        at = jnp.argsort(weights.reshape(-1) == 0,
                         stable=True)[:_head_rows(weights.size)]

        def rows(a):
            return a.reshape((-1,) + a.shape[2:]).at[at].get(
                unique_indices=True, mode="promise_in_bounds")
        x, targets, weights = rows(x), rows(targets), rows(weights)
    return _dense_head_loss(x, head, targets, weights)


def _routed_head_loss(of_route, x, head, targets, weights):
    """``lax.cond`` over the two routes, each passed through ``of_route``:
    compacted where the batch's weighted positions fit the buffer, else
    dense. One executable holds both; the input decides on the device."""
    with jax.default_matmul_precision("default"), jax.named_scope("lm_head"):
        with jax.named_scope("head_rows"):
            fits = jnp.count_nonzero(weights) <= _head_rows(weights.size)
        return lax.cond(fits, of_route(_compact_head_loss),
                        of_route(_dense_head_loss), x, head, targets, weights)


@jax.custom_vjp
def _head_loss(x, head, targets, weights):
    """Output head and weighted cross-entropy of final hidden states ``x``
    (B, T, hidden), on the positions that carry loss.

    A position of weight 0 adds exactly nothing to the loss or to any
    gradient, so head, logsumexp, target logit and their backward run on a
    buffer of ``_head_rows(B*T)`` gathered rows; only the order of the sums
    differs from the dense head. The conditional encloses forward AND
    backward of a route (the forward rule keeps the two gradients, computed
    inside it by ``jax.vjp``): differentiating through a ``lax.cond`` would
    make each branch return the union of both branches' residuals, and the
    compacted one would write the dense one's (B, T, vocab) logits as
    zeros."""
    return _routed_head_loss(lambda route: route, x, head, targets, weights)


def _head_loss_fwd(x, head, targets, weights):
    def with_grads(route):
        def run(x, head, targets, weights):
            loss, pull = jax.vjp(
                lambda x_, head_: route(x_, head_, targets, weights), x, head)
            return loss, pull(jnp.ones_like(loss))
        return run
    return _routed_head_loss(with_grads, x, head, targets, weights)


def _head_loss_bwd(grads, ct):
    with jax.named_scope("lm_head"):
        return tuple(g * ct.astype(g.dtype) for g in grads) + (None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def lm_loss(params, batch, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Masked/causal LM cross-entropy. batch = {'tokens': (B,T) int32,
    'targets': (B,T) int32, 'weights': (B,T) float} — weights zero out
    unmasked positions (MLM) or padding.

    Which head runs is read off the input, never set. A causal model (every
    position is a target) and any mesh (the batch is sharded; a whole-batch
    compaction is not) build the dense program: ``loss_from_logits`` of
    ``_forward_raw``. A bidirectional model on one device builds
    ``_head_loss``: at run time a batch whose weighted (``w != 0``)
    positions fit a quarter of B*T multiplies only those rows by the head,
    any other batch takes the dense head, inside the same executable."""
    if mesh is not None or cfg.causal:
        return loss_from_logits(
            _forward_raw(params, batch["tokens"], cfg, mesh), batch)
    return _head_loss(encode(params, batch["tokens"], cfg),
                      params["lm_head"], batch["targets"], batch["weights"])


def batch_pspec(mesh: Mesh) -> P:
    """Tokens (B, T): batch over 'data', sequence over 'context'."""
    d = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    c = CONTEXT_AXIS if CONTEXT_AXIS in mesh.axis_names else None
    return P(d, c)


def make_infer_last_logits(cfg: TransformerConfig,
                           mesh: Optional[Mesh] = None):
    """Build the batching-engine inference executable: token ids (B, T)
    -> last-position logits (B, vocab). ``CausalLMAdapter.infer``
    (serving/registry.py) dispatches this for InferenceEngine traffic;
    it is minted here — not in the serving layer — so every serving
    executable comes from a models/ factory and inherits forward()'s
    flash/packed-attention routing (the recompile-risk lint enforces
    the boundary). One signature per (B, T) bucket the engine's padded
    ladder produces."""

    def last_logits(params, tokens):
        return forward(params, tokens, cfg, mesh)[:, -1, :]

    return jax.jit(last_logits)


# Model families: configuration class -> the functions ``make_train_step``
# and the package's ``init_params`` / ``forward`` / ``lm_loss`` /
# ``param_pspecs`` build from (``init_params``, ``param_pspecs``, ``forward``,
# ``lm_loss``, and ``loss_and_aux``: the loss and a small pytree of counters
# computed on the device, or ``None``). A second model file registers itself
# here, so the step, the optimizer and the donation are one code path.
_FAMILIES: Dict[type, Any] = {}


def register_family(config_cls: type, family) -> None:
    _FAMILIES[config_cls] = family


def family_of(cfg):
    return _FAMILIES[type(cfg)]


def make_train_step(cfg, mesh: Optional[Mesh] = None,
                    learning_rate: float = 1e-4, weight_decay: float = 0.01):
    """Build (init_state, step) for a configuration of any registered
    family. step(params, opt_state, batch) -> (params, opt_state, loss), and
    a fourth element where the family's loss returns counters — ONE donated
    pjit executable (the anti-3.2: no per-op interpreter, no per-op JNI)."""
    tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    family = family_of(cfg)

    def init_state(params):
        return tx.init(params)

    def step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            family.loss_and_aux, has_aux=True)(params, batch, cfg, mesh)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if aux is None:
            return params, opt_state, loss
        return params, opt_state, loss, aux

    if mesh is None:
        return init_state, jax.jit(step, donate_argnums=(0, 1))

    param_sh = _shardings(cfg, mesh)
    bspec = NamedSharding(mesh, batch_pspec(mesh))
    batch_sh = {"tokens": bspec, "targets": bspec, "weights": bspec}
    repl = NamedSharding(mesh, P())

    def init_state_sharded(params):
        st = tx.init(params)
        placed = []
        for s in st:
            if hasattr(s, "mu"):  # ScaleByAdamState: mu/nu mirror the param tree
                placed.append(s._replace(
                    count=jax.device_put(s.count, repl),
                    mu=jax.device_put(s.mu, param_sh),
                    nu=jax.device_put(s.nu, param_sh)))
            else:
                placed.append(jax.tree.map(lambda l: jax.device_put(l, repl), s))
        return tuple(placed)

    # optimizer-state sharding tree, structurally derived via eval_shape so
    # the jit contract pins OUTPUT shardings too — leaving out_shardings
    # unconstrained lets GSPMD re-shard returned params (e.g. pos_emb onto
    # 'context'), which then fails the next call's in_shardings check
    abstract_params = jax.eval_shape(
        lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    opt_sh = []
    for s in jax.eval_shape(tx.init, abstract_params):
        if hasattr(s, "mu"):
            opt_sh.append(s._replace(count=repl,
                                     mu=jax.tree.map(lambda _, p: p, s.mu, param_sh),
                                     nu=jax.tree.map(lambda _, p: p, s.nu, param_sh)))
        else:
            opt_sh.append(jax.tree.map(lambda _: repl, s))
    opt_sh = tuple(opt_sh)

    jstep = jax.jit(step, donate_argnums=(0, 1),
                    in_shardings=(param_sh, opt_sh, batch_sh),
                    out_shardings=(param_sh, opt_sh, None))
    return init_state_sharded, jstep


def _shardings(cfg, mesh: Mesh):
    """param_pspecs as a matching pytree of NamedShardings; axes absent from
    the mesh (e.g. a pure-DP mesh with no 'model') degrade to replication."""
    return tree_shardings(mesh, family_of(cfg).param_pspecs(cfg))


def place_params(params, cfg, mesh: Mesh):
    """Shard a parameter pytree onto the mesh per param_pspecs."""
    return jax.device_put(params, _shardings(cfg, mesh))


# --------------------------------------------------------------------------
# Autoregressive generation: slot-based KV cache + prefill + decode_step
# --------------------------------------------------------------------------
#
# The generative path is built for continuous batching (ORCA OSDI'22 /
# vLLM SOSP'23): the cache is a FIXED-SHAPE (slots, max_len) tensor per
# layer, per-slot lengths drive the causal mask, and dead slots simply
# compute masked garbage — so the whole serving lifetime compiles exactly
# ONE decode executable (shape (slots,) regardless of how many slots are
# live) plus one prefill executable per prompt-length bucket. Without a
# cache every generated token would re-run full prefill: O(T²) work and a
# fresh jit signature per novel length.
#
# Cache pytree:  {"layers": [{"k","v"}: (slots, max_len, heads, head_dim)
#                 per layer], "lengths": (slots,) int32}
# ``lengths[s]`` counts tokens whose K/V live in slot s. Sharded over the
# mesh like the params: heads ride the 'model' axis (the qkv projection is
# column-parallel, so per-shard heads are already contiguous), slots and
# positions replicate — see kv_cache_pspecs.


def validate_block_size(block_size, max_len: int) -> int:
    """Validate a paged-cache block size and return it as a plain int:
    positive power of two (the in-kernel block index math is a
    shift/mask) no larger than ``max_len``. THE single predicate —
    shared by :func:`init_kv_cache` and the serving engine's constructor
    so the check and its named-value error messages cannot drift."""
    if not isinstance(block_size, (int, np.integer)) or block_size <= 0 \
            or (int(block_size) & (int(block_size) - 1)) != 0:
        raise ValueError(
            f"block_size must be a positive power of two (the in-kernel "
            f"block index math is a shift/mask), got {block_size!r}")
    if block_size > max_len:
        raise ValueError(
            f"block_size {block_size} exceeds max_len {max_len}: a block "
            "larger than a slot's whole capacity can never be filled and "
            "defeats paging")
    return int(block_size)


KV_DTYPES = ("float32", "int8")


def validate_kv_dtype(kv_dtype: str, block_size) -> str:
    """Validate the KV storage mode. ``"float32"`` keeps full-precision
    storage in the cache ``dtype`` (the pre-int8 behavior, bitwise);
    ``"int8"`` stores quantized values + per-token-per-head fp32 scales
    and requires the paged (block-pool) layout — the scales are
    block-shaped tensors and the dequant lives in the block read. THE
    single predicate, shared by :func:`init_kv_cache` and the serving
    engine's constructor."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    if kv_dtype == "int8" and block_size is None:
        raise ValueError(
            "kv_dtype='int8' requires the paged KV cache (pass "
            "block_size): the per-block scale tensors and on-read "
            "dequant are block-pool concepts")
    return kv_dtype


def quantize_kv(x):
    """Symmetric per-token-per-head int8 quantization of a K/V tensor
    whose trailing axis is head_dim: returns (int8 values, fp32 scales)
    with ``x ~= values * scales[..., None]``. Per-token scales mean a
    decode-step write touches only its own scale entry — no block
    requantization ever happens."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def init_kv_cache(cfg: TransformerConfig, slots: int, max_len: int,
                  dtype: Any = None, block_size: Optional[int] = None,
                  num_blocks: Optional[int] = None,
                  kv_dtype: str = "float32") -> Dict[str, Any]:
    """Allocate the generation cache. ``dtype`` defaults to the compute
    dtype (bf16 on TPU) — the cache is read every decode step, so halving
    it halves decode's dominant HBM stream.

    Two layouts share this constructor:

    - ``block_size=None`` (legacy): the contiguous per-slot layout,
      ``{"layers": [{"k","v"}: (slots, max_len, heads, head_dim)],
      "lengths": (slots,) int32}`` — every slot reserves worst-case
      ``max_len`` positions whether it uses them or not.
    - ``block_size=B`` (paged, vLLM SOSP'23): a shared block pool
      ``{"layers": [{"k","v"}: (num_blocks, B, heads, head_dim)]}``.
      Block 0 is the reserved scratch block (dead-slot writes and CoW
      no-ops land there; it is never allocated to a stream). Slot →
      position mapping lives OUTSIDE the cache, in a host-side block
      table the paged prefill/decode executables take as a gather index,
      so sequence lengths only consume the blocks they touch and a
      common prefix's blocks can be referenced by many streams.
      ``num_blocks`` defaults to the contiguous layout's capacity
      (``slots * ceil(max_len / B)``) plus the scratch block; pass a
      smaller pool to trade worst-case headroom for resident streams.

    ``kv_dtype="int8"`` (paged only) stores the pool quantized —
    ``{"k","v"}`` int8 plus ``{"k_scale","v_scale"}: (num_blocks, B,
    heads)`` fp32 per-token-per-head scales — roughly quartering the
    dominant HBM stream vs fp32 storage (head_dim bytes + 4 scale bytes
    per head-token instead of 4*head_dim) and so multiplying resident
    streams at a fixed budget. Quantization happens on write (prefill
    scatter + decode writeback, :func:`quantize_kv`), dequantization on
    read (the block gather, or fused into the paged-attention kernel).
    The default ``"float32"`` keeps full-precision storage in ``dtype``
    — the bitwise pre-int8 layout.
    """
    if max_len > cfg.max_seq:
        raise ValueError(
            f"max_len {max_len} exceeds the model's positional table "
            f"max_seq={cfg.max_seq}")
    if slots <= 0:
        raise ValueError(f"slots must be positive, got {slots}")
    if max_len <= 0:
        raise ValueError(f"max_len must be positive, got {max_len}")
    validate_kv_dtype(kv_dtype, block_size)
    dt = cfg.dtype if dtype is None else dtype
    if block_size is None:
        if num_blocks is not None:
            raise ValueError(
                f"num_blocks={num_blocks} requires block_size: the block "
                "pool is a paged-layout concept")
        shape = (slots, max_len, cfg.heads, cfg.head_dim)
        return {
            "layers": [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                       for _ in range(cfg.layers)],
            "lengths": jnp.zeros((slots,), jnp.int32),
        }
    block_size = validate_block_size(block_size, max_len)
    blocks_per_slot = -(-max_len // block_size)
    if num_blocks is None:
        num_blocks = slots * blocks_per_slot + 1
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved scratch "
            f"block), got {num_blocks}")
    shape = (num_blocks, block_size, cfg.heads, cfg.head_dim)
    if kv_dtype == "int8":
        sshape = (num_blocks, block_size, cfg.heads)
        return {
            "layers": [{"k": jnp.zeros(shape, jnp.int8),
                        "v": jnp.zeros(shape, jnp.int8),
                        "k_scale": jnp.zeros(sshape, jnp.float32),
                        "v_scale": jnp.zeros(sshape, jnp.float32)}
                       for _ in range(cfg.layers)],
        }
    return {
        "layers": [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                   for _ in range(cfg.layers)],
    }


def kv_cache_pspecs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs for the cache: heads over 'model' (matching the
    column-parallel qkv layout), slots/positions replicated. Slots stay off
    the 'data' axis on purpose: prefill writes ONE slot at a time via
    dynamic_update_slice, which a slot-sharded cache would turn into an
    all-gather per admission."""
    kv = P(None, None, MODEL_AXIS, None)
    return {
        "layers": [{"k": kv, "v": kv} for _ in range(cfg.layers)],
        "lengths": P(),
    }


def paged_kv_cache_pspecs(cfg: TransformerConfig,
                          kv_dtype: str = "float32") -> Dict[str, Any]:
    """PartitionSpecs for the paged block pool: heads over 'model' (the
    same column-parallel qkv alignment as the contiguous cache), blocks
    and in-block positions replicated — the block table is a host-side
    gather index over the (replicated) block axis, so paging adds zero
    collectives under a dp/tp mesh. int8 pools carry per-token-per-head
    scale tensors whose heads axis shards identically."""
    kv = P(None, None, MODEL_AXIS, None)
    layer = {"k": kv, "v": kv}
    if kv_dtype == "int8":
        layer = dict(layer, k_scale=P(None, None, MODEL_AXIS),
                     v_scale=P(None, None, MODEL_AXIS))
    return {"layers": [dict(layer) for _ in range(cfg.layers)]}


def grow_block_table(tables: np.ndarray, slot: int, n_entries: int,
                     block: int) -> int:
    """Append one physical block to a slot's row of the HOST-side block
    table — the on-demand allocator's whole device story. The table is
    FIXED-WIDTH (``(slots, ceil(max_len/block_size))``, zero-padded to
    the scratch block), so growing a stream's footprint is writing the
    next entry of its row: the donated paged decode executable's
    signature never changes, only the gather index it is handed each
    step. Returns the new entry count; raises when the row is already
    full (the stream's ``max_len`` worth of blocks are all mapped —
    admission bounds total length, so hitting this is a bookkeeping
    bug, not load)."""
    if not 0 <= n_entries < tables.shape[1]:
        raise ValueError(
            f"slot {slot} block-table row is full ({n_entries} of "
            f"{tables.shape[1]} entries) — cannot map block {block}")
    tables[slot, n_entries] = block
    return n_entries + 1


def place_kv_cache(cache, cfg: TransformerConfig, mesh: Mesh):
    """Shard a generation cache (any layout — the contiguous one carries
    'lengths', the paged pool does not, the int8 pool adds scales) onto
    the mesh."""
    if "lengths" in cache:
        spec = kv_cache_pspecs(cfg)
    else:
        kv_dtype = "int8" if "k_scale" in cache["layers"][0] else "float32"
        spec = paged_kv_cache_pspecs(cfg, kv_dtype)
    return jax.device_put(cache, tree_shardings(mesh, spec))


def sample_token(logits, key, temperature, top_k):
    """On-device sampling for ONE stream: greedy (``temperature <= 0``),
    temperature, and top-k — all shape-static so per-request knobs never
    mint a new executable (``top_k == 0`` disables the filter; greedy is a
    select, not a python branch). Sampling itself is the gumbel-max trick,
    so only ``key`` (not co-scheduled neighbors) touches the draw —
    bitwise-identical streams whether a slot decodes alone or co-batched.

    The gumbel draw runs under ``threefry_partitionable``: inside the
    sharded prefill/decode executables the logits are vocab-sharded
    (column-parallel lm_head), and legacy threefry generates DIFFERENT
    bits when GSPMD partitions the random op — the partitionable
    implementation is sharding-invariant, so a stream is also bitwise
    independent of the mesh shape serving it."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    desc = jnp.sort(logits)[::-1]
    kth = desc[jnp.clip(top_k - 1, 0, v - 1)]
    filtered = jnp.where(
        logits >= jnp.where(top_k > 0, kth, -jnp.inf), logits, -jnp.inf)
    greedy = temperature <= 0.0
    with jax.threefry_partitionable(True):
        gumbel = jax.random.gumbel(key, (v,), jnp.float32)
    z = jnp.where(greedy, filtered,
                  filtered / jnp.where(greedy, 1.0, temperature) + gumbel)
    return jnp.argmax(z).astype(jnp.int32)


def _sample_at(logits, key, step, temperature, top_k):
    """Per-stream sample of token index ``step``: the request's base PRNG
    key folded with the step index, so a stream's draws depend only on
    (key, step) — never on which slot or iteration served it."""
    with jax.named_scope("sample"):
        with jax.threefry_partitionable(True):
            folded = jax.random.fold_in(key, step)
        return sample_token(logits, folded, temperature, top_k)


def make_prefill(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Build the jitted prefill: run one PADDED prompt through the standard
    forward (the same ``_block`` — flash/packed attention routing included),
    write its per-layer K/V into cache slot ``slot``, and sample token 0.

    ``prefill(params, cache, tokens, slot, length, key, temperature, top_k)
    -> (cache, token0)`` with tokens (1, T_bucket) int32 and ``length`` the
    real prompt length. One executable per T bucket; the cache is donated so
    prefill updates in place. Prompts prefill one at a time (batch dim 1):
    batching prompts too would square the signature ladder (T × B buckets)
    and break per-request bitwise determinism."""
    if not cfg.causal:
        raise ValueError("generation needs a causal LM: set "
                         "TransformerConfig(causal=True)")

    def prefill(params, cache, tokens, slot, length, key, temperature, top_k):
        _, T = tokens.shape
        slot = jnp.asarray(slot, jnp.int32)
        z = jnp.zeros((), jnp.int32)   # literal 0s would be int64 under x64
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, k, v = _block(bp, x, cfg, mesh, return_kv=True)
                with jax.named_scope("kv_write"):
                    layers.append({
                        "k": lax.dynamic_update_slice(
                            lc["k"], k.astype(lc["k"].dtype),
                            (slot, z, z, z)),
                        "v": lax.dynamic_update_slice(
                            lc["v"], v.astype(lc["v"].dtype),
                            (slot, z, z, z)),
                    })
            logits = _logits(params, x, length)
        token0 = _sample_at(logits, key, 0, temperature, top_k)
        new_cache = {"layers": layers,
                     "lengths": cache["lengths"].at[slot].set(length)}
        return new_cache, token0

    if mesh is None:
        return jax.jit(prefill, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, kv_cache_pspecs(cfg))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        prefill, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh, repl, repl, repl, repl, repl, repl),
        out_shardings=(cache_sh, repl))


def make_decode_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Build THE decode executable: one token for every slot, live or dead.

    ``decode_step(params, cache, tokens, live, keys, steps, temperatures,
    top_ks) -> (cache, next_tokens)`` where every argument after ``cache``
    is a (slots,)-leading array — tokens int32 (last sampled token per
    slot), live bool (dead slots compute masked garbage and keep their
    lengths), keys (slots, 2) uint32 per-request base PRNG keys, steps
    int32 (index of the token being sampled). Shape is (slots,) no matter
    how many slots are occupied, so this compiles EXACTLY ONCE per engine
    lifetime; the cache is donated, so decode is a true in-place update.

    Per-slot math is row-wise (layernorm, GEMMs, masked attention over the
    slot's own cache rows, gumbel-max under the slot's own folded key), so
    a stream's tokens are bitwise-independent of its co-tenants — the
    property continuous batching needs to be transparent to callers."""
    if not cfg.causal:
        raise ValueError("generation needs a causal LM: set "
                         "TransformerConfig(causal=True)")

    def decode_step(params, cache, tokens, live, keys, steps,
                    temperatures, top_ks):
        lengths = cache["lengths"]
        max_len = cache["layers"][0]["k"].shape[1]
        pos = jnp.clip(lengths, 0, max_len - 1)
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg, pos)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, lc = _slot_block(bp, x, lc, pos, cfg)
                layers.append(lc)
            logits = _logits(params, x)
        next_tokens = jax.vmap(_sample_at)(logits, keys, steps,
                                           temperatures, top_ks)
        new_cache = {"layers": layers,
                     "lengths": jnp.where(live, lengths + 1, lengths)}
        return new_cache, next_tokens

    if mesh is None:
        return jax.jit(decode_step, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, kv_cache_pspecs(cfg))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        decode_step, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh) + (repl,) * 6,
        out_shardings=(cache_sh, repl))


# --------------------------------------------------------------------------
# Paged generation: block-pool KV cache + block-table gather decode
# --------------------------------------------------------------------------
#
# The contiguous cache above reserves worst-case (slots, max_len) rows, so
# HBM — not compute — caps resident streams. The paged variants (vLLM,
# Kwon et al. SOSP '23) store K/V in a shared pool of fixed-size blocks and
# address it through a per-slot FIXED-SHAPE block table passed from the
# host: decode gathers ``pool[block_table]`` back into the exact (S, L,
# heads, head_dim) layout the contiguous attention consumed, so the math —
# and crucially the compiled-signature story — is unchanged: ONE donated
# decode executable for the engine's lifetime, one prefill per prompt
# bucket. Sequence lengths host-side; copy-on-write for shared prefixes is
# a (src, dst) block-copy argument folded INTO the decode executable (a
# no-op self-copy of the scratch block on steps with nothing to CoW), so
# prefix sharing mints no third executable.


def make_paged_prefill(cfg: TransformerConfig, block_size: int,
                       mesh: Optional[Mesh] = None,
                       kv_dtype: str = "float32"):
    """Build the jitted paged prefill: one PADDED prompt through the
    standard forward (the same ``_block``), its per-layer K/V scattered
    into the physical blocks named by ``block_row``, and token 0 sampled.

    ``prefill(params, cache, tokens, block_row, length, key, temperature,
    top_k, step) -> (cache, token0)`` with tokens (1, T_bucket) int32 and
    ``block_row`` (ceil(T_bucket/block_size),) int32 physical block ids —
    entries past the prompt's real blocks point at the reserved scratch
    block 0, so padding K/V lands in scratch, never in a live block. One
    executable per T bucket; the cache (block pool) is donated. Unlike
    the contiguous prefill there is no ``slot`` argument: lengths live on
    the host, and the block row alone names where this prompt's K/V go.

    ``step`` is the SAMPLE index the trailing token draw folds into the
    request key (``_sample_at``): 0 for a fresh prompt (the pre-existing
    behavior, bitwise-unchanged), and the victim's next token index when
    a preempted stream recomputes through prefill with its
    generated-so-far tokens appended to the prompt — per-request keys
    fold the token index, so the resumed draw is position-stable and the
    resumed stream bitwise-matches its unpreempted run.

    ``kv_dtype="int8"``: quantization is FOLDED into the scatter — each
    block's values land int8 with their per-token scales written beside
    them, so the fp-sized prompt K/V never touches the pool."""
    if not cfg.causal:
        raise ValueError("generation needs a causal LM: set "
                         "TransformerConfig(causal=True)")
    validate_kv_dtype(kv_dtype, block_size)

    def prefill(params, cache, tokens, block_row, length, key,
                temperature, top_k, step):
        _, T = tokens.shape
        nb = block_row.shape[0]
        pad = nb * block_size - T
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, k, v = _block(bp, x, cfg, mesh, return_kv=True)
                with jax.named_scope("kv_write"):
                    kb = jnp.pad(k[0], ((0, pad), (0, 0), (0, 0))).reshape(
                        nb, block_size, cfg.heads, cfg.head_dim)
                    vb = jnp.pad(v[0], ((0, pad), (0, 0), (0, 0))).reshape(
                        nb, block_size, cfg.heads, cfg.head_dim)
                    if kv_dtype == "int8":
                        kq, ks = quantize_kv(kb)
                        vq, vs = quantize_kv(vb)
                        layers.append({
                            "k": lc["k"].at[block_row].set(kq),
                            "v": lc["v"].at[block_row].set(vq),
                            "k_scale": lc["k_scale"].at[block_row].set(ks),
                            "v_scale": lc["v_scale"].at[block_row].set(vs),
                        })
                        continue
                    layers.append({
                        "k": lc["k"].at[block_row].set(
                            kb.astype(lc["k"].dtype)),
                        "v": lc["v"].at[block_row].set(
                            vb.astype(lc["v"].dtype)),
                    })
            logits = _logits(params, x, length)
        token0 = _sample_at(logits, key, step, temperature, top_k)
        return {"layers": layers}, token0

    if mesh is None:
        return jax.jit(prefill, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, paged_kv_cache_pspecs(cfg, kv_dtype))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        prefill, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh) + (repl,) * 7,
        out_shardings=(cache_sh, repl))


def _paged_attention_mesh_spec(cfg: TransformerConfig, mesh: Mesh):
    """PartitionSpecs for running the fused paged-attention kernel under
    ``mesh`` via shard_map — heads ride the 'model' axis (matching the
    column-parallel qkv layout), block/table/position axes replicate, so
    the per-device kernel is embarrassingly parallel over heads: zero
    extra collectives, exactly the packed-kernel pattern. Returns None
    when the kernel cannot partition (heads not divisible)."""
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if cfg.heads % tp:
        return None
    m = MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None
    return {"q": P(None, m, None), "pool": P(None, None, m, None),
            "scale": P(None, None, m), "repl": P()}


def make_paged_decode_step(cfg: TransformerConfig, block_size: int,
                           mesh: Optional[Mesh] = None,
                           kv_dtype: str = "float32",
                           paged_attention: str = "gather"):
    """Build THE paged decode executable: one token for every slot.

    ``decode_step(params, cache, tables, lengths, tokens, keys, steps,
    temperatures, top_ks, cow_src, cow_dst) -> (cache, next_tokens)``
    where ``tables`` is the (slots, max_blocks_per_slot) int32 block table
    (a dead slot's row is all scratch-block 0 — its write lands in
    scratch, its gather reads masked garbage), ``lengths`` (slots,) int32
    the host-tracked token counts, and ``cow_src``/``cow_dst`` (slots,)
    int32 drive the copy-on-write: each slot's dst block is overwritten
    with its src block BEFORE this step's K/V write and gather (slots with
    nothing to CoW pass src == dst == 0, a scratch self-copy). Every
    argument is fixed-shape, so this compiles EXACTLY ONCE per engine
    lifetime — the block-table gather preserves the contiguous path's
    one-donated-executable invariant while the pool replaces the
    per-slot worst-case reservation.

    ``paged_attention`` selects how the attention read happens:

    - ``"gather"`` (default): XLA materializes ``pool[tables]`` back into
      the (S, L, heads, D) layout the contiguous attention consumed —
      same einsums, same mask, bitwise-stable vs PR 6 at
      ``kv_dtype="float32"``, but the single-token read pays a full
      HBM round-trip of the gathered view every step.
    - ``"fused"``: the Pallas :func:`~deeplearning4j_tpu.ops.
      pallas_kernels.paged_decode_attention` kernel streams each slot's
      blocks through VMEM behind a scalar-prefetched block table — the
      (S, L) view never exists in HBM, and int8 dequant fuses into the
      same pass. Numerically equivalent within fp tolerance (online
      softmax reassociates the reduction); still the SAME single donated
      executable and signature.

    ``kv_dtype="int8"`` stores the pool quantized (see
    :func:`init_kv_cache`): the decode writeback quantizes the new token
    (per-token scales — no block requantization), the CoW copy moves
    scales alongside values, and both attention routes dequantize on
    read."""
    if not cfg.causal:
        raise ValueError("generation needs a causal LM: set "
                         "TransformerConfig(causal=True)")
    validate_kv_dtype(kv_dtype, block_size)
    if paged_attention not in ("gather", "fused"):
        raise ValueError(
            f"paged_attention must be 'gather' or 'fused', "
            f"got {paged_attention!r}")
    quantized = kv_dtype == "int8"
    mesh_spec = None
    if paged_attention == "fused" and mesh is not None:
        mesh_spec = _paged_attention_mesh_spec(cfg, mesh)
        if mesh_spec is None:
            raise ValueError(
                f"paged_attention='fused' cannot shard {cfg.heads} heads "
                f"over the mesh's {mesh.shape.get(MODEL_AXIS, 1)}-way "
                f"'{MODEL_AXIS}' axis; use paged_attention='gather' or a "
                "dividing mesh")

    def _fused_attention(q, ck, cv, cks, cvs, tables, pos, scale):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            paged_decode_attention)
        interp = jax.default_backend() != "tpu"

        def _local(ql, kl, vl, tb, ps, *scales):
            ksl, vsl = scales if quantized else (None, None)
            return paged_decode_attention(
                ql, kl, vl, tb, ps, block_size=block_size, scale=scale,
                k_scale=ksl, v_scale=vsl, interpret=interp)

        if mesh is None:
            return _local(q, ck, cv, tables, pos,
                          *((cks, cvs) if quantized else ()))
        ms = mesh_spec
        in_specs = (ms["q"], ms["pool"], ms["pool"], ms["repl"],
                    ms["repl"]) + ((ms["scale"],) * 2 if quantized else ())
        return shard_map(_local, mesh=mesh, in_specs=in_specs,
                         out_specs=ms["q"], check_vma=False)(
            q, ck, cv, tables, pos,
            *((cks, cvs) if quantized else ()))

    def decode_block(bp, x, lc, tables, pos, cow_src, cow_dst):
        # x: (S, hidden); lc["k"]/["v"]: (NB, B, heads, D); tables:
        # (S, max_blocks); pos: (S,) logical write position. CoW first,
        # then the new K/V write, then the attention read — data
        # dependence orders them, so the read sees both.
        S, H = x.shape
        nb = tables.shape[1]
        L = nb * block_size
        q, k, v = _qkv(bp, x)
        q = q.reshape(S, cfg.heads, cfg.head_dim)
        with jax.named_scope("kv_write"):
            rows = jnp.arange(S)
            ck = lc["k"].at[cow_dst].set(lc["k"][cow_src])
            cv = lc["v"].at[cow_dst].set(lc["v"][cow_src])
            blk = pos // block_size
            off = pos % block_size
            pb = tables[rows, blk]                                 # (S,)
            cks = cvs = None
            if quantized:
                cks = lc["k_scale"].at[cow_dst].set(lc["k_scale"][cow_src])
                cvs = lc["v_scale"].at[cow_dst].set(lc["v_scale"][cow_src])
                kq, ks = quantize_kv(k.reshape(S, cfg.heads, cfg.head_dim))
                vq, vs = quantize_kv(v.reshape(S, cfg.heads, cfg.head_dim))
                ck = ck.at[pb, off].set(kq)
                cv = cv.at[pb, off].set(vq)
                cks = cks.at[pb, off].set(ks)
                cvs = cvs.at[pb, off].set(vs)
            else:
                ck = ck.at[pb, off].set(
                    k.reshape(S, cfg.heads, cfg.head_dim).astype(ck.dtype))
                cv = cv.at[pb, off].set(
                    v.reshape(S, cfg.heads, cfg.head_dim).astype(cv.dtype))
        scale = 1.0 / np.sqrt(cfg.head_dim)
        if paged_attention == "fused":
            with jax.named_scope("attention"):
                o = _fused_attention(q, ck, cv, cks, cvs, tables, pos,
                                     scale).reshape(S, H).astype(x.dtype)
        else:
            # block-table gather: back to the exact (S, L, heads, D)
            # layout the contiguous attention consumed — same einsums,
            # same mask (int8 dequantizes into the compute dtype first)
            with jax.named_scope("kv_gather"):
                gk = ck[tables].reshape(S, L, cfg.heads, cfg.head_dim)
                gv = cv[tables].reshape(S, L, cfg.heads, cfg.head_dim)
                if quantized:
                    gks = cks[tables].reshape(S, L, cfg.heads)
                    gvs = cvs[tables].reshape(S, L, cfg.heads)
                    gk = (gk.astype(jnp.float32)
                          * gks[..., None]).astype(q.dtype)
                    gv = (gv.astype(jnp.float32)
                          * gvs[..., None]).astype(q.dtype)
            with jax.named_scope("attention"):
                s = jnp.einsum("shd,slhd->shl", q, gk.astype(q.dtype)) * scale
                mask = jnp.arange(L)[None, :] <= pos[:, None]      # (S, L)
                s = jnp.where(mask[:, None, :], s, jnp.finfo(s.dtype).min)
                p = jax.nn.softmax(s.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
                o = jnp.einsum("shl,slhd->shd", p,
                               gv.astype(p.dtype)).reshape(S, H)
        x = _attn_out_mlp(bp, x, o)
        out = {"k": ck, "v": cv}
        if quantized:
            out.update(k_scale=cks, v_scale=cvs)
        return x, out

    def decode_step(params, cache, tables, lengths, tokens, keys, steps,
                    temperatures, top_ks, cow_src, cow_dst):
        L = tables.shape[1] * block_size
        pos = jnp.clip(lengths, 0, min(L, cfg.max_seq) - 1)
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg, pos)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, lc = decode_block(bp, x, lc, tables, pos, cow_src,
                                     cow_dst)
                layers.append(lc)
            logits = _logits(params, x)
        next_tokens = jax.vmap(_sample_at)(logits, keys, steps,
                                           temperatures, top_ks)
        return {"layers": layers}, next_tokens

    if mesh is None:
        return jax.jit(decode_step, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, paged_kv_cache_pspecs(cfg, kv_dtype))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        decode_step, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh) + (repl,) * 9,
        out_shardings=(cache_sh, repl))


# --------------------------------------------------------------------------
# Speculative decoding: draft-model executables + k-token verify step
# --------------------------------------------------------------------------
#
# Speculative decoding (Leviathan et al., ICML'23) amortizes decode's
# memory-bandwidth cost: a small DRAFT model proposes k tokens one at a
# time (cheap — its whole KV stream is tiny), then the target model scores
# all k+1 positions in ONE fixed-shape verify step and commits the longest
# proposal prefix its own sampling agrees with. The adaptation here is
# exact-match verification against the target's OWN deterministic samples:
# every token of a stream is already a pure function of (request key, token
# index) via ``_sample_at``, so the verify step computes the target's
# samples g_0..g_k at the k+1 positions and acceptance only decides HOW
# MANY of them commit this turn — the emitted values are ALWAYS the
# target's, so a speculative stream is bitwise the non-speculative one at
# ANY temperature, not just greedy. Speedup comes from acceptance, never
# from changed sampling.
#
# The draft model keeps a CONTIGUOUS (slots, max_len) cache with NO
# device-side lengths — the scheduler passes lengths per call, so
# rewinding a rejected tail after verify is host arithmetic, not a device
# op. Both factories preserve the one-donated-executable discipline: one
# draft step, one verify step, for the engine's lifetime.


def init_draft_kv_cache(cfg: TransformerConfig, slots: int, max_len: int,
                        dtype: Any = None) -> Dict[str, Any]:
    """Allocate the draft model's contiguous KV cache: the legacy
    (slots, max_len, heads, head_dim) layout WITHOUT the device-side
    ``lengths`` leaf — draft positions are host-tracked so the serving
    scheduler can rewind a rejected speculation tail for free (the next
    turn simply passes a smaller length and overwrites)."""
    if max_len > cfg.max_seq:
        raise ValueError(
            f"max_len {max_len} exceeds the draft model's positional "
            f"table max_seq={cfg.max_seq}")
    dt = cfg.dtype if dtype is None else dtype
    shape = (slots, max_len, cfg.heads, cfg.head_dim)
    return {"layers": [{"k": jnp.zeros(shape, dt),
                        "v": jnp.zeros(shape, dt)}
                       for _ in range(cfg.layers)]}


def draft_kv_cache_pspecs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs for the draft cache: identical head-over-'model'
    layout as :func:`kv_cache_pspecs`, minus the lengths leaf."""
    kv = P(None, None, MODEL_AXIS, None)
    return {"layers": [{"k": kv, "v": kv} for _ in range(cfg.layers)]}


def place_draft_kv_cache(cache, cfg: TransformerConfig, mesh: Mesh):
    """Shard a draft KV cache onto ``mesh`` per
    :func:`draft_kv_cache_pspecs` (heads over the 'model' axis)."""
    return jax.device_put(cache,
                          tree_shardings(mesh, draft_kv_cache_pspecs(cfg)))


def make_draft_prefill(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Build the jitted draft prefill: one PADDED prompt through the draft
    model's standard forward, its per-layer K/V written into cache slot
    ``slot``. ``draft_prefill(params, cache, tokens, slot) -> cache`` with
    tokens (1, T_bucket) int32. No sampling — the draft's first proposal
    is drawn by :func:`make_draft_step` feeding the target's last sampled
    token. One executable per T bucket (the engine reuses its prompt
    ladder); the cache is donated. Padding K/V past the real prompt lands
    in the slot row but is masked by every later draft step's causal mask,
    exactly the contiguous target layout's convention."""
    if not cfg.causal:
        raise ValueError("speculative drafting needs a causal LM: set "
                         "TransformerConfig(causal=True)")

    def draft_prefill(params, cache, tokens, slot):
        _, T = tokens.shape
        slot = jnp.asarray(slot, jnp.int32)
        z = jnp.zeros((), jnp.int32)
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, k, v = _block(bp, x, cfg, mesh, return_kv=True)
                with jax.named_scope("kv_write"):
                    layers.append({
                        "k": lax.dynamic_update_slice(
                            lc["k"], k.astype(lc["k"].dtype),
                            (slot, z, z, z)),
                        "v": lax.dynamic_update_slice(
                            lc["v"], v.astype(lc["v"].dtype),
                            (slot, z, z, z)),
                    })
        return {"layers": layers}

    if mesh is None:
        return jax.jit(draft_prefill, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, draft_kv_cache_pspecs(cfg))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        draft_prefill, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh, repl, repl),
        out_shardings=cache_sh)


def make_draft_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Build THE draft decode executable: one proposed token per slot.

    ``draft_step(params, cache, tokens, lengths, keys, steps,
    temperatures, top_ks) -> (cache, proposals)`` — the contiguous
    :func:`make_decode_step` math with ``lengths`` passed from the HOST
    (the draft cache has no device lengths and no ``live`` mask: dead or
    draft-cold slots compute masked garbage the scheduler ignores). The
    scheduler invokes this executable k times per speculative turn, each
    call feeding the previous proposal at the next position; ``steps``
    carries the TARGET token index each proposal predicts, so the gumbel
    draw folds the exact key/step the verify step will fold — a draft
    whose logits track the target's proposes the target's own sample with
    high probability even at temperature > 0. Shape is (slots,) always,
    so this compiles EXACTLY ONCE; the cache is donated."""
    if not cfg.causal:
        raise ValueError("speculative drafting needs a causal LM: set "
                         "TransformerConfig(causal=True)")

    def draft_step(params, cache, tokens, lengths, keys, steps,
                   temperatures, top_ks):
        max_len = cache["layers"][0]["k"].shape[1]
        pos = jnp.clip(lengths, 0, min(max_len, cfg.max_seq) - 1)
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg, pos)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, lc = _slot_block(bp, x, lc, pos, cfg)
                layers.append(lc)
            logits = _logits(params, x)
        proposals = jax.vmap(_sample_at)(logits, keys, steps,
                                         temperatures, top_ks)
        return {"layers": layers}, proposals

    if mesh is None:
        return jax.jit(draft_step, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, draft_kv_cache_pspecs(cfg))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        draft_step, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh) + (repl,) * 6,
        out_shardings=(cache_sh, repl))


def make_verify_step(cfg: TransformerConfig, block_size: int, k: int,
                     mesh: Optional[Mesh] = None,
                     kv_dtype: str = "float32",
                     paged_attention: str = "gather"):
    """Build THE speculative verify executable: score k+1 positions per
    slot in one step and count the accepted proposal prefix on device.

    ``verify_step(params, cache, tables, lengths, tokens, keys, steps,
    temperatures, top_ks, cow_src, cow_dst) -> (cache, samples,
    accepted)`` — :func:`make_paged_decode_step` extended from one query
    per slot to ``k + 1``: ``tokens`` is (slots, k+1) int32 with column 0
    the slot's last committed token and columns 1..k the draft proposals
    d_1..d_k; K/V for ALL k+1 tokens are written at positions length..
    length+k, each query position length+j attends its own causal prefix
    (positions <= length+j), and ``samples[:, j]`` is the TARGET's own
    deterministic sample for token index ``steps + j`` — per-position
    attention reuses the single-query decode math exactly, so
    ``samples[:, j]`` is bitwise what ``decode_step`` would have sampled
    at that point given the same history. ``accepted[:, ]`` counts the
    longest prefix with ``tokens[:, j+1] == samples[:, j]`` — the
    rejection-sampling acceptance under deterministic gumbel-max
    (exact-match, temperature-independent). The scheduler commits
    ``min(accepted+1, k)`` of the samples; position length+accepted+1's
    K/V (a rejected proposal's) is overwritten by the next turn's write
    at the new length, the same convention a dead slot's garbage follows.

    Writes that would land past the pool capacity or ``cfg.max_seq`` are
    routed to the reserved scratch block 0 instead of clamping — a
    clamped scatter near the boundary would collide multiple positions
    onto a LIVE block entry and corrupt committed K/V; scratch-routing
    keeps dead/overflow garbage where dead-slot garbage already lives.
    Dead slots compute masked garbage across all k+1 positions exactly as
    they do in decode_step. Both attention routes (``"gather"`` and the
    fused Pallas kernel — invoked once per query position inside the SAME
    executable) and both ``kv_dtype`` modes are supported; every argument
    is fixed-shape, so this compiles EXACTLY ONCE per engine lifetime and
    the engine's executable bound grows to buckets + 2 (prefill ladder +
    decode + verify)."""
    if not cfg.causal:
        raise ValueError("generation needs a causal LM: set "
                         "TransformerConfig(causal=True)")
    if k < 1:
        raise ValueError(
            f"verify needs k >= 1 proposed tokens per turn, got {k} — "
            "k == 0 IS the plain decode_step; the engine falls back to "
            "it rather than minting a degenerate verify executable")
    validate_kv_dtype(kv_dtype, block_size)
    if paged_attention not in ("gather", "fused"):
        raise ValueError(
            f"paged_attention must be 'gather' or 'fused', "
            f"got {paged_attention!r}")
    T = k + 1
    quantized = kv_dtype == "int8"
    mesh_spec = None
    if paged_attention == "fused" and mesh is not None:
        mesh_spec = _paged_attention_mesh_spec(cfg, mesh)
        if mesh_spec is None:
            raise ValueError(
                f"paged_attention='fused' cannot shard {cfg.heads} heads "
                f"over the mesh's {mesh.shape.get(MODEL_AXIS, 1)}-way "
                f"'{MODEL_AXIS}' axis; use paged_attention='gather' or a "
                "dividing mesh")

    def _fused_attention(q, ck, cv, cks, cvs, tables, pos, scale):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            paged_decode_attention)
        interp = jax.default_backend() != "tpu"

        def _local(ql, kl, vl, tb, ps, *scales):
            ksl, vsl = scales if quantized else (None, None)
            return paged_decode_attention(
                ql, kl, vl, tb, ps, block_size=block_size, scale=scale,
                k_scale=ksl, v_scale=vsl, interpret=interp)

        if mesh is None:
            return _local(q, ck, cv, tables, pos,
                          *((cks, cvs) if quantized else ()))
        ms = mesh_spec
        in_specs = (ms["q"], ms["pool"], ms["pool"], ms["repl"],
                    ms["repl"]) + ((ms["scale"],) * 2 if quantized else ())
        return shard_map(_local, mesh=mesh, in_specs=in_specs,
                         out_specs=ms["q"], check_vma=False)(
            q, ck, cv, tables, pos,
            *((cks, cvs) if quantized else ()))

    def verify_block(bp, x, lc, tables, pos, cow_src, cow_dst):
        # x: (S, T, hidden); lc pool tensors: (NB, B, heads, D); pos:
        # (S,) the FIRST write position (== current length, clamped).
        # CoW first, then all T K/V writes, then the per-position
        # attention reads — data dependence orders them.
        S, _T, H = x.shape
        nb = tables.shape[1]
        L = nb * block_size
        Lcap = min(L, cfg.max_seq)
        q, kx, vx = _qkv(bp, x)
        q = q.reshape(S, T, cfg.heads, cfg.head_dim)
        with jax.named_scope("kv_write"):
            rows = jnp.arange(S)
            ck = lc["k"].at[cow_dst].set(lc["k"][cow_src])
            cv = lc["v"].at[cow_dst].set(lc["v"][cow_src])
            # (S, T) write positions; overflow routes to the scratch block —
            # NOT a clamp: a clamped position would scatter-collide onto a
            # live block entry and corrupt committed K/V near the boundary
            posm = pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :]
            valid = posm < Lcap
            blk = jnp.minimum(posm, L - 1) // block_size
            off = posm % block_size
            pb = jnp.where(valid, tables[rows[:, None], blk], 0)
            cks = cvs = None
            if quantized:
                cks = lc["k_scale"].at[cow_dst].set(lc["k_scale"][cow_src])
                cvs = lc["v_scale"].at[cow_dst].set(lc["v_scale"][cow_src])
                kq, ks = quantize_kv(
                    kx.reshape(S, T, cfg.heads, cfg.head_dim))
                vq, vs = quantize_kv(
                    vx.reshape(S, T, cfg.heads, cfg.head_dim))
                ck = ck.at[pb, off].set(kq)
                cv = cv.at[pb, off].set(vq)
                cks = cks.at[pb, off].set(ks)
                cvs = cvs.at[pb, off].set(vs)
            else:
                ck = ck.at[pb, off].set(
                    kx.reshape(S, T, cfg.heads, cfg.head_dim).astype(ck.dtype))
                cv = cv.at[pb, off].set(
                    vx.reshape(S, T, cfg.heads, cfg.head_dim).astype(cv.dtype))
        scale = 1.0 / np.sqrt(cfg.head_dim)
        if paged_attention == "fused":
            with jax.named_scope("attention"):
                outs = [
                    _fused_attention(
                        q[:, j], ck, cv, cks, cvs, tables,
                        jnp.minimum(pos + j, Lcap - 1), scale)
                    for j in range(T)]
                o = jnp.stack(outs, axis=1).reshape(S, T, H).astype(x.dtype)
        else:
            with jax.named_scope("kv_gather"):
                gk = ck[tables].reshape(S, L, cfg.heads, cfg.head_dim)
                gv = cv[tables].reshape(S, L, cfg.heads, cfg.head_dim)
                if quantized:
                    gks = cks[tables].reshape(S, L, cfg.heads)
                    gvs = cvs[tables].reshape(S, L, cfg.heads)
                    gk = (gk.astype(jnp.float32)
                          * gks[..., None]).astype(q.dtype)
                    gv = (gv.astype(jnp.float32)
                          * gvs[..., None]).astype(q.dtype)
            with jax.named_scope("attention"):
                # one single-query attention per position — the EXACT einsum
                # shapes decode_step compiles, so each position's output (and
                # therefore its sample) is bitwise the sequential decode's
                outs = []
                for j in range(T):
                    pj = jnp.minimum(pos + j, Lcap - 1)
                    s = jnp.einsum("shd,slhd->shl", q[:, j],
                                   gk.astype(q.dtype)) * scale
                    mask = jnp.arange(L)[None, :] <= pj[:, None]
                    s = jnp.where(mask[:, None, :], s, jnp.finfo(s.dtype).min)
                    p = jax.nn.softmax(s.astype(jnp.float32),
                                       axis=-1).astype(q.dtype)
                    outs.append(jnp.einsum("shl,slhd->shd", p,
                                           gv.astype(p.dtype)))
                o = jnp.stack(outs, axis=1).reshape(S, T, H)
        x = _attn_out_mlp(bp, x, o)
        out = {"k": ck, "v": cv}
        if quantized:
            out.update(k_scale=cks, v_scale=cvs)
        return x, out

    def verify_step(params, cache, tables, lengths, tokens, keys, steps,
                    temperatures, top_ks, cow_src, cow_dst):
        L = tables.shape[1] * block_size
        Lcap = min(L, cfg.max_seq)
        pos = jnp.clip(lengths, 0, Lcap - 1)
        posm = jnp.minimum(
            pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :],
            Lcap - 1)
        with jax.default_matmul_precision("default"):
            x = _embed(params, tokens, cfg, posm)
            layers = []
            for bp, lc in zip(params["blocks"], cache["layers"]):
                x, lc = verify_block(bp, x, lc, tables, pos, cow_src,
                                     cow_dst)
                layers.append(lc)
            logits = _logits(params, x)

        def _sample_row(lg, key, step0, temperature, top_k):
            st = step0 + jnp.arange(T, dtype=jnp.int32)
            return jax.vmap(
                lambda l, s: _sample_at(l, key, s, temperature, top_k)
            )(lg, st)

        samples = jax.vmap(_sample_row)(logits, keys, steps,
                                        temperatures, top_ks)
        matches = (tokens[:, 1:] == samples[:, :k]).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
        return {"layers": layers}, samples, accepted.astype(jnp.int32)

    if mesh is None:
        return jax.jit(verify_step, donate_argnums=(1,))
    param_sh = _shardings(cfg, mesh)
    cache_sh = tree_shardings(mesh, paged_kv_cache_pspecs(cfg, kv_dtype))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        verify_step, donate_argnums=(1,),
        in_shardings=(param_sh, cache_sh) + (repl,) * 9,
        out_shardings=(cache_sh, repl, repl))


register_family(TransformerConfig, types.SimpleNamespace(
    init_params=init_params, param_pspecs=param_pspecs, forward=forward,
    lm_loss=lm_loss,
    loss_and_aux=lambda params, batch, cfg, mesh: (
        lm_loss(params, batch, cfg, mesh), None)))
