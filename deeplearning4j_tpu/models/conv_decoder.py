"""Gated short-convolution / attention decoder with a leading dense layer and
routed SwiGLU experts — the fourth family.

The block of the LFM2 mixture-of-experts class of decoders (``model_type``
``lfm2_moe``): every layer is **two** residual steps on the stream ``x``
(T x hidden), RMSNorm, no bias anywhere::

    h = x + mixer(rmsnorm(x; g_op))
    y = h + ffn(rmsnorm(h; g_ffn))

and both halves change **by layer**: the mixer by ``cfg.mixers`` (``conv`` a
gated short convolution, ``full_attention`` grouped-query attention), the
feed-forward part by ``cfg.dense_layers`` (that many leading layers carry a
dense gated-SiLU MLP, every other one a layer of routed experts).

``conv``, with ``u = rmsnorm(x; g_op)`` and ``K = conv_kernel`` taps::

    [B | C | X] = u W_in            hidden -> 3 x hidden, three equal chunks
    Z   = B * X
    V_t = sum_{j<K} w_j * Z_(t-j)   causal, depthwise, zeros before position
    out = (C * V) W_out             0, no bias, no activation

``full_attention``: ``q, k, v = u W_q, u W_k, u W_v``; ``q`` and ``k`` through
an RMSNorm of their own **per head**, over the head's dimensions, before the
rotation; rotate-half RoPE over the whole head; causal softmax attention over
the whole sequence (``flash_attention`` with its kv group, no window);
``out = o W_o``.

Dense feed-forward, with ``m = rmsnorm(h; g_ffn)``:
``(silu(m W_gate) * (m W_up)) W_down``, hidden -> ``mlp_dim`` -> hidden.

Routed feed-forward::

    s = sigmoid(m W_r)                    float32, experts_total outputs
    S = top-k(s + b)                      the bias b chooses and does not weigh
    w_e = scale * s_e / (sum_S s + 1e-6)
    out = sum_{e in S, e held here}
          w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e

**One chip's share** is by experts alone: ``experts_count`` of
``experts_total`` from ``experts_offset``, and ``vocab_size`` rows of the
vocabulary; mixers, the dense MLP, router, bias and norms are whole on every
chip. The layer computes what its own experts give; what the absent experts
would add is left out, and that partial result goes on to the next layer. On
one chip the layer runs without its exchange and nothing here stands in for
the other chips. **A share does not train its router**: where
``experts_count < experts_total`` the router's scores carry no gradient (the
held experts' terms of that gradient, applied alone, only teach the router to
choose the experts held here: ``hybrid_decoder``'s docstring, PERF.md section
6, PR 32); the whole model trains it through the weights of the chosen.
``b`` is a constant of the step. ``param_pspecs`` names the ``expert`` mesh
axis a sharded step would use; that step is not built (``lm_loss`` under a
mesh raises).

**Shared code.** The expert layer is ``moe_decoder.routed_experts`` with this
family's choices and weights (``hybrid_decoder._route``, with the ``1e-6``)
and its body (``moe_decoder._grouped_ffn`` with silu as the gate's
activation). At 8 of 64 held and 4 a token a slot is one of the token's
choices *and* the layer has a rung: ``N`` rows under the worst case's
``4 N``, picked on the device where the counted rows fit. Attention is
``moe_decoder._attention`` and ``_rope``, the taps ``hybrid_decoder.
_causal_conv`` without a bias. In a trace the expert layer's work reads by
kind under three names of ``moe_decoder.SCOPES`` that are only ever nested
inside ``moe_dispatch``, ``moe_combine`` and ``experts``, in both routes,
forward and backward: ``rows_moved`` (the gathers of rows by index),
``row_index`` (the sort, its inverse, the counts, the trimming to the rung,
the gathers of scalars) and ``gmm`` (each megablox call of ``_grouped_ffn``,
apart from silu, the casts and the update).

**Precision.** Params are float32; the residual stream, every norm (the
per-head ones too), the rotation, the sigmoid and the router's matmul
(``Precision.HIGHEST``) are float32; the other matmuls run in ``cfg.dtype``
(bfloat16) with float32 accumulation. In the convolution mixer ``B``, ``C``
and ``X`` leave the input projection in ``cfg.dtype``; ``B * X`` is taken in
``cfg.dtype`` (one rounding of the product); the taps multiply and add in
float32 on float32 taps; ``C * V`` is taken in float32 and rounded once to
``cfg.dtype`` for ``W_out``. The step, the optimizer and the loss are
``bert.py``'s: this file registers with ``bert.register_family``.
"""
from __future__ import annotations

import dataclasses
import functools
import types
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.models import bert, moe_decoder
from deeplearning4j_tpu.models.bert import loss_from_logits
from deeplearning4j_tpu.models.hybrid_decoder import _causal_conv, _route
from deeplearning4j_tpu.models.moe_decoder import (
    EXPERT_AXIS, _QKV_NAMES, _attention, _grouped_ffn, _rmsnorm, _rope,
    head_logits, routed_experts)

# ``moe_decoder.SCOPES`` and this family's: ``conv_in`` (the operator norm
# and ``W_in``), ``conv_gate`` (``B * X``, the taps, ``C * V``), ``conv_out``
# (``W_out`` and the residual). The dense MLP and its norm run under the
# existing ``mlp``, the per-head norms of q and k under ``attn_qkv``. The
# three nested names of the expert layer (``rows_moved``, ``row_index``,
# ``gmm``) come with ``moe_decoder.SCOPES``. PERF.md section 3 lists what
# reads each.
SCOPES = moe_decoder.SCOPES + ("conv_in", "conv_gate", "conv_out")
# ``checkpoint_name`` names of what a rematerialised block keeps beside its
# input and attention's five (``_QKV_NAMES``, ``FLASH_SAVED_NAMES``): the
# router's float32 logits (the name sits on the ``HIGHEST`` matmul's result,
# so the replay runs no second one) and its choice (no second top-k).
# Nothing of a convolution mixer, of the dense MLP or of the expert layer is
# kept (PERF.md section 6, PR 34).
_KEPT_NAMES = ("router_logits", "router_choice")
_MIXERS = {"conv": "c", "c": "c", "full_attention": "a", "a": "a"}
# the routed experts' body: three grouped products with silu on the gate
_swiglu_ffn = functools.partial(_grouped_ffn, act=jax.nn.silu)


@dataclasses.dataclass(frozen=True)
class ConvDecoderConfig:
    vocab_size: int = 65536          # rows of the vocabulary held here
    hidden: int = 2048
    layers: int = 40
    # one mixer a layer, ``conv`` or ``full_attention`` (or their first
    # letters ``c`` / ``a``, as a string), read from its start
    mixers: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv") * 10
    dense_layers: int = 2            # leading layers with the dense MLP
    conv_kernel: int = 3             # taps of the short convolution
    heads: int = 32
    kv_heads: int = 8
    head_dim: Optional[int] = None   # None: hidden // heads
    mlp_dim: int = 11776             # the dense MLP's inner width
    expert_dim: int = 1536           # a routed expert's inner width
    experts_total: int = 64          # the router's outputs
    experts_per_token: int = 4
    experts_count: Optional[int] = None   # experts held here (None: all)
    experts_offset: int = 0          # the first expert held here
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_seq: int = 128000
    dtype: Any = jnp.bfloat16        # matmul compute dtype (params fp32)
    attention_impl: str = "flash"    # 'flash' (streamed kernels) | 'full'
    # jax.checkpoint each block: the backward pass replays it from its
    # input, but for what _KEPT_NAMES, _QKV_NAMES and FLASH_SAVED_NAMES name
    remat: bool = True

    def __post_init__(self):
        if not isinstance(self.mixers, str):             # a list from JSON
            object.__setattr__(self, "mixers", tuple(self.mixers))
        if self.experts_count is None:
            object.__setattr__(self, "experts_count", self.experts_total)
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden // self.heads)
        off, count = self.experts_held
        assert 0 <= off and off + count <= self.experts_total, (off, count)
        assert len(self.mixers) >= self.layers \
            and set(self.mixers[:self.layers]) <= set(_MIXERS), self.mixers
        assert 0 <= self.dense_layers <= self.layers
        assert self.heads % self.kv_heads == 0

    causal = True      # every position is a target: ``lm_loss``'s dense head

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(offset, count) of the experts this program holds."""
        return self.experts_offset, self.experts_count

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's two letters: the mixer (``c`` convolution, ``a``
        attention) and the feed-forward part (``d`` dense, ``e`` routed)."""
        return tuple(_MIXERS[m] + ("d" if i < self.dense_layers else "e")
                     for i, m in enumerate(self.mixers[:self.layers]))


# ------------------------------------------------------------- parameters
def init_params(key, cfg: ConvDecoderConfig) -> Dict[str, Any]:
    """The parameter pytree of what is held here: normal(0.02) matrices,
    unit norm scales, the token embedding normal(1.0) (a stream of unit
    scale in which a token's own embedding outweighs what random blocks add
    to every token alike, ``moe_decoder.init_params``), the convolution's
    taps uniform in +-1/sqrt(K), the router's selection bias
    normal(0.005): it decides close choices, and at the 0.02 of the hybrid
    decoder a share's rows would differ by seed by a twentieth, and its step
    with them (PERF.md section 6, PR 34). The per-head scales of q and k are
    uniform in [1, 3]: normed queries and keys have unit length a
    dimension, so the scales alone set how peaked the softmax is; at ones
    the scores' spread is 1, a late position's attention is the mean of
    thousands of random values (2 % of the stream), and neither the layer
    nor its norm shows in a comparison with the reference (PERF.md section
    6, PR 34). No bias anywhere else."""
    def dense(k, shape, std=0.02):
        return jax.random.normal(k, shape, jnp.float32) * std

    def scale(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    H, D, K = cfg.hidden, cfg.head_dim, cfg.conv_kernel
    keys = jax.random.split(key, 2 + cfg.layers)
    blocks = []
    for kind, bk in zip(cfg.kinds, keys[2:]):
        bk = jax.random.split(bk, 14)
        if kind[0] == "c":
            bound = K ** -0.5
            block = {"in": dense(bk[0], (H, 3 * H)),
                     "conv": jax.random.uniform(bk[1], (K, H), jnp.float32,
                                                -bound, bound),
                     "out": dense(bk[2], (H, H))}
        else:
            block = {"q": dense(bk[0], (H, cfg.heads * D)),
                     "k": dense(bk[1], (H, cfg.kv_heads * D)),
                     "v": dense(bk[2], (H, cfg.kv_heads * D)),
                     "o": dense(bk[3], (cfg.heads * D, H)),
                     **{n: {"scale": jax.random.uniform(
                         k, (D,), jnp.float32, 1.0, 3.0)}
                        for n, k in (("q_norm", bk[12]), ("k_norm", bk[13]))}}
        if kind[1] == "d":
            F = cfg.mlp_dim
            block["mlp"] = {"gate": dense(bk[4], (H, F)),
                            "up": dense(bk[5], (H, F)),
                            "down": dense(bk[6], (F, H))}
        else:
            F, held = cfg.expert_dim, cfg.experts_count
            block.update(
                router=dense(bk[7], (H, cfg.experts_total)),
                router_bias=dense(bk[8], (cfg.experts_total,), 0.005),
                experts={"gate": dense(bk[9], (held, H, F)),
                         "up": dense(bk[10], (held, H, F)),
                         "down": dense(bk[11], (held, F, H))})
        blocks.append(dict(block, ln_op=scale(H), ln_ffn=scale(H)))
    return {"tok_emb": dense(keys[0], (cfg.vocab_size, H), 1.0),
            "ln_f": scale(H),
            "lm_head": dense(keys[1], (H, cfg.vocab_size)),
            "blocks": blocks}


def param_pspecs(cfg: ConvDecoderConfig) -> Dict[str, Any]:
    """Expert parallelism's layout: the experts' leading axis and the
    vocabulary ride the ``expert`` mesh axis; mixers, the dense MLP, router,
    bias and norms are whole on every chip."""
    norm, expert = {"scale": P()}, P(EXPERT_AXIS, None, None)
    mixer = {"c": {"in": P(), "conv": P(), "out": P()},
             "a": {"q": P(), "k": P(), "v": P(), "o": P(),
                   "q_norm": norm, "k_norm": norm}}
    ffn = {"d": {"mlp": {"gate": P(), "up": P(), "down": P()}},
           "e": {"router": P(), "router_bias": P(),
                 "experts": {"gate": expert, "up": expert, "down": expert}}}
    return {"tok_emb": P(EXPERT_AXIS, None), "ln_f": norm,
            "lm_head": P(None, EXPERT_AXIS),
            "blocks": [dict(mixer[kind[0]], **ffn[kind[1]], ln_op=norm,
                            ln_ffn=norm) for kind in cfg.kinds]}


# ------------------------------------------------------------------ mixers
def _conv_gate(bcx, taps):
    """``C * conv(B * X)`` of the input projection's three chunks ``bcx``
    (B, T, 3 x hidden) in the compute dtype, taps (K, hidden) float32 with
    the last on the current position. The result is in ``bcx``'s dtype; the
    module's docstring says what is rounded where."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    v = _causal_conv((b * x).astype(jnp.float32), taps)
    return (c.astype(jnp.float32) * v).astype(bcx.dtype)


def _conv_mixer(bp, x, positions, cfg: ConvDecoderConfig):
    """The gated short convolution on the float32 stream x (B, T, hidden)."""
    del positions                       # the convolution carries order
    with jax.named_scope("conv_in"):
        u = _rmsnorm(x, bp["ln_op"], cfg.rms_eps).astype(cfg.dtype)
        bcx = u @ bp["in"].astype(cfg.dtype)
    with jax.named_scope("conv_gate"):
        gated = _conv_gate(bcx, bp["conv"])
    with jax.named_scope("conv_out"):
        return x + jnp.dot(gated, bp["out"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


def _attend(bp, x, positions, cfg: ConvDecoderConfig):
    """Grouped-query causal attention on the float32 stream: q and k normed
    per head, then rotated; no window."""
    Bsz, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        u = _rmsnorm(x, bp["ln_op"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = (
            (u @ bp[n].astype(cfg.dtype)).reshape(Bsz, T, -1, cfg.head_dim)
            for n in ("q", "k", "v"))
        q = _rmsnorm(q, bp["q_norm"], cfg.rms_eps)
        k = _rmsnorm(k, bp["k_norm"], cfg.rms_eps)
    with jax.named_scope("rope"):
        q, k = (_rope(t, positions, cfg.rope_theta).astype(cfg.dtype)
                for t in (q, k))
    with jax.named_scope("attention"):
        o = _attention(
            *(checkpoint_name(t.transpose(0, 2, 1, 3), n)
              for t, n in zip((q, k, v), _QKV_NAMES)), None, cfg)
        o = o.transpose(0, 2, 1, 3).reshape(Bsz, T, -1)
    with jax.named_scope("attn_out"):
        return x + jnp.dot(o, bp["o"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


# ------------------------------------------------------------ feed-forward
def _dense_mlp(bp, h, cfg: ConvDecoderConfig):
    """The dense gated-SiLU MLP with its norm and residual."""
    with jax.named_scope("mlp"):
        m = _rmsnorm(h, bp["ln_ffn"], cfg.rms_eps).astype(cfg.dtype)
        gate, up, down = (bp["mlp"][n].astype(cfg.dtype)
                          for n in ("gate", "up", "down"))
        inner = jax.nn.silu(m @ gate) * (m @ up)
        return h + jnp.dot(inner, down, preferred_element_type=jnp.float32)


def _expert_part(bp, m, cfg: ConvDecoderConfig):
    """The expert layer on normed rows ``m`` (N, hidden) float32: what the
    experts held here give, (N, hidden) float32, and the counters."""
    with jax.named_scope("router"):
        r = checkpoint_name(jnp.dot(m, bp["router"],
                                    precision=lax.Precision.HIGHEST),
                            "router_logits")
        if cfg.experts_count < cfg.experts_total:
            # a share alone: the scores are a constant of the step (the
            # module's docstring, "One chip's share")
            r = lax.stop_gradient(r)
        top_e, top_w = _route(jax.nn.sigmoid(r), bp["router_bias"], cfg,
                              eps=1e-6)
    return routed_experts(m, top_e, top_w, cfg.experts_held,
                          cfg.experts_total, cfg.dtype, _swiglu_ffn,
                          bp["experts"])


def _experts(bp, h, cfg: ConvDecoderConfig):
    Bsz, T, H = h.shape
    with jax.named_scope("moe_dispatch"):
        m = _rmsnorm(h, bp["ln_ffn"], cfg.rms_eps)
    out, counters = _expert_part(bp, m.reshape(Bsz * T, H), cfg)
    with jax.named_scope("moe_combine"):
        return h + out.reshape(Bsz, T, H), counters


# ---------------------------------------------------------------- the model
def _block(bp, x, positions, kind: str, cfg: ConvDecoderConfig):
    """One layer on the float32 residual stream x (B, T, hidden): the new
    stream, and the routing counters of an expert layer (else None)."""
    h = (_conv_mixer if kind[0] == "c" else _attend)(bp, x, positions, cfg)
    if kind[1] == "d":
        return _dense_mlp(bp, h, cfg), None
    return _experts(bp, h, cfg)


def encode(params, token_ids, cfg: ConvDecoderConfig, positions=None):
    """Embedding, the blocks and the final norm: the float32 hidden states
    (B, T, hidden) and the routing counters, stacked over the expert
    layers."""
    from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
    if positions is None:
        positions = jnp.arange(token_ids.shape[1])
    keep = jax.checkpoint_policies.save_only_these_names(
        *FLASH_SAVED_NAMES, *_QKV_NAMES, *_KEPT_NAMES)
    with jax.default_matmul_precision("default"):
        with jax.named_scope("embed"):
            x = params["tok_emb"][token_ids]
        # one function a kind, so that layers of one kind trace once
        blocks = {kind: functools.partial(_block, kind=kind, cfg=cfg)
                  for kind in set(cfg.kinds)}
        if cfg.remat:
            blocks = {kind: jax.checkpoint(blk, policy=keep)
                      for kind, blk in blocks.items()}
        counters = []
        for kind, bp in zip(cfg.kinds, params["blocks"]):
            x, c = blocks[kind](bp, x, positions)
            if c is not None:
                counters.append(c)
        with jax.named_scope("final_ln"):
            x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
    return x, jax.tree.map(lambda *c: jnp.stack(c), *counters) \
        if counters else None


def _one_chip(mesh: Optional[Mesh]):
    if mesh is not None:
        raise NotImplementedError(
            "the convolution decoder runs one chip's share without its "
            "exchange; the sharded step (all-to-all over the 'expert' axis "
            "of param_pspecs) is not built")


def forward(params, token_ids, cfg: ConvDecoderConfig,
            mesh: Optional[Mesh] = None, positions=None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) float32."""
    _one_chip(mesh)
    x, _ = encode(params, token_ids, cfg, positions)
    return head_logits(params, x, cfg).astype(jnp.float32)


def lm_loss_and_counters(params, batch, cfg: ConvDecoderConfig,
                         mesh: Optional[Mesh] = None):
    """Weighted next-token cross-entropy of ``batch`` (tokens, targets,
    weights) through ``bert.loss_from_logits``, and the routing counters of
    the step as ``moe_decoder.lm_loss_and_counters`` gives them, stacked
    over the expert layers."""
    _one_chip(mesh)
    x, counters = encode(params, batch["tokens"], cfg)
    return loss_from_logits(head_logits(params, x, cfg), batch), counters


def lm_loss(params, batch, cfg: ConvDecoderConfig,
            mesh: Optional[Mesh] = None):
    return lm_loss_and_counters(params, batch, cfg, mesh)[0]


bert.register_family(ConvDecoderConfig, types.SimpleNamespace(
    init_params=init_params, param_pspecs=param_pspecs, forward=forward,
    lm_loss=lm_loss, loss_and_aux=lm_loss_and_counters))
