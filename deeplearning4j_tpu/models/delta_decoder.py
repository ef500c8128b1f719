"""Gated delta-rule / gated attention decoder with routed SwiGLU experts
beside a shared expert — the fifth family.

The block of the Solar Open 2 class of decoders (``model_type``
``solar_open2``): every layer is **two** residual steps on the stream ``x``
(T x hidden), RMSNorm, no bias but the router's selection bias, no positional
encoding anywhere::

    h = x + mixer(rmsnorm(x; g1))
    y = h + experts(rmsnorm(h; g2))

The mixer changes **by layer**: the layers ``cfg.attention_layers`` carry
grouped-query softmax attention with an output gate, every other one a gated
delta-rule linear-attention mixer (the KDA layer of Kimi Linear,
arXiv:2510.26692) whose state decays **per channel**.

Delta-rule mixer, with ``u = rmsnorm(x; g1)``, per head ``h`` held here,
``d = delta_head_dim``, ``conv`` a causal depthwise convolution of
``conv_kernel`` taps without a bias::

    q = l2norm(silu(conv(u W_q))),  k = l2norm(silu(conv(u W_k)))
    v = silu(conv(u W_v))
    g = -exp(A_log[h]) * softplus((u W_fd) W_fu + dt_bias)    (T, h, d) < 0
    beta = 2 * sigmoid(u W_b)           (T, h); the 2 is ``cfg.neg_eigval``
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T (q_t / sqrt(d))         S (d x d) float32, S_0 = 0
    out = [rmsnorm_head(o_t; gamma) * sigmoid((u W_gd) W_gu)] W_o

The state is *corrected* by what it already holds for the current key, so
this is no ``hybrid_decoder._ssd`` with other numbers. ``_delta_rule``
computes it **chunked** (``cfg.chunk`` positions): with ``G`` the cumulative
sum of ``g`` inside a chunk and ``A[r, i] = sum_c k_rc k_ic exp(G_rc - G_ic)``
for ``i < r``, the chunk's corrected values are the solution of the
unit-lower-triangular system ``(I + diag(beta) tril(A, -1)) [W | U] =
diag(beta) [exp(G) k | v]`` (the WY / UT transform), which needs no state;
a ``lax.scan`` then carries one state a chunk (``U - W S``, ``S' = exp(G_end)
S + (k exp(G_end - G))^T (U - W S)``) and the outputs of all chunks are read
at once (``(q exp(G)) S + tril(A_q) (U - W S)``). **No exponential of a
positive number is ever taken**: ``exp(G_r) exp(-G_i)`` over a chunk
overflows float32 at decays a trained layer reaches (and the seeded one's
strongest channels: 64 steps of -2), so ``exp(G_r - G_i)`` is formed from
reference points inside the chunk. Rows and columns in different sub-blocks
of ``_SUB`` positions go through the first row of the row's sub-block
(``exp(G_r - G_ref) exp(G_ref - G_i)``, both at most 1, two matrix
products), pairs inside a sub-block are taken as they are, pair by pair.
There is no clamp, floor or cut of ``g``, ``beta`` or the state. A sequence
that is no multiple of the chunk is padded with ``beta = 0, g = 0`` (a
padded step neither decays nor writes) and the result cut back.

Attention mixer: ``q, k, v = u W_q, u W_k, u W_v``, causal softmax over the
whole sequence (``moe_decoder._attention``, the streamed kernels with their
kv group), ``out = [o * sigmoid(u W_gate)] W_o``, the gate elementwise over
heads x head_dim columns (arXiv:2505.06708).

Feed-forward half, with ``m = rmsnorm(h; g2)``::

    s = sigmoid(m W_r)                    float32, experts_total outputs
    S = top-k(s + b)                      the bias b chooses and does not weigh
    w_e = scale * s_e / sum_S s
    out = sum_{e in S, e held here} w_e (silu(m W1_e) * (m W3_e)) W2_e
          + (silu(m Ws1) * (m Ws3)) Ws2   the shared expert

**One chip's share** is by heads *and* by experts, as ``hybrid_decoder``'s:
``model_share`` chips divide every layer by heads and this is chip
``model_rank`` of them. It holds ``delta_heads`` delta-rule heads (their
columns of ``W_q``, ``W_k``, ``W_v``, ``W_fu``, ``W_gu``, ``W_b``, their
taps, ``A_log`` and ``dt_bias``, their rows of ``W_o``), ``heads`` query
heads with their gate on ``kv_heads`` key/value heads (of
``kv_heads_total``), ``shared_dim // model_share`` columns of the shared
expert and ``vocab_size`` rows of the vocabulary; the routed experts divide
over more chips than that (``experts_count`` of ``experts_total`` from
``experts_offset``). Router, bias, norms (the head norm's ``gamma`` too) and
the two gate bottlenecks ``W_fd``, ``W_gd`` are whole on every chip
(``cfg.whole``, ``share_of``, ``param_pspecs``). A chip computes its heads'
and experts' part of each half's output, and that partial result plus the
residual goes on: on one chip the layers run without their all-reduce and
their all-to-all, and nothing here stands in for the other chips. **A share
does not train its router** (``experts_count < experts_total`` puts a
``stop_gradient`` on the scores: ``hybrid_decoder``'s docstring). The bias
is a constant of the step: its balancing update and any auxiliary loss are
training recipes the source's configuration does not give, and are left out.
The sharded step is not built (``lm_loss`` under a mesh raises).

**Shared code, imported and not copied**: ``moe_decoder.routed_experts``
with its rung (8 of 320 held at 8 a token: 4,096 rows under the worst
case's 65,536 at 8,192 tokens), ``_grouped_ffn`` with silu,
``hybrid_decoder._route`` and ``_causal_conv``, ``_attention``,
``_rmsnorm``, ``head_logits``, and ``bert.py``'s step, optimizer and loss
(``bert.register_family``).

**Precision.** Params are float32; the residual stream, the norms, the
convolutions' taps, silu, the L2 norms, softplus, ``g`` and its cumulative
sums, the pairs inside a sub-block, the triangular solve, the carried state,
the sigmoids of ``beta`` and of the router and the router's matmul
(``Precision.HIGHEST``) are float32; the other matmuls run in ``cfg.dtype``
(bfloat16) with float32 accumulation, those of the scan among them. On a TPU
the scan is the kernel pair ``pallas_kernels.delta_rule`` (``_delta_rule``):
the same chunks, solve and state, in float32, with every pair of a chunk
meeting through a reference row: the pairs inside a sub-block with float32
factors (``Precision.HIGHEST``), those across sub-blocks with factors in
``cfg.dtype``.
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
from deeplearning4j_tpu.models.hybrid_decoder import (
    MODEL_AXIS, _causal_conv, _route)
from deeplearning4j_tpu.models.moe_decoder import (
    EXPERT_AXIS, _QKV_NAMES, _ROUTE_NAMES, _attention, _grouped_ffn,
    _rmsnorm, head_logits, routed_experts)

# ``moe_decoder.SCOPES`` and this family's, flat and never nested inside one
# another: ``kda_in`` (the norm, ``W_q``, ``W_k``, ``W_v``, the two gate
# bottlenecks and ``W_b``), ``kda_conv`` (the taps, silu, the L2 norms),
# ``kda_scan`` (the decay gate, the cumulative sums, the chunked delta rule
# and its carry), ``kda_out`` (the gated head norm, ``W_o``, the residual),
# ``moe_shared`` (the shared expert, as in ``hybrid_decoder``). The
# attention gate's projection runs under ``attn_qkv`` and its product under
# ``attn_out``. PERF.md section 3 lists what reads each.
SCOPES = moe_decoder.SCOPES + ("kda_in", "kda_conv", "kda_scan", "kda_out",
                               "moe_shared")
# ``checkpoint_name`` names of what a rematerialised block keeps beside its
# input and attention's five (``_QKV_NAMES``, ``FLASH_SAVED_NAMES``): the
# router's float32 logits (the name sits on the ``HIGHEST`` matmul's result),
# its choice (no second top-k), the inputs of the expert layer's backward
# rule (``_ROUTE_NAMES``: no second sort) and, of a delta-rule mixer, the
# projection's q, k and v in the compute dtype before their taps
# (``kda_qkv``, 50 MB a layer at the benchmark's sizes: the replay runs the
# narrow gate product alone; 7.2 ms a step for 3 MB more planned, because
# the step's peak lies where no delta-rule layer's value is alive; PERF.md
# section 6). On the kernel route ``encode`` also keeps the scan's output
# and the state that entered each chunk (``pallas_kernels.KDA_SAVED_NAMES``,
# 33.5 MB and 67 MB a layer at the benchmark's sizes), so the replay runs
# no scan. The ``jax.numpy`` route names nothing of its scan: a
# ``lax.scan``'s backward reads its own residuals, and the scores and the
# solve are 0.3 GB a layer.
_KEPT_NAMES = ("router_logits", "router_choice", *_ROUTE_NAMES, "kda_qkv")
# positions in a sub-block of a chunk: pairs inside one are taken pair by
# pair, pairs across two through a reference point (``_decayed_scores``)
_SUB = 16
# the routed experts' body: three grouped products with silu on the gate
_swiglu_ffn = functools.partial(_grouped_ffn, act=jax.nn.silu)


@dataclasses.dataclass(frozen=True)
class DeltaDecoderConfig:
    vocab_size: int = 196608         # rows of the vocabulary held here
    hidden: int = 4096
    layers: int = 48
    # the layers whose mixer is attention; every other one is delta-rule
    attention_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    delta_heads: int = 64            # delta-rule heads held here
    delta_head_dim: int = 128        # and the width of the gate bottlenecks
    conv_kernel: int = 4             # taps of the short convolutions
    chunk: int = 64                  # positions in a chunk of the scan
    neg_eigval: bool = True          # beta in (0, 2) and not (0, 1)
    heads: int = 64                  # query heads held here
    kv_heads: int = 8                # key/value heads held here
    kv_heads_total: Optional[int] = None   # None: kv_heads * model_share
    head_dim: int = 128
    expert_dim: int = 1280           # a routed expert's inner width
    shared_dim: int = 1280           # the shared expert's published width
    experts_total: int = 320         # the router's outputs
    experts_per_token: int = 8
    experts_count: Optional[int] = None   # experts held here (None: all)
    experts_offset: int = 0          # the first expert held here
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    model_share: int = 1             # chips that divide a layer by heads
    model_rank: int = 0              # which of them this is
    rms_eps: float = 1e-5
    max_seq: int = 1048576
    dtype: Any = jnp.bfloat16        # matmul compute dtype (params fp32)
    attention_impl: str = "flash"    # 'flash' (streamed kernels) | 'full'
    # jax.checkpoint each block: the backward pass replays it from its
    # input, but for what _KEPT_NAMES, _QKV_NAMES and FLASH_SAVED_NAMES name
    remat: bool = True

    def __post_init__(self):
        object.__setattr__(self, "attention_layers",       # a list from JSON
                           tuple(self.attention_layers))
        if self.experts_count is None:
            object.__setattr__(self, "experts_count", self.experts_total)
        if self.kv_heads_total is None:
            object.__setattr__(self, "kv_heads_total",
                               self.kv_heads * self.model_share)
        off, count = self.experts_held
        assert 0 <= off and off + count <= self.experts_total, (off, count)
        assert self.heads % self.kv_heads == 0
        assert self.shared_dim % self.model_share == 0
        assert 0 <= self.model_rank < self.model_share
        assert self.kv_heads == max(
            1, self.kv_heads_total // self.model_share), self.kv_heads_total
        assert self.chunk % _SUB == 0 or self.chunk < _SUB, self.chunk

    causal = True      # every position is a target: ``lm_loss``'s dense head

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(offset, count) of the experts this program holds."""
        return self.experts_offset, self.experts_count

    @property
    def kinds(self) -> str:
        """The mixer of each layer: ``a`` attention, ``d`` delta rule."""
        return "".join("a" if i in self.attention_layers else "d"
                       for i in range(self.layers))

    @property
    def delta_inner(self) -> int:
        return self.delta_heads * self.delta_head_dim

    @property
    def shared_columns(self) -> int:
        """Columns of the shared expert held here."""
        return self.shared_dim // self.model_share

    @property
    def whole(self) -> "DeltaDecoderConfig":
        """The uncut model this is a share of."""
        s = self.model_share
        return dataclasses.replace(
            self, model_share=1, model_rank=0, vocab_size=self.vocab_size * s,
            delta_heads=self.delta_heads * s, heads=self.heads * s,
            kv_heads=self.kv_heads_total, kv_heads_total=None,
            experts_count=self.experts_total, experts_offset=0)


# ------------------------------------------------------------- parameters
def init_params(key, cfg: DeltaDecoderConfig) -> Dict[str, Any]:
    """The parameter pytree of what is held here, initialised as
    ``conv_decoder``'s: normal(0.02) matrices, unit norm scales, the token
    embedding normal(1.0), the taps uniform in +-1/sqrt(K), the router's
    selection bias normal(0.005). The decay gate as the published layer
    initialises it: ``A_log`` the log of a uniform draw in [1, 16] (one a
    head), ``dt_bias`` the inverse softplus of a log-uniform draw in
    [0.001, 0.1] (one a channel). No bias anywhere else."""
    def dense(k, shape, std=0.02):
        return jax.random.normal(k, shape, jnp.float32) * std

    def scale(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    H, K, d = cfg.hidden, cfg.conv_kernel, cfg.delta_head_dim
    inner, F, S = cfg.delta_inner, cfg.expert_dim, cfg.shared_columns
    held = cfg.experts_count
    keys = jax.random.split(key, 2 + cfg.layers)
    blocks = []
    for kind, bk in zip(cfg.kinds, keys[2:]):
        bk = jax.random.split(bk, 24)
        if kind == "d":
            bound = K ** -0.5
            dt = jnp.exp(jax.random.uniform(bk[11], (inner,))
                         * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
            block = {
                **{n: dense(k, (H, inner)) for n, k in zip("qkv", bk)},
                "conv": {n: jax.random.uniform(k, (K, inner), jnp.float32,
                                               -bound, bound)
                         for n, k in zip("qkv", bk[3:6])},
                "f_down": dense(bk[6], (H, d)),
                "f_up": dense(bk[7], (d, inner)),
                "A_log": jnp.log(jax.random.uniform(
                    bk[10], (cfg.delta_heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "beta": dense(bk[12], (H, cfg.delta_heads)),
                "g_down": dense(bk[8], (H, d)),
                "g_up": dense(bk[9], (d, inner)),
                "norm": scale(d),
                "o": dense(bk[13], (inner, H))}
        else:
            D = cfg.head_dim
            block = {"q": dense(bk[0], (H, cfg.heads * D)),
                     "gate": dense(bk[4], (H, cfg.heads * D)),
                     "k": dense(bk[1], (H, cfg.kv_heads * D)),
                     "v": dense(bk[2], (H, cfg.kv_heads * D)),
                     "o": dense(bk[3], (cfg.heads * D, H))}
        block.update(
            ln1=scale(H), ln2=scale(H),
            router=dense(bk[14], (H, cfg.experts_total)),
            router_bias=dense(bk[15], (cfg.experts_total,), 0.005),
            experts={"gate": dense(bk[16], (held, H, F)),
                     "up": dense(bk[17], (held, H, F)),
                     "down": dense(bk[18], (held, F, H))},
            shared={"gate": dense(bk[19], (H, S)),
                    "up": dense(bk[20], (H, S)),
                    "down": dense(bk[21], (S, H))})
        blocks.append(block)
    return {"tok_emb": dense(keys[0], (cfg.vocab_size, H), 1.0),
            "ln_f": scale(H),
            "lm_head": dense(keys[1], (H, cfg.vocab_size)),
            "blocks": blocks}


def param_pspecs(cfg: DeltaDecoderConfig) -> Dict[str, Any]:
    """The layout of the stated deployment: heads, the shared expert's
    columns and the vocabulary ride the ``model`` mesh axis (a projection
    into heads by columns, out of them by rows), the routed experts'
    leading axis the ``expert`` axis; router, bias, norms and the two gate
    bottlenecks are whole on every chip."""
    cols, rows, vec = P(None, MODEL_AXIS), P(MODEL_AXIS, None), P(MODEL_AXIS)
    norm, expert = {"scale": P()}, P(EXPERT_AXIS, None, None)
    mixer = {
        "d": {"q": cols, "k": cols, "v": cols,
              "conv": {n: cols for n in "qkv"},
              "f_down": P(), "f_up": cols, "A_log": vec, "dt_bias": vec,
              "beta": cols, "g_down": P(), "g_up": cols, "norm": norm,
              "o": rows},
        "a": {"q": cols, "gate": cols, "k": cols, "v": cols, "o": rows}}
    ffn = {"ln1": norm, "ln2": norm, "router": P(), "router_bias": P(),
           "experts": {"gate": expert, "up": expert, "down": expert},
           "shared": {"gate": cols, "up": cols, "down": rows}}
    return {"tok_emb": rows, "ln_f": norm, "lm_head": cols,
            "blocks": [dict(mixer[kind], **ffn) for kind in cfg.kinds]}


def share_of(params, cfg: DeltaDecoderConfig) -> Dict[str, Any]:
    """This chip's share (``cfg``) of the uncut model's parameters
    (``params`` of ``cfg.whole``): what ``param_pspecs`` shards, cut at
    ``model_rank`` and ``experts_offset``. A key/value head that several
    ranks read is copied to each."""
    rank = cfg.model_rank

    def part(a, axis, width, start=None):
        start = rank * width if start is None else start
        return lax.slice_in_dim(a, start, start + width, axis=axis)

    inner, q = cfg.delta_inner, cfg.heads * cfg.head_dim
    kv = cfg.kv_heads * cfg.head_dim
    kv_start = rank * cfg.kv_heads_total // cfg.model_share * cfg.head_dim
    off, held = cfg.experts_held
    S = cfg.shared_columns
    blocks = []
    for kind, bp in zip(cfg.kinds, params["blocks"]):
        if kind == "d":
            block = {
                **{n: part(bp[n], 1, inner)
                   for n in ("q", "k", "v", "f_up", "g_up")},
                "conv": {n: part(bp["conv"][n], 1, inner) for n in "qkv"},
                "beta": part(bp["beta"], 1, cfg.delta_heads),
                "A_log": part(bp["A_log"], 0, cfg.delta_heads),
                "dt_bias": part(bp["dt_bias"], 0, inner),
                "o": part(bp["o"], 0, inner),
                **{n: bp[n] for n in ("f_down", "g_down", "norm")}}
        else:
            block = {"q": part(bp["q"], 1, q), "gate": part(bp["gate"], 1, q),
                     "o": part(bp["o"], 0, q),
                     "k": part(bp["k"], 1, kv, kv_start),
                     "v": part(bp["v"], 1, kv, kv_start)}
        block.update(
            {n: bp[n] for n in ("ln1", "ln2", "router", "router_bias")},
            experts={n: part(w, 0, held, off)
                     for n, w in bp["experts"].items()},
            shared={"gate": part(bp["shared"]["gate"], 1, S),
                    "up": part(bp["shared"]["up"], 1, S),
                    "down": part(bp["shared"]["down"], 0, S)})
        blocks.append(block)
    return {"tok_emb": part(params["tok_emb"], 0, cfg.vocab_size),
            "ln_f": params["ln_f"],
            "lm_head": part(params["lm_head"], 1, cfg.vocab_size),
            "blocks": blocks}


# ------------------------------------------------------- delta-rule mixer
def _decayed_scores(rows, k, G, sub: int):
    """``out[j, r, i] = sum_c rows[j, r, c] k[i, c] exp(G[r, c] - G[i, c])``
    for ``i <= r`` and 0 above the diagonal, float32, with no exponential of
    a positive number. ``rows`` (J, ..., C, d) stacks the J kinds of rows
    that meet the keys ``k`` (..., C, d), both in the compute dtype; ``G``
    (..., C, d) float32 is the cumulative log-decay inside the chunk, which
    never rises. Sub-block I's rows meet the columns of earlier sub-blocks
    through ``ref``, the log-decay at I's first row: ``exp(G_r - ref)`` and
    ``exp(ref - G_i)`` are both at most 1. Inside a sub-block the
    differences are taken pair by pair."""
    J, C, d = rows.shape[0], *k.shape[-2:]
    lead = k.shape[:-2]
    nb = C // sub
    dtype = k.dtype

    def blocks(t):                              # (..., C, d) -> (..., nb, sub, d)
        return t.reshape(t.shape[:-2] + (nb, sub, d))

    Gb, kb, rb = blocks(G), blocks(k), blocks(rows)
    # pairs inside a sub-block: (..., nb, r, i, d), float32
    seg = Gb[..., :, None, :] - Gb[..., None, :, :]
    inside = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    pair = jnp.exp(jnp.where(inside, seg, -jnp.inf)) \
        * kb[..., None, :, :].astype(jnp.float32)
    diag = jnp.einsum("j...rd,...rid->j...ri", rb.astype(jnp.float32), pair)
    # pairs across sub-blocks, through the row's reference point; the first
    # sub-block has no earlier column and its factors are exp(-inf) = 0
    ref = Gb[..., :1, :]                                    # (..., nb, 1, d)
    row_f = (rb * jnp.exp(Gb - ref)).astype(dtype)
    before = (jnp.arange(C)[None, :] // sub
              < jnp.arange(nb)[:, None])[:, :, None]        # (nb, C, 1)
    col_f = (k[..., None, :, :] * jnp.exp(jnp.where(
        before, ref - G[..., None, :, :], -jnp.inf))).astype(dtype)
    cross = jnp.einsum("j...rd,...id->j...ri", row_f, col_f,
                       preferred_element_type=jnp.float32)  # (J,..,nb,sub,C)
    on_diag = jnp.eye(nb, dtype=jnp.float32)[:, None, :, None]
    full = cross.reshape((J,) + lead + (nb, sub, nb, sub)) \
        + diag[..., None, :] * on_diag
    return full.reshape((J,) + lead + (C, C))


def _delta_rule(q, k, v, g, beta, chunk: int, state_dtype=jnp.float32):
    """The chunked gated delta rule. ``q``, ``k``, ``v`` (B, heads, T, d) in
    the compute dtype (``q`` scaled), ``g`` (B, heads, T, d) float32, never
    positive, ``beta`` (B, heads, T) float32. Returns ``o_t = S_t^T q_t``
    (B, heads, T, d) float32 for ``S_t = (I - beta_t k_t k_t^T)
    Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T``, ``S_0 = 0``.

    On a TPU, where ``pallas_kernels.delta_rule_fits`` the chunk and the
    head, this is the kernel pair ``pallas_kernels.delta_rule`` (the same
    algorithm with the chunk's pairs through halving levels); the
    ``jax.numpy`` form below is the route everywhere else and the kernel's
    reference in the tests. The route follows from the backend and the
    shapes alone.

    ``state_dtype`` is what the triangular solve and the carried state are
    computed in. The model never passes it; ``tests/test_delta_decoder.py``
    does, to hold that one precision less is a hundred times further from
    the recurrence, on both routes. The benchmark's ``correct`` does not see
    that (PERF.md section 6), so the kernel is held to that test."""
    from deeplearning4j_tpu.ops import pallas_kernels
    Bsz, heads, T, d = q.shape
    if jax.default_backend() == "tpu" \
            and pallas_kernels.delta_rule_fits(chunk, d):
        return pallas_kernels.delta_rule(q, k, v, g, beta, chunk, state_dtype)
    dtype = q.dtype
    pad = -T % chunk
    if pad:     # beta = 0, g = 0: a padded step neither decays nor writes
        q, k, v, g = (jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)])
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, [(0, 0), (0, 0), (0, pad)])
    N = (T + pad) // chunk
    q, k, v, g = (t.reshape(Bsz, heads, N, chunk, d) for t in (q, k, v, g))
    beta = beta.reshape(Bsz, heads, N, chunk, 1)
    # log-decay from a chunk's start to each of its positions, inclusive
    G = jnp.cumsum(g, axis=3)
    decay = jnp.exp(G)
    Akk, Aqk = _decayed_scores(jnp.stack([k, q]), k, G, min(_SUB, chunk))

    # the corrected keys and values of every chunk, before any state
    rhs = beta * jnp.concatenate(
        [k.astype(jnp.float32) * decay, v.astype(jnp.float32)], axis=-1)
    system = jnp.tril(beta * Akk, -1) + jnp.eye(chunk, dtype=jnp.float32)
    W, U = jnp.split(jax.scipy.linalg.solve_triangular(
        system.astype(state_dtype), rhs.astype(state_dtype), lower=True,
        unit_diagonal=True).astype(dtype), 2, axis=-1)
    to_end = (k * jnp.exp(G[..., -1:, :] - G)).astype(dtype)
    through = decay[..., -1, :]                             # (B, h, N, d)

    def carry(S, step):
        W_c, U_c, to_end_c, through_c = step
        new = U_c - jnp.einsum("bhcd,bhde->bhce", W_c, S.astype(dtype),
                               preferred_element_type=jnp.float32)
        S_next = through_c[..., None] * S + jnp.einsum(
            "bhcd,bhce->bhde", to_end_c, new.astype(dtype),
            preferred_element_type=jnp.float32)
        return S_next.astype(S.dtype), (S, new.astype(dtype))

    chunks_first = [jnp.moveaxis(t, 2, 0) for t in (W, U, to_end, through)]
    _, (entering, new) = lax.scan(
        carry, jnp.zeros((Bsz, heads, d, d), state_dtype), chunks_first)
    # entering (N, B, h, d, d): the state before each chunk; new (N, B, h,
    # chunk, d): what each position writes, corrected by that state
    o = jnp.einsum("bhncd,nbhde->bhnce", (q * decay).astype(dtype),
                   entering.astype(dtype),
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bhnci,nbhie->bhnce", Aqk.astype(dtype), new,
                     preferred_element_type=jnp.float32)
    return o.reshape(Bsz, heads, T + pad, d)[:, :, :T]


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_mixer(bp, x, cfg: DeltaDecoderConfig):
    """The gated delta-rule mixer on the float32 stream x (B, T, hidden)."""
    Bsz, T, _ = x.shape
    heads, d, inner = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_inner

    def by_head(t):                     # (B, T, inner) -> (B, heads, T, d)
        return t.reshape(Bsz, T, heads, d).transpose(0, 2, 1, 3)

    with jax.named_scope("kda_in"):
        u = _rmsnorm(x, bp["ln1"], cfg.rms_eps).astype(cfg.dtype)
        qkv = checkpoint_name(u @ jnp.concatenate(
            [bp[n] for n in "qkv"], axis=1).astype(cfg.dtype), "kda_qkv")
        f, gate, b = jnp.split(jnp.dot(u, jnp.concatenate(
            [bp[n] for n in ("f_down", "g_down", "beta")],
            axis=1).astype(cfg.dtype), preferred_element_type=jnp.float32),
            [d, 2 * d], axis=-1)
        f = jnp.dot(f.astype(cfg.dtype), bp["f_up"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32)
        gate = jnp.dot(gate.astype(cfg.dtype), bp["g_up"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32)
    with jax.named_scope("kda_conv"):
        qkv = jax.nn.silu(_causal_conv(
            qkv.astype(jnp.float32),
            jnp.concatenate([bp["conv"][n] for n in "qkv"], axis=1)))
        q, k, v = (by_head(t) for t in jnp.split(qkv, 3, axis=-1))
        q = (_l2norm(q) * d ** -0.5).astype(cfg.dtype)
        k, v = _l2norm(k).astype(cfg.dtype), v.astype(cfg.dtype)
    with jax.named_scope("kda_scan"):
        g = -jnp.exp(bp["A_log"])[:, None, None] \
            * by_head(jax.nn.softplus(f + bp["dt_bias"]))
        beta = (2.0 if cfg.neg_eigval else 1.0) \
            * jax.nn.sigmoid(b).transpose(0, 2, 1)
        o = _delta_rule(q, k, v, g, beta, cfg.chunk)
    with jax.named_scope("kda_out"):
        o = _rmsnorm(o, bp["norm"], cfg.rms_eps) \
            * by_head(jax.nn.sigmoid(gate))
        o = o.transpose(0, 2, 1, 3).reshape(Bsz, T, inner)
        return x + jnp.dot(o.astype(cfg.dtype), bp["o"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------- attention mixer
def _attend(bp, x, cfg: DeltaDecoderConfig):
    """Grouped-query causal attention on the float32 stream, with no
    positional encoding and no window, its output gated elementwise."""
    Bsz, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        u = _rmsnorm(x, bp["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = (
            (u @ bp[n].astype(cfg.dtype)).reshape(Bsz, T, -1, cfg.head_dim)
            for n in ("q", "k", "v"))
        gate = jax.nn.sigmoid(u @ bp["gate"].astype(cfg.dtype))
    with jax.named_scope("attention"):
        o = _attention(
            *(checkpoint_name(t.transpose(0, 2, 1, 3), n)
              for t, n in zip((q, k, v), _QKV_NAMES)), None, cfg)
        o = o.transpose(0, 2, 1, 3).reshape(Bsz, T, -1)
    with jax.named_scope("attn_out"):
        return x + jnp.dot(o * gate, bp["o"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


# ------------------------------------------------------------ feed-forward
def _expert_parts(bp, m, cfg: DeltaDecoderConfig):
    """The expert layer on normed rows ``m`` (N, hidden) float32: what the
    experts held here give, what the shared expert's columns held here
    give, both (N, hidden) float32, and the counters."""
    with jax.named_scope("router"):
        r = checkpoint_name(jnp.dot(m, bp["router"],
                                    precision=lax.Precision.HIGHEST),
                            "router_logits")
        if cfg.experts_count < cfg.experts_total:
            # a share alone: the scores are a constant of the step (the
            # module's docstring, "One chip's share")
            r = lax.stop_gradient(r)
        top_e, top_w = _route(jax.nn.sigmoid(r), bp["router_bias"], cfg)
    routed, counters = routed_experts(
        m, top_e, top_w, cfg.experts_held, cfg.experts_total, cfg.dtype,
        _swiglu_ffn, bp["experts"])
    with jax.named_scope("moe_shared"):
        mc = m.astype(cfg.dtype)
        gate, up, down = (bp["shared"][n].astype(cfg.dtype)
                          for n in ("gate", "up", "down"))
        shared = jnp.dot(jax.nn.silu(mc @ gate) * (mc @ up), down,
                         preferred_element_type=jnp.float32)
    return routed, shared, counters


def _experts(bp, h, cfg: DeltaDecoderConfig):
    Bsz, T, H = h.shape
    with jax.named_scope("moe_dispatch"):
        m = _rmsnorm(h, bp["ln2"], cfg.rms_eps)
    routed, shared, counters = _expert_parts(bp, m.reshape(Bsz * T, H), cfg)
    with jax.named_scope("moe_combine"):
        return h + (routed + shared).reshape(Bsz, T, H), counters


# ---------------------------------------------------------------- the model
def _block(bp, x, kind: str, cfg: DeltaDecoderConfig):
    """One layer on the float32 residual stream x (B, T, hidden): the new
    stream and the routing counters of its expert layer."""
    h = (_attend if kind == "a" else _delta_mixer)(bp, x, cfg)
    return _experts(bp, h, cfg)


def encode(params, token_ids, cfg: DeltaDecoderConfig):
    """Embedding, the blocks and the final norm: the float32 hidden states
    (B, T, hidden) and the routing counters, stacked over the layers."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        FLASH_SAVED_NAMES, KDA_SAVED_NAMES)
    keep = jax.checkpoint_policies.save_only_these_names(
        *FLASH_SAVED_NAMES, *KDA_SAVED_NAMES, *_QKV_NAMES, *_KEPT_NAMES)
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
            x, c = blocks[kind](bp, x)
            counters.append(c)
        with jax.named_scope("final_ln"):
            x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
    return x, jax.tree.map(lambda *c: jnp.stack(c), *counters)


def _one_chip(mesh: Optional[Mesh]):
    if mesh is not None:
        raise NotImplementedError(
            "the delta-rule decoder runs one chip's share without its "
            "exchange; the sharded step (all-reduce over the 'model' axis "
            "and all-to-all over the 'expert' axis of param_pspecs) is not "
            "built")


def forward(params, token_ids, cfg: DeltaDecoderConfig,
            mesh: Optional[Mesh] = None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) float32."""
    _one_chip(mesh)
    x, _ = encode(params, token_ids, cfg)
    return head_logits(params, x, cfg).astype(jnp.float32)


def lm_loss_and_counters(params, batch, cfg: DeltaDecoderConfig,
                         mesh: Optional[Mesh] = None):
    """Weighted next-token cross-entropy of ``batch`` (tokens, targets,
    weights) through ``bert.loss_from_logits``, and the routing counters of
    the step as ``moe_decoder.lm_loss_and_counters`` gives them, stacked
    over the layers."""
    _one_chip(mesh)
    x, counters = encode(params, batch["tokens"], cfg)
    return loss_from_logits(head_logits(params, x, cfg), batch), counters


def lm_loss(params, batch, cfg: DeltaDecoderConfig,
            mesh: Optional[Mesh] = None):
    return lm_loss_and_counters(params, batch, cfg, mesh)[0]


bert.register_family(DeltaDecoderConfig, types.SimpleNamespace(
    init_params=init_params, param_pspecs=param_pspecs, forward=forward,
    lm_loss=lm_loss, loss_and_aux=lm_loss_and_counters))
