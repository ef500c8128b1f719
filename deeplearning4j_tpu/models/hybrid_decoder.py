"""Hybrid state-space / attention / latent-expert decoder — the third family.

The block of the Nemotron-H class of decoders (``model_type`` ``nemotron_h``):
every layer is ``x + mixer(rmsnorm(x; g))`` on the stream ``x`` (T x hidden),
with **one** mixer a layer, picked by a letter of ``cfg.pattern``: ``M`` a
Mamba-2 state-space mixer, ``*`` grouped-query attention, ``E`` a layer of
routed experts that work in a latent narrower than the stream, beside one
shared expert. No bias but the convolution's, no positional encoding (the
state-space layers carry order).

``M``, with ``u = rmsnorm(x)``::

    [z | X B C | dt] = u W_in          widths inner | inner + 2 G N | heads
    X B C = silu(conv1d_K(X B C) + b)  causal, depthwise, K = conv_kernel
    delta = softplus(dt + dt_bias);  A = -exp(A_log)       one scalar a head
    h_t = exp(delta_t A) h_(t-1) + delta_t X_t (x) B_t     head h reads group
    y_t = h_t C_t + D X_t                                  h // (heads / G)
    out = rmsnorm_group(y * silu(z); g_norm) W_out         norm over a group

``*``: ``q, k, v = u W_q, u W_k, u W_v``; causal softmax attention over the
whole sequence (``flash_attention`` with its kv group, no window);
``out = o W_o``.

``E``::

    s = sigmoid(u W_r)                 float32, experts_total outputs
    S = top-k(s + b);  w_e = scale * s_e / sum_S s      the bias b chooses and
    l = u W_down                       hidden -> latent   does not weigh
    routed = sum_{e in S, e held here} w_e relu(l W1_e)^2 W2_e
    out = routed W_up + relu(u Ws1)^2 Ws2               the shared expert

**The program's scan.** The recurrence is computed in its chunked (SSD) form
(``_ssd``): inside a chunk of ``cfg.chunk`` positions a decay-masked quadratic
part (scores ``C B^T`` per group, the mask from a float32 cumulative sum of
``delta A`` inside the chunk, ``(L o scores) X``), one state per chunk
(``B^T (decay delta X)``), the state carried from chunk to chunk by a
``lax.scan``, and ``C state`` for what earlier chunks give. All of it is
``jax.numpy`` whose matmuls reach the MXU, differentiated by JAX. A sequence
that is no multiple of the chunk is padded at its end with ``delta = 0``
(a padded step neither decays nor writes the state) and the result cut back.

**One chip's share.** The fields count what is held **here**, and one pair
says whose share it is: ``model_share`` chips divide every layer by heads
(tensor parallelism) and this is chip ``model_rank`` of them. It holds
``mamba_heads`` state-space heads with ``mamba_groups`` B/C groups (and as
many groups of the gated norm), ``heads`` query heads on ``kv_heads`` key/value
heads (of ``kv_heads_total``: where ranks outnumber them a key/value head is
replicated), ``shared_dim // model_share`` columns of the shared expert and
``vocab_size`` rows of the vocabulary; the published totals are ``model_share``
times the held counts, and every offset is ``model_rank`` times the held
count (``cfg.whole``, ``share_of``). The routed experts divide over more
chips than that: ``experts_count`` of ``experts_total`` from
``experts_offset``, as in ``moe_decoder``. Router, ``W_down``, ``W_up`` and the
norms are whole on every chip. A chip computes its heads' and experts' part
of each mixer's output, and that partial result plus the residual goes on to
the next layer: on one chip the layers run without their all-reduce and
their all-to-all, and nothing here stands in for the other chips.
**A share does not train its router**: where ``experts_count`` is less than
``experts_total`` the router's scores carry no gradient. A token's weights
are normalised over all its chosen experts, and the gradient that tells the
router which of them helped is a sum over all of them, which the all-to-all
brings together; a chip alone has the terms of the experts it holds and
nothing for the others, so that partial gradient, applied alone, only ever
says "the held experts help and the absent ones do not", and the router
learns to send every token to the experts held here (at the benchmark's
sizes the held experts' rows grew fourteenfold in 64 steps, PERF.md section
6, PR 32). The whole model (``experts_count == experts_total``) trains its
router as the source does, through the weights of the chosen experts.
``param_pspecs`` names the ``model`` and ``expert`` mesh axes a sharded step
would use; that step is not built (``lm_loss`` under a mesh raises).

**The expert layer** is ``moe_decoder.routed_experts`` (sort by expert,
``_dispatch``, megablox grouped products, ``_combine``; no token dropped, no
capacity): this family gives it the choices and weights of its sigmoid/bias
router and the experts' body (two grouped products with relu^2 between, on
latent rows). Its buffer has ``tokens x min(experts_per_token,
experts_count)`` rows, all that can land here; a share that holds few of
the router's experts fills a few per cent of them (8 of 512 at 22 a token:
5,632 of 131,072 at a uniform router), and every elementwise pass and row
gather pays for the buffer, not the rows. So the layer has a second buffer,
the rung, whose size follows from the shapes (the smallest power-of-two
fraction of the worst case, a quarter or less, that holds twice a uniform
router's rows: 16,384 there) and which it picks on the device, step by
step, from the rows its sort has just counted: fewer than the rung, and
dispatch, grouped products, relu^2, combine and all their backward passes
run on the rung's rows; otherwise on the whole buffer, as exactly. One
executable holds both routes behind a conditional that encloses the forward
and the backward of a route each on its own; ``_KEPT_NAMES`` stay outside
it. The counter ``buffer_rows`` says which ran (``routed_experts``). In a
trace the layer's work reads by kind under three names of
``moe_decoder.SCOPES`` that are only ever nested inside ``moe_dispatch``,
``moe_combine`` and ``experts``, in both routes, forward and backward:
``rows_moved`` (the gathers of latent rows by index), ``row_index`` (the
sort, its inverse, the counts, the trimming to the rung, the gathers of
scalars) and ``gmm`` (each megablox call of ``_relu2_ffn``, apart from
relu^2, the casts and the update).

Params are float32; the residual stream, the norms, softplus / exp / the
cumulative sums of the scan, the carried state, the sigmoid and the router's
matmul (``Precision.HIGHEST``) are float32; the other matmuls run in
``cfg.dtype`` (bfloat16) with float32 accumulation. The step, the optimizer
and the loss are ``bert.py``'s: this file registers with
``bert.register_family``.
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
from deeplearning4j_tpu.models.moe_decoder import (
    EXPERT_AXIS, _QKV_NAMES, _ROUTE_NAMES, _attention, _grouped_matmul,
    _rmsnorm, head_logits, routed_experts)

MODEL_AXIS = "model"
# ``moe_decoder.SCOPES`` and this family's: ``ssm_in`` (the norm and the
# input projection), ``ssm_conv``, ``ssm_scan`` (delta, the decays, the
# chunked scan, the ``D`` skip), ``ssm_out`` (the gated norm, the output
# projection, the residual), ``moe_latent`` (``W_down``, ``W_up``),
# ``moe_shared`` (the shared expert). The three nested names of the expert
# layer (``rows_moved``, ``row_index``, ``gmm``) come with
# ``moe_decoder.SCOPES``. PERF.md section 3 lists what reads each.
SCOPES = moe_decoder.SCOPES + ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out",
                               "moe_latent", "moe_shared")
# ``checkpoint_name`` names of what a rematerialised block keeps beside its
# input and attention's five (``_QKV_NAMES``, ``FLASH_SAVED_NAMES``), each
# the forward's own value in the forward's dtype. Of an expert layer: the
# router's float32 logits (its matmul runs at ``HIGHEST``, six passes; the
# name sits on the matmul's result, so that the sigmoid's backward reads the
# kept value) and its choice (no second top-k); the combined latent rows
# that ``W_up`` reads (its weight gradient needs them: without the name the
# replay gathers them a second time); and, since PR 37, the inputs of the
# expert layer's backward rule, which the replay made again only to hand
# them over: the held choices' weights, the sort by expert, its inverse and
# the group sizes (``_ROUTE_NAMES``, 1.6 MB a layer at the benchmark's
# sizes, for a sort, a scatter and two masked sums) and the latent rows
# ``u W_down`` (34 MB); the shared expert's ``u W_s1`` before its relu (22
# MB: relu's backward reads its input, so the name sits on the product).
# With the weights kept a share's backward reads logits and choice no
# longer, and they fall out of its residuals; the whole model's reads them.
# Of a state-space layer: the input projection's float32 result ``[z | X B
# C | dt]`` (152 MB) and the convolution's float32 taps before their silu
# (84 MB; silu's backward reads its input). PERF.md section 7 ("What a
# block could still keep") has every name's bytes and the milliseconds it
# ends, and the candidates that were refused (the normed input in the
# compute dtype: the step got slower). Nothing of the scan is kept: the
# state entering every chunk is 67 MB a layer at the benchmark's sizes, and
# a ``lax.scan``'s backward reads its own residuals, not a named copy, so
# the replay runs the carry whatever is named (PERF.md section 6, PR 32).
_KEPT_NAMES = ("router_logits", "router_choice", "moe_part", *_ROUTE_NAMES,
               "latent_rows", "shared_hidden", "ssm_projected", "ssm_taps")
_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    vocab_size: int = 131072         # rows of the vocabulary held here
    hidden: int = 4096
    layers: int = 88
    pattern: str = _PATTERN          # one mixer a layer, read from its start
    mamba_heads: int = 128           # state-space heads held here
    mamba_head_dim: int = 64
    mamba_groups: int = 8            # B/C groups (and norm groups) held here
    state_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 128                 # positions in a chunk of the scan
    heads: int = 32                  # query heads held here
    kv_heads: int = 2                # key/value heads held here
    kv_heads_total: Optional[int] = None   # None: kv_heads * model_share
    head_dim: int = 128
    latent_dim: int = 1024           # the routed experts' input and output
    expert_dim: int = 2688           # a routed expert's inner width
    shared_dim: int = 5376           # the shared expert's published width
    experts_total: int = 512         # the router's outputs
    experts_per_token: int = 22
    experts_count: Optional[int] = None   # experts held here (None: all)
    experts_offset: int = 0          # the first expert held here
    norm_topk_prob: bool = True
    routed_scale: float = 5.0
    model_share: int = 1             # chips that divide a layer by heads
    model_rank: int = 0              # which of them this is
    rms_eps: float = 1e-5
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16        # matmul compute dtype (params fp32)
    attention_impl: str = "flash"    # 'flash' (streamed kernels) | 'full'
    # jax.checkpoint each block: the backward pass replays it from its
    # input, but for what _KEPT_NAMES, _QKV_NAMES and FLASH_SAVED_NAMES name
    remat: bool = True

    def __post_init__(self):
        if self.experts_count is None:
            object.__setattr__(self, "experts_count", self.experts_total)
        if self.kv_heads_total is None:
            object.__setattr__(self, "kv_heads_total",
                               self.kv_heads * self.model_share)
        off, count = self.experts_held
        assert 0 <= off and off + count <= self.experts_total, (off, count)
        assert len(self.pattern) >= self.layers \
            and set(self.pattern) <= set("M*E"), self.pattern
        assert self.mamba_heads % self.mamba_groups == 0
        assert self.heads % self.kv_heads == 0
        assert self.shared_dim % self.model_share == 0
        assert 0 <= self.model_rank < self.model_share
        assert self.kv_heads == max(
            1, self.kv_heads_total // self.model_share), self.kv_heads_total

    causal = True      # every position is a target: ``lm_loss``'s dense head

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(offset, count) of the experts this program holds."""
        return self.experts_offset, self.experts_count

    @property
    def kinds(self) -> str:
        """The mixer of each layer, one letter a layer."""
        return self.pattern[:self.layers]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def shared_columns(self) -> int:
        """Columns of the shared expert held here."""
        return self.shared_dim // self.model_share

    @property
    def whole(self) -> "HybridDecoderConfig":
        """The uncut model this is a share of."""
        s = self.model_share
        return dataclasses.replace(
            self, model_share=1, model_rank=0, vocab_size=self.vocab_size * s,
            mamba_heads=self.mamba_heads * s,
            mamba_groups=self.mamba_groups * s, heads=self.heads * s,
            kv_heads=self.kv_heads_total, kv_heads_total=None,
            experts_count=self.experts_total, experts_offset=0)


# ------------------------------------------------------------- parameters
def _mamba_widths(cfg) -> Dict[str, int]:
    """Columns of the input projection's parts held here."""
    gn = cfg.mamba_groups * cfg.state_dim
    return {"z": cfg.mamba_inner, "x": cfg.mamba_inner, "B": gn, "C": gn,
            "dt": cfg.mamba_heads}


def init_params(key, cfg: HybridDecoderConfig) -> Dict[str, Any]:
    """The parameter pytree of what is held here: normal(0.02) matrices,
    unit norm scales, the token embedding normal(1.0) (a stream of unit
    scale in which a token's own embedding outweighs what random blocks add
    to every token alike, ``moe_decoder.init_params``). The state-space
    layers as the source initialises them: ``dt_bias`` the inverse softplus
    of a log-uniform draw in [0.001, 0.1] floored at 1e-4, ``A_log`` the log
    of a uniform draw in [1, 16], ``D`` = 1, the convolution uniform in
    +-1/sqrt(K) with its bias. The router's selection bias is normal(0.02),
    so that it decides close choices."""
    def dense(k, shape, std=0.02):
        return jax.random.normal(k, shape, jnp.float32) * std

    def scale(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    H, K = cfg.hidden, cfg.conv_kernel
    widths = _mamba_widths(cfg)
    keys = jax.random.split(key, 2 + cfg.layers)
    blocks = []
    for kind, bk in zip(cfg.kinds, keys[2:]):
        bk = jax.random.split(bk, 12)
        if kind == "M":
            dt = jnp.exp(jax.random.uniform(bk[10], (cfg.mamba_heads,))
                         * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
            dt = jnp.maximum(dt, 1e-4)
            bound = K ** -0.5
            block = {
                "in": {n: dense(k, (H, w))
                       for k, (n, w) in zip(bk, widths.items())},
                "conv": {n: jax.random.uniform(
                    k, (K, widths[n]), jnp.float32, -bound, bound)
                    for k, n in zip(bk[5:8], "xBC")},
                "conv_bias": {n: jax.random.uniform(
                    k, (widths[n],), jnp.float32, -bound, bound)
                    for k, n in zip(jax.random.split(bk[8], 3), "xBC")},
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    bk[11], (cfg.mamba_heads,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((cfg.mamba_heads,), jnp.float32),
                "norm": scale(cfg.mamba_inner),
                "out": dense(bk[9], (cfg.mamba_inner, H))}
        elif kind == "*":
            D = cfg.head_dim
            block = {"q": dense(bk[0], (H, cfg.heads * D)),
                     "k": dense(bk[1], (H, cfg.kv_heads * D)),
                     "v": dense(bk[2], (H, cfg.kv_heads * D)),
                     "o": dense(bk[3], (cfg.heads * D, H))}
        else:
            Z, F, S = cfg.latent_dim, cfg.expert_dim, cfg.shared_columns
            held = cfg.experts_count
            block = {"router": dense(bk[0], (H, cfg.experts_total)),
                     "router_bias": dense(bk[1], (cfg.experts_total,)),
                     "down": dense(bk[2], (H, Z)),
                     "up": dense(bk[3], (Z, H)),
                     "experts": {"w1": dense(bk[4], (held, Z, F)),
                                 "w2": dense(bk[5], (held, F, Z))},
                     "shared": {"w1": dense(bk[6], (H, S)),
                                "w2": dense(bk[7], (S, H))}}
        blocks.append(dict(block, ln=scale(H)))
    return {"tok_emb": dense(keys[0], (cfg.vocab_size, H), 1.0),
            "ln_f": scale(H),
            "lm_head": dense(keys[1], (H, cfg.vocab_size)),
            "blocks": blocks}


def param_pspecs(cfg: HybridDecoderConfig) -> Dict[str, Any]:
    """The layout of the stated deployment: heads, groups, the shared
    expert's columns and the vocabulary ride the ``model`` mesh axis (a
    projection into heads by columns, out of them by rows), the routed
    experts' leading axis the ``expert`` axis; router, latent projections
    and the layer norms are whole on every chip."""
    cols, rows, vec = P(None, MODEL_AXIS), P(MODEL_AXIS, None), P(MODEL_AXIS)
    by_kind = {
        "M": {"in": {n: cols for n in ("z", "x", "B", "C", "dt")},
              "conv": {n: cols for n in "xBC"},
              "conv_bias": {n: vec for n in "xBC"},
              "dt_bias": vec, "A_log": vec, "D": vec,
              "norm": {"scale": vec}, "out": rows},
        "*": {"q": cols, "k": cols, "v": cols, "o": rows},
        "E": {"router": P(), "router_bias": P(), "down": P(), "up": P(),
              "experts": {"w1": P(EXPERT_AXIS, None, None),
                          "w2": P(EXPERT_AXIS, None, None)},
              "shared": {"w1": cols, "w2": rows}}}
    return {"tok_emb": rows, "ln_f": {"scale": P()}, "lm_head": cols,
            "blocks": [dict(by_kind[kind], ln={"scale": P()})
                       for kind in cfg.kinds]}


def share_of(params, cfg: HybridDecoderConfig) -> Dict[str, Any]:
    """This chip's share (``cfg``) of the uncut model's parameters
    (``params`` of ``cfg.whole``): what ``param_pspecs`` shards, cut at
    ``model_rank`` and ``experts_offset``. A key/value head that several
    ranks read is copied to each."""
    rank = cfg.model_rank

    def part(a, axis, width, start=None):
        start = rank * width if start is None else start
        return lax.slice_in_dim(a, start, start + width, axis=axis)

    widths = _mamba_widths(cfg)
    kv = cfg.kv_heads * cfg.head_dim
    kv_start = rank * cfg.kv_heads_total // cfg.model_share * cfg.head_dim
    off, held = cfg.experts_held
    blocks = []
    for kind, bp in zip(cfg.kinds, params["blocks"]):
        if kind == "M":
            block = {
                "in": {n: part(bp["in"][n], 1, w) for n, w in widths.items()},
                "conv": {n: part(bp["conv"][n], 1, widths[n]) for n in "xBC"},
                "conv_bias": {n: part(bp["conv_bias"][n], 0, widths[n])
                              for n in "xBC"},
                **{n: part(bp[n], 0, cfg.mamba_heads)
                   for n in ("dt_bias", "A_log", "D")},
                "norm": {"scale": part(bp["norm"]["scale"], 0,
                                       cfg.mamba_inner)},
                "out": part(bp["out"], 0, cfg.mamba_inner)}
        elif kind == "*":
            q = cfg.heads * cfg.head_dim
            block = {"q": part(bp["q"], 1, q), "o": part(bp["o"], 0, q),
                     "k": part(bp["k"], 1, kv, kv_start),
                     "v": part(bp["v"], 1, kv, kv_start)}
        else:
            block = {
                **{n: bp[n] for n in ("router", "router_bias", "down", "up")},
                "experts": {n: part(w, 0, held, off)
                            for n, w in bp["experts"].items()},
                "shared": {
                    "w1": part(bp["shared"]["w1"], 1, cfg.shared_columns),
                    "w2": part(bp["shared"]["w2"], 0, cfg.shared_columns)}}
        blocks.append(dict(block, ln=bp["ln"]))
    return {"tok_emb": part(params["tok_emb"], 0, cfg.vocab_size),
            "ln_f": params["ln_f"],
            "lm_head": part(params["lm_head"], 1, cfg.vocab_size),
            "blocks": blocks}


# ------------------------------------------------------- state-space mixer
def _causal_conv(x, w, b=None):
    """Depthwise causal convolution over time: x (B, T, channels) float32,
    taps w (K, channels) with the last on the current position, bias b or
    none. Shared: the Mamba-2 mixer here gives a bias, ``conv_decoder``'s
    gated short convolution none."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    taps = sum(padded[:, j:j + T] * w[j] for j in range(K))
    return taps if b is None else b + taps


def _ssd(X, delta, A, Bm, Cm, chunk: int):
    """The chunked scan. ``X`` (B, T, heads, P) and ``Bm``, ``Cm``
    (B, T, groups, N) in the compute dtype, ``delta`` (B, T, heads) float32
    after its softplus, ``A`` (heads,) float32 and negative. Returns
    ``y_t = C_t h_t`` (B, T, heads, P) float32 for
    ``h_t = exp(delta_t A) h_(t-1) + delta_t X_t (x) B_t``, ``h_0 = 0``."""
    Bsz, T, heads, Pd = X.shape
    G, N = Bm.shape[2:]
    R = heads // G                          # heads that read one group
    dtype = X.dtype
    pad = -T % chunk
    if pad:     # delta = 0: a padded step neither decays nor writes
        X, delta, Bm, Cm = (jnp.pad(t, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (t.ndim - 2))
                            for t in (X, delta, Bm, Cm))
    C = (T + pad) // chunk
    Xc = X.reshape(Bsz, C, chunk, G, R, Pd)
    Bc, Cc = (t.reshape(Bsz, C, chunk, G, N) for t in (Bm, Cm))
    dc = delta.reshape(Bsz, C, chunk, G, R)
    # log-decay from a chunk's start to each of its positions, inclusive
    cum = jnp.cumsum(dc * A.reshape(G, R), axis=2)             # (b,c,l,g,r)

    # inside a chunk: position l reads s <= l through exp(cum_l - cum_s)
    scores = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc,
                        preferred_element_type=jnp.float32)
    cum_t = cum.transpose(0, 1, 3, 4, 2)                       # (b,c,g,r,l)
    seg = cum_t[..., :, None] - cum_t[..., None, :]            # (b,c,g,r,l,s)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    mixed = scores[:, :, :, None] * decay \
        * dc.transpose(0, 1, 3, 4, 2)[..., None, :]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mixed.astype(dtype), Xc,
                   preferred_element_type=jnp.float32)

    # a chunk's own state at its end, and the state entering every chunk
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dc                # (b,c,s,g,r)
    own = jnp.einsum("bcsgn,bcsgrp->cbgrpn", Bc,
                     (Xc * to_end[..., None]).astype(dtype),
                     preferred_element_type=jnp.float32)
    through = jnp.exp(cum[:, :, -1]).transpose(1, 0, 2, 3)     # (c,b,g,r)

    def carry(h, step):
        own_c, through_c = step
        return through_c[..., None, None] * h + own_c, h

    _, entering = lax.scan(carry, jnp.zeros_like(own[0]), (own, through))
    # entering (c,b,g,r,p,n)
    y = y + jnp.einsum("bclgn,cbgrpn->bclgrp", Cc, entering.astype(dtype),
                       preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(Bsz, T + pad, heads, Pd)[:, :T]


def _mamba(bp, x, cfg: HybridDecoderConfig):
    """The Mamba-2 mixer on the float32 stream x (B, T, hidden)."""
    Bsz, T, _ = x.shape
    heads, Pd, G = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups
    widths = _mamba_widths(cfg)
    inner, gn = widths["x"], widths["B"]
    with jax.named_scope("ssm_in"):
        u = _rmsnorm(x, bp["ln"], cfg.rms_eps).astype(cfg.dtype)
        w_in = jnp.concatenate([bp["in"][n] for n in widths],
                               axis=1).astype(cfg.dtype)
        zxbcdt = checkpoint_name(
            jnp.dot(u, w_in, preferred_element_type=jnp.float32),
            "ssm_projected")
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(checkpoint_name(_causal_conv(
            xbc, jnp.concatenate([bp["conv"][n] for n in "xBC"], axis=1),
            jnp.concatenate([bp["conv_bias"][n] for n in "xBC"])),
            "ssm_taps"))
        X, Bm, Cm = jnp.split(xbc.astype(cfg.dtype), [inner, inner + gn],
                              axis=-1)
        X = X.reshape(Bsz, T, heads, Pd)
    with jax.named_scope("ssm_scan"):
        delta = jax.nn.softplus(dt + bp["dt_bias"])
        y = _ssd(X, delta, -jnp.exp(bp["A_log"]),
                 Bm.reshape(Bsz, T, G, -1), Cm.reshape(Bsz, T, G, -1),
                 cfg.chunk)
        y = y + bp["D"][:, None] * X
    with jax.named_scope("ssm_out"):
        y = (y.reshape(Bsz, T, inner) * jax.nn.silu(z)).reshape(
            Bsz, T, G, inner // G)
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.rms_eps)
        y = y.reshape(Bsz, T, inner) * bp["norm"]["scale"]
        return x + jnp.dot(y.astype(cfg.dtype), bp["out"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------- attention mixer
def _attend(bp, x, cfg: HybridDecoderConfig):
    """Grouped-query causal attention on the float32 stream, with no
    positional encoding and no window."""
    Bsz, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        u = _rmsnorm(x, bp["ln"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = (
            (u @ bp[n].astype(cfg.dtype)).reshape(Bsz, T, -1, cfg.head_dim)
            for n in ("q", "k", "v"))
    with jax.named_scope("attention"):
        o = _attention(
            *(checkpoint_name(t.transpose(0, 2, 1, 3), n)
              for t, n in zip((q, k, v), _QKV_NAMES)), None, cfg)
        o = o.transpose(0, 2, 1, 3).reshape(Bsz, T, -1)
    with jax.named_scope("attn_out"):
        return x + jnp.dot(o, bp["o"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


# ------------------------------------------------------------ expert mixer
def _route(s, bias, cfg, eps: float = 0.0):
    """Sigmoid scores (N, experts_total) float32 -> the chosen experts
    (N, k), by score plus bias, and their weights, from the scores alone:
    normalised over the chosen (``cfg.norm_topk_prob``; ``eps`` is added to
    that sum where the family has one) and times ``cfg.routed_scale``.
    Shared with ``conv_decoder``, whose sum carries 1e-6; ``cfg`` is either
    family's (``experts_per_token``, ``norm_topk_prob``, ``routed_scale``)."""
    _, top_e = lax.top_k(s + bias, cfg.experts_per_token)
    top_e = checkpoint_name(top_e, "router_choice")
    # s[n, top_e[n, j]] as a masked sum over the experts, which XLA fuses
    # into one pass: a gather of N x k scalars, and the scatter that is its
    # transpose, take ten times as long on the chip (PERF.md section 6)
    top_s = jnp.where(top_e[:, :, None] == jnp.arange(s.shape[-1]),
                      s[:, None, :], 0.0).sum(-1)
    if cfg.norm_topk_prob:
        total = top_s.sum(-1, keepdims=True)
        if eps:
            total = total + eps
        top_s = top_s / total
    return top_e, top_s * cfg.routed_scale


def _relu2_ffn(xs, experts, sizes):
    """The held experts over latent rows sorted by expert: two grouped
    products with relu^2 between, no gate."""
    w1, w2 = (experts[n].astype(xs.dtype) for n in ("w1", "w2"))
    h = jax.nn.relu(_grouped_matmul(xs, w1, sizes))
    return _grouped_matmul(h * h, w2, sizes)


def _expert_parts(bp, u, cfg: HybridDecoderConfig):
    """The expert layer on normed rows ``u`` (N, hidden) float32: what the
    experts held here give (through ``W_up``), what the shared expert's
    columns held here give, both (N, hidden) float32, and the counters."""
    with jax.named_scope("router"):
        r = checkpoint_name(jnp.dot(u, bp["router"],
                                    precision=lax.Precision.HIGHEST),
                            "router_logits")
        if cfg.experts_count < cfg.experts_total:
            # a share alone: the scores are a constant of the step (the
            # module's docstring, "One chip's share")
            r = lax.stop_gradient(r)
        top_e, top_w = _route(jax.nn.sigmoid(r), bp["router_bias"], cfg)
    uc = u.astype(cfg.dtype)
    with jax.named_scope("moe_latent"):
        latent = checkpoint_name(uc @ bp["down"].astype(cfg.dtype),
                                 "latent_rows")
    part, counters = routed_experts(
        latent, top_e, top_w, cfg.experts_held, cfg.experts_total, cfg.dtype,
        _relu2_ffn, bp["experts"])
    with jax.named_scope("moe_latent"):
        part = checkpoint_name(part.astype(cfg.dtype), "moe_part")
        routed = jnp.dot(part, bp["up"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
    with jax.named_scope("moe_shared"):
        h = jax.nn.relu(checkpoint_name(
            uc @ bp["shared"]["w1"].astype(cfg.dtype), "shared_hidden"))
        shared = jnp.dot(h * h, bp["shared"]["w2"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
    return routed, shared, counters


def _experts(bp, x, cfg: HybridDecoderConfig):
    Bsz, T, H = x.shape
    with jax.named_scope("moe_dispatch"):
        u = _rmsnorm(x, bp["ln"], cfg.rms_eps)
    routed, shared, counters = _expert_parts(bp, u.reshape(Bsz * T, H), cfg)
    with jax.named_scope("moe_combine"):
        return x + (routed + shared).reshape(Bsz, T, H), counters


# ---------------------------------------------------------------- the model
def _block(bp, x, kind: str, cfg: HybridDecoderConfig):
    """One layer on the float32 residual stream x (B, T, hidden): the new
    stream, and the routing counters of an expert layer (else None)."""
    if kind == "E":
        return _experts(bp, x, cfg)
    return (_mamba if kind == "M" else _attend)(bp, x, cfg), None


def encode(params, token_ids, cfg: HybridDecoderConfig):
    """Embedding, the blocks and the final norm: the float32 hidden states
    (B, T, hidden) and the routing counters, stacked over the expert
    layers."""
    from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
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
            x, c = blocks[kind](bp, x)
            if c is not None:
                counters.append(c)
        with jax.named_scope("final_ln"):
            x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
    return x, jax.tree.map(lambda *c: jnp.stack(c), *counters) \
        if counters else None


def _one_chip(mesh: Optional[Mesh]):
    if mesh is not None:
        raise NotImplementedError(
            "the hybrid decoder runs one chip's share without its exchange; "
            "the sharded step (all-reduce over the 'model' axis and "
            "all-to-all over the 'expert' axis of param_pspecs) is not "
            "built")


def forward(params, token_ids, cfg: HybridDecoderConfig,
            mesh: Optional[Mesh] = None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) float32."""
    _one_chip(mesh)
    x, _ = encode(params, token_ids, cfg)
    return head_logits(params, x, cfg).astype(jnp.float32)


def lm_loss_and_counters(params, batch, cfg: HybridDecoderConfig,
                         mesh: Optional[Mesh] = None):
    """Weighted next-token cross-entropy of ``batch`` (tokens, targets,
    weights) through ``bert.loss_from_logits``, and the routing counters of
    the step as ``moe_decoder.lm_loss_and_counters`` gives them, stacked
    over the expert layers."""
    _one_chip(mesh)
    x, counters = encode(params, batch["tokens"], cfg)
    return loss_from_logits(head_logits(params, x, cfg), batch), counters


def lm_loss(params, batch, cfg: HybridDecoderConfig,
            mesh: Optional[Mesh] = None):
    return lm_loss_and_counters(params, batch, cfg, mesh)[0]


bert.register_family(HybridDecoderConfig, types.SimpleNamespace(
    init_params=init_params, param_pspecs=param_pspecs, forward=forward,
    lm_loss=lm_loss, loss_and_aux=lm_loss_and_counters))
