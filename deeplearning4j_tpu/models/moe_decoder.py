"""Causal decoder with routed experts — the second model family.

The block of today's small-expert open decoders, beside ``bert.py``'s
pre-LayerNorm block: RMSNorm, no biases, grouped-query attention (``heads``
query heads share ``kv_heads`` key/value heads), rotary positions or none and
a sliding window or none **by layer** (``rope_layout``, ``window_layout``),
and in place of the MLP a layer of ``experts_total`` gated (ReGLU) experts of
which each token takes ``experts_per_token``. The router reads the layer's
input, before the input norm and before attention.

For layer ``l`` with input ``x`` (T x hidden)::

    r   = x W_r                                  router logits, float32
    a   = rmsnorm(x; g1);  q, k, v = a W_q, a W_k, a W_v
    q,k = rope(q), rope(k)                       if rope_layout[l] else as is
    y   = x + attention(q, k, v) W_o             causal; if window_layout[l],
                                                 key j visible iff 0 <= i-j < window
    m   = rmsnorm(y; g2);  p = softmax(r);  S = top-k(p);  w_e = p_e / sum_S p
    out = y + sum_{e in S, e held here} w_e (relu(m W_g,e) * (m W_u,e)) W_d,e

**Expert parallelism's share.** A program holds the experts
``experts_offset .. experts_offset + experts_count`` (``cfg.experts_held``):
the router keeps all ``experts_total`` outputs and its ``experts_per_token``,
the layer computes the part of the sum its own experts give, and what the
absent experts would add is left out — that partial result goes on to the
next layer. On one chip the layer runs without its exchange; nothing here
stands in for the other chips. ``param_pspecs`` gives the experts' leading
axis (and the vocabulary) the ``expert`` mesh axis, which is what a
four-chip mesh would shard; the sharded step (the all-to-all) is not built.

**No token is dropped.** The token-choices that land on held experts are
sorted by expert into a buffer of ``tokens x min(experts_per_token,
experts held)`` rows (the worst case: every choice that can land here does;
a token's choices are different experts), the experts run as grouped matrix
products over the ragged groups (megablox ``gmm``, whose grid visits only
the tiles that hold rows), and the weighted results are gathered back.
There is no capacity factor and no dummy expert. From the choices on the
layer is ``routed_experts``, which a family calls with its own router's
choices and weights and its own experts' body: this file's softmax router
and ReGLU, ``hybrid_decoder.py``'s sigmoid/bias router and relu^2 experts
on latent rows, ``conv_decoder.py``'s sigmoid/bias router and SwiGLU. Where
few of the router's experts are held, the layer has
a second, small buffer (the rung, from shapes alone) and picks it on the
device, step by step, whenever the rows it has just counted fit it; this
family's share at the benchmark's sizes has none (``routed_experts``).

**What the layer moves.** A layer gathers ``tokens x k`` rows five times in
a rematerialised step: the dispatch (``_dispatch``: forward, and again in
the block's replay, from the ``tokens`` normed rows), the combine
(``_combine``, forward only, from the buffer), and their two backward rules,
which are gathers too (the cotangent's rows by token, in float32; the
buffer's rows back by choice). ``_combine`` keeps the experts' results, the
weights and the index arrays, never the gathered rows, and takes the router
weights' gradient as a row-wise dot product in buffer order: the replay
holds no combine. The backward rules view the routed rows ``(k, tokens,
hidden)``, a free reshape of the buffer summed over its leading axis;
``(tokens, k, hidden)`` with ``k = 6`` is a padded copy on the chip, and the
forward still makes one (see ``_combine``).

Params are float32, matmul compute is ``cfg.dtype`` (bfloat16), the residual
stream, norms, softmaxes and the router's matmul are float32. The step, the
optimizer and the loss are ``bert.py``'s (``make_train_step``,
``loss_from_logits``): this file registers its functions with
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

from deeplearning4j_tpu.models import bert
from deeplearning4j_tpu.models.bert import loss_from_logits

EXPERT_AXIS = "expert"
# The vocabulary of ``jax.named_scope`` names with this family's names after
# ``bert.SCOPES`` (which stays as it is, so every metric file that lists its
# names by hand reads as before). This block reuses ``embed``, ``attn_qkv``,
# ``attention``, ``attn_out``, ``final_ln``, ``lm_head``, ``loss`` and
# ``optimizer`` and adds: ``router`` (the router's matmul, softmax and
# top-k), ``moe_dispatch`` (the norm before the experts, the sort by expert,
# the row gather and its backward: the gather back from the buffer and the
# sum over the slots), ``experts`` (the grouped products), ``moe_combine``
# (the gather back, the weighted sum, the residual, and the combine's
# backward: the cotangent's rows gathered by token, weighted, and their dot
# products with the experts' results), ``rope``. The backward rules need no
# outer scope of their own: the transposed name stack keeps the forward's.
# The last three name the routed-expert layer's work by kind and are only
# ever nested inside ``moe_dispatch``, ``moe_combine`` or ``experts``, never
# at top level and never around a whole one of them, so a metric file that
# does not list them reads what it read (as ``head_rows`` in ``lm_head``):
# ``rows_moved`` (every gather of full-width rows by index: ``_dispatch``,
# ``_combine`` and their two backward rules; not the sums, copies, casts and
# norms around them), ``row_index`` (the work on indices and scalars: the
# sort by expert, its inverse, the group sizes, ``fits`` and
# ``buffer_rows``, the integer divisions and index transposes, the
# trimming to the rung, the combine's two gathers of ``tokens x k``
# scalars), ``gmm`` (each megablox call with what the library's wrapper
# runs for it: group metadata, the zero-fill of the "none" rows; not the
# activation, the casts, the operand transposes nor the AdamW update). A
# backward rule opens its nested names itself. PERF.md section 3 lists what
# reads each.
SCOPES = bert.SCOPES + ("router", "moe_dispatch", "experts", "moe_combine",
                        "rope", "rows_moved", "row_index", "gmm")
# The grouped products' tiles (megablox ``tiling``): at most this many rows,
# and along a weight's dimension the largest divisor up to this many columns
# (2560 -> 1280, 768 whole), so no tile is ever wider than its array.
# Measured on the v5e at this block's widths (PERF.md section 6, PR 28).
_GMM_ROWS, _GMM_COLS = 512, 1280
# ``checkpoint_name`` names of a block's queries, rotated keys and values in
# the attention kernels' (B, heads, T, head_dim) layout. With the kernel's
# own ``pallas_kernels.FLASH_SAVED_NAMES`` they are all that a rematerialised
# block keeps beside its inputs (``encode``): the replay then runs neither
# the three projections, the rotation and the head transposes nor
# ``flash_fwd``. At the benchmark's sizes the kernel's two cost 0.36 GB of
# the step's planned bytes for 5.8 % more tokens a second, these three
# another 0.36 GB for 2.3 % (PERF.md section 6, PR 29).
_QKV_NAMES = ("attn_q", "attn_k", "attn_v")
# ``checkpoint_name`` names of what ``routed_experts`` makes from a token's
# choices before any row moves: the held choices' weights (N, slots)
# float32, the sort by expert ``order`` (N * slots) int32, its inverse
# ``back`` (N, slots) and the group sizes. They are the expert layer's
# residuals (``_tiered`` and ``_combine`` keep their inputs), so a family
# whose checkpoint policy lists them replays neither the sort, the inverse
# permutation's scatter nor the masked sums; a policy that does not list a
# name ignores it, and a name lowers to nothing. ``hybrid_decoder`` lists
# them (1.6 MB a layer at its benchmark's sizes); ``encode`` here and
# ``conv_decoder`` do not (PERF.md section 7, "What a block could still
# keep").
_ROUTE_NAMES = ("route_weight", "route_order", "route_back", "route_sizes")


@dataclasses.dataclass(frozen=True)
class MoEDecoderConfig:
    vocab_size: int = 151936
    hidden: int = 2560
    layers: int = 52
    heads: int = 28
    kv_heads: int = 4
    head_dim: int = 128
    expert_dim: int = 768            # a routed expert's inner width
    experts_total: int = 64          # the router's outputs
    experts_per_token: int = 6
    experts_count: Optional[int] = None   # experts held here (None: all)
    experts_offset: int = 0          # the first expert held here
    norm_topk_prob: bool = True
    window: int = 4096
    # per layer, 1 = sliding window / rotary positions, 0 = global / none;
    # a layout longer than ``layers`` is read from its start
    window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    max_seq: int = 16384
    dtype: Any = jnp.bfloat16        # matmul compute dtype (params fp32)
    attention_impl: str = "flash"    # 'flash' (streamed kernels) | 'full'
    # jax.checkpoint each block. The backward pass replays the block from
    # its inputs, but for what attention made: q, rotated k and v, and the
    # streamed kernel's output and logsumexp are kept (_QKV_NAMES,
    # FLASH_SAVED_NAMES); nothing of the expert layer is. Its replay is the
    # dispatch gather and the grouped products: the combine's backward
    # reads the experts' results, not the rows gathered back (_combine)
    remat: bool = True

    def __post_init__(self):
        for name in ("window_layout", "rope_layout"):   # lists from JSON
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.experts_count is None:
            object.__setattr__(self, "experts_count", self.experts_total)
        off, count = self.experts_held
        assert 0 <= off and off + count <= self.experts_total, (off, count)
        assert self.heads % self.kv_heads == 0
        assert min(len(self.window_layout),
                   len(self.rope_layout)) >= self.layers

    causal = True      # every position is a target: ``lm_loss``'s dense head

    @property
    def experts_held(self) -> Tuple[int, int]:
        """(offset, count) of the experts this program holds."""
        return self.experts_offset, self.experts_count


def init_params(key, cfg: MoEDecoderConfig) -> Dict[str, Any]:
    """The parameter pytree: normal(0.02) matrices, unit norm scales, no
    bias anywhere. Only the held experts exist. The token embedding is
    normal(1.0): a residual stream of unit scale, in which a token's own
    embedding outweighs what randomly weighted blocks add to every token
    alike. The router reads that stream un-normed, so its logits are of
    order one and differ from token to token, as a trained router's do. At
    0.02 the stream is the blocks' common output, every token picks the
    same experts within fifty AdamW steps at 1e-4, and the rows an expert
    sees are all or none (PERF.md section 6, PR 28)."""
    def dense(k, shape, std=0.02):
        return jax.random.normal(k, shape, jnp.float32) * std

    H, D, F = cfg.hidden, cfg.head_dim, cfg.expert_dim
    held = cfg.experts_count
    keys = jax.random.split(key, 2 + cfg.layers)
    blocks = []
    for i in range(cfg.layers):
        bk = jax.random.split(keys[2 + i], 8)
        blocks.append({
            "ln1": {"scale": jnp.ones((H,), jnp.float32)},
            "q": dense(bk[0], (H, cfg.heads * D)),
            "k": dense(bk[1], (H, cfg.kv_heads * D)),
            "v": dense(bk[2], (H, cfg.kv_heads * D)),
            "o": dense(bk[3], (cfg.heads * D, H)),
            "ln2": {"scale": jnp.ones((H,), jnp.float32)},
            "router": dense(bk[4], (H, cfg.experts_total)),
            "experts": {"gate": dense(bk[5], (held, H, F)),
                        "up": dense(bk[6], (held, H, F)),
                        "down": dense(bk[7], (held, F, H))},
        })
    return {"tok_emb": dense(keys[0], (cfg.vocab_size, H), 1.0),
            "ln_f": {"scale": jnp.ones((H,), jnp.float32)},
            "lm_head": dense(keys[1], (H, cfg.vocab_size)),
            "blocks": blocks}


def param_pspecs(cfg: MoEDecoderConfig) -> Dict[str, Any]:
    """Expert parallelism's layout: the experts' leading axis and the
    vocabulary ride the ``expert`` mesh axis, attention and the router are
    whole on every chip."""
    expert = P(EXPERT_AXIS, None, None)
    block = {"ln1": {"scale": P()}, "ln2": {"scale": P()},
             "q": P(), "k": P(), "v": P(), "o": P(), "router": P(),
             "experts": {"gate": expert, "up": expert, "down": expert}}
    return {"tok_emb": P(EXPERT_AXIS, None), "ln_f": {"scale": P()},
            "lm_head": P(None, EXPERT_AXIS),
            "blocks": [block for _ in range(cfg.layers)]}


def _rmsnorm(x, p, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * p["scale"]


def _rope(x, positions, theta: float):
    """Rotate-half rotary embedding over all of the head's dimensions:
    x (B, T, heads, D), positions (B, T) or (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq     # (.., T, half)
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, window: Optional[int], cfg: MoEDecoderConfig):
    """Causal grouped-query attention: q (B, heads, T, D), k and v
    (B, kv_heads, T, D). The streamed kernels read a query head's kv head
    through their index maps and skip the blocks outside the window."""
    T = q.shape[2]
    if window is not None and window >= T:
        window = None                   # the band covers the whole triangle
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _attention_reference, flash_attention, flash_envelope_ok)
    if cfg.attention_impl == "flash" and flash_envelope_ok(T):
        return flash_attention(q, k, v, True, None, None, None,
                               jax.default_backend() != "tpu", window)
    return _attention_reference(q, k, v, True, None, window)


# ------------------------------------------------------------ expert layer
def _route(r, cfg: MoEDecoderConfig):
    """Router logits (N, experts_total) float32 -> the chosen experts
    (N, k) and their weights, normalised over the chosen."""
    p = jax.nn.softmax(r, axis=-1)
    top_p, top_e = lax.top_k(p, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e, top_p


@jax.custom_vjp
def _dispatch(x, order, back):
    """The routed rows in buffer order: ``x[order // k]`` for ``x`` (N, H).
    ``order`` (N * k) lists the choices ``token * k + slot`` in buffer order
    and ``back`` (N, k) is its inverse, so every row of ``x`` is read exactly
    ``k`` times: the transpose is a gather and a sum, not the scatter-add XLA
    would make (14 times slower than the gather on the v5e at this block's
    sizes). The backward gathers the buffer's rows slot by slot
    (``back.T``), so that its view is ``(k, N, H)``, a free reshape summed
    over the leading axis; ``(N, k, H)`` with ``k = 6`` is a padded copy on
    the chip."""
    with jax.named_scope("row_index"):
        tokens = order // back.shape[1]
    with jax.named_scope("rows_moved"):
        return x[tokens]


def _dispatch_fwd(x, order, back):
    return _dispatch(x, order, back), back


def _dispatch_bwd(back, g):
    with jax.named_scope("row_index"):
        slots = back.T
    with jax.named_scope("rows_moved"):
        picked = g[slots.reshape(-1)]
    return picked.reshape(slots.shape + g.shape[1:]).sum(0), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, weight, order, back):
    """``out[n] = sum_j weight[n, j] * ys[back[n, j]]`` in float32: the
    buffer's rows ``ys`` (N * k, H) gathered back and summed with the
    router's weights (N, k). Its backward runs in buffer order on ``ys``,
    the weights and the index arrays, so nothing keeps the gathered rows and
    a block's replay holds no combine. The forward is written as it was
    before PR 31, view ``(N, k, H)`` and all: written slot-major, the
    program ``forward`` compiles to and the one ``lm_loss_and_counters``
    compiles to round differently enough on the chip to choose other
    experts at 0.5 % of the positions (PERF.md section 6, PR 31)."""
    N, k = back.shape
    with jax.named_scope("rows_moved"):
        picked = ys[back.reshape(-1)]
    return jnp.einsum("nkh,nk->nh", picked.reshape(N, k, -1), weight,
                      preferred_element_type=jnp.float32)


def _combine_fwd(ys, weight, order, back):
    return _combine(ys, weight, order, back), (ys, weight, order, back)


def _combine_bwd(res, d_out):
    ys, weight, order, back = res
    with jax.named_scope("row_index"):
        tokens = order // back.shape[1]
    with jax.named_scope("rows_moved"):
        g_rows = d_out[tokens]                              # float32
    with jax.named_scope("row_index"):
        w_rows = weight.reshape(-1)[order]
    # rounded to the compute dtype once, after the multiplication
    d_ys = (g_rows * w_rows[:, None]).astype(ys.dtype)
    dots = jnp.einsum("rh,rh->r", g_rows, ys,
                      preferred_element_type=jnp.float32)
    with jax.named_scope("row_index"):
        return d_ys, dots[back], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _tile(size: int, most: int) -> int:
    """The largest divisor of ``size`` that is at most ``most``."""
    return next(t for t in range(min(size, most), 0, -1) if size % t == 0)


def _tiling(rows: int, inner: int, outer: int):
    return (_tile(rows, _GMM_ROWS), _tile(inner, _GMM_COLS),
            _tile(outer, _GMM_COLS))


@jax.custom_vjp
def _grouped_matmul(xs, w, sizes):
    """``xs[rows of group g] @ w[g]`` for the ``len(w)`` held experts
    (megablox ``gmm``). ``sizes`` has one more entry than ``w`` has experts:
    the rows of no held expert, which come last and read zeros. The kernel's
    grid visits only the row tiles that belong to a held expert."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    with jax.named_scope("gmm"):
        return gmm(
            xs, w, sizes, xs.dtype, _tiling(xs.shape[0], *w.shape[1:]),
            interpret=jax.default_backend() != "tpu")


def _grouped_matmul_fwd(xs, w, sizes):
    return _grouped_matmul(xs, w, sizes), (xs, w, sizes)


def _grouped_matmul_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    xs, w, sizes = res
    rows, (held, inner, outer) = xs.shape[0], w.shape
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope("gmm"):
        dxs = gmm(g, w, sizes, xs.dtype, _tiling(rows, outer, inner),
                  transpose_rhs=True, interpret=interpret)
    lhs = xs.swapaxes(0, 1)         # the transpose ``tgmm`` wants: no product
    with jax.named_scope("gmm"):
        dw = tgmm(lhs, g, sizes, w.dtype, _tiling(rows, inner, outer),
                  num_actual_groups=held, interpret=interpret)
    return dxs, dw, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _grouped_ffn(xs, experts, sizes, act=jax.nn.relu):
    """The held experts' gated body over rows sorted by expert: three
    grouped products with ``act`` on the gate (ReGLU here, SwiGLU where
    ``conv_decoder`` gives silu)."""
    gate, up, down = (experts[n].astype(xs.dtype)
                      for n in ("gate", "up", "down"))
    h = act(_grouped_matmul(xs, gate, sizes)) \
        * _grouped_matmul(xs, up, sizes)
    return _grouped_matmul(h, down, sizes)


def _rung(tokens: int, k: int, count: int, total: int) -> Optional[int]:
    """Rows of the small buffer, from shapes alone, or None where the layer
    has none. A uniform router sends ``tokens * k * count / total`` rows to
    the ``count`` experts held of ``total``; the rung is the smallest
    power-of-two fraction of the worst case, ``tokens * min(k, count)`` rows,
    that holds twice that, and it exists only where that fraction is at most
    a quarter. A larger one is left part-way through a run as a trained
    router drifts towards the experts held (PERF.md section 6, PR 33)."""
    full = rows = tokens * min(k, count)
    while rows % 2 == 0 and rows // 2 * total >= 2 * tokens * k * count:
        rows //= 2
    return rows if 4 * rows <= full else None


def _on_rows(rows: int, ffn, m, weight, experts, order, back, sizes):
    """Dispatch, the experts' body and the combine on the buffer's first
    ``rows`` rows. Held rows sort first, so where fewer than ``rows`` are
    routed these are all of them and fill: rows of the "none" group, of
    which at least one is left, the last. A slot that is not held reads that
    row, which the grouped products leave zero in the result and in the
    cotangent, as they leave the slot's own row in the whole buffer."""
    N, slots = back.shape
    if rows < N * slots:
        with jax.named_scope("moe_dispatch"), jax.named_scope("row_index"):
            order = order[:rows]
            back = jnp.minimum(back, rows - 1)
            sizes = sizes.at[-1].set(rows - sizes[:-1].sum())
    with jax.named_scope("moe_dispatch"):
        xs = _dispatch(m, order, back)
    with jax.named_scope("experts"):
        ys = ffn(xs, experts, sizes)
    with jax.named_scope("moe_combine"):
        return _combine(ys, weight, order, back)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _tiered(ffn, rung: int, fits, m, weight, experts, order, back, sizes):
    """``_on_rows`` on ``rung`` rows where ``fits`` says that the routed rows
    fit them, else on the whole buffer: one executable holds both, and the
    count the sort has just made decides on the device. The conditional
    encloses forward AND backward of a route, each on its own (the backward
    rule keeps the layer's inputs, replays the route that ran and pulls the
    cotangent through it): differentiating through a ``lax.cond`` would
    make each branch return the union of both branches' residuals, and the
    small one would write the whole buffer's as zeros
    (``bert._head_loss``)."""
    return lax.cond(
        fits, functools.partial(_on_rows, rung, ffn),
        functools.partial(_on_rows, back.size, ffn),
        m, weight, experts, order, back, sizes)


def _tiered_fwd(ffn, rung, *inputs):
    return _tiered(ffn, rung, *inputs), inputs


def _tiered_bwd(ffn, rung, inputs, d_out):
    fits, m, weight, experts, order, back, sizes = inputs

    def pull(rows, d_out, *wrt):
        return jax.vjp(lambda *a: _on_rows(rows, ffn, *a, order, back, sizes),
                       *wrt)[1](d_out)
    grads = lax.cond(fits, functools.partial(pull, rung),
                     functools.partial(pull, back.size),
                     d_out, m, weight, experts)
    return (None, *grads, None, None, None)


_tiered.defvjp(_tiered_fwd, _tiered_bwd)


def routed_experts(m, top_e, top_w, held: Tuple[int, int], total: int, dtype,
                   ffn, experts):
    """The routed-expert layer of any family, from the choices on: rows
    ``m`` (N, width), each token's chosen experts ``top_e`` (N, k) and their
    weights ``top_w`` (N, k) as the family's router made them, the experts
    ``held`` here (offset, count) of the router's ``total``, and the
    experts' body ``ffn(xs, experts, sizes)`` on rows sorted by expert with
    the held experts' matrices ``experts``. Returns the weighted sum of the
    held experts' results (N, width) float32 and the routing counters.

    The buffer has ``N x min(k, count)`` rows, which is all that can land
    here: a token's ``k`` choices are ``k`` different experts. Where more
    are held than a token takes, a slot is one of the token's choices;
    where fewer are, a slot is one of the held experts, taken or not.

    **The rung.** Where few of the router's experts are held, most of that
    buffer belongs to no held expert, and every pass and gather pays for
    all of it. ``_rung`` gives such a layer a second, small buffer from its
    shapes alone (at the hybrid decoder's share, 8 of 512 held at 22 a
    token, 16,384 rows under 131,072; at the convolution decoder's, 8 of 64
    held at 4 a token, N rows under 4 N, with a slot one of the token's
    choices; at the routed-expert decoder's, 16 of 64 at 6, none: its
    program is the one without a rung). The layer counts
    its routed rows in the sort, and where they are fewer than the rung it
    runs on the buffer's first ``rung`` rows, forward and backward
    (``_tiered``, ``_on_rows``); where they are not, on the whole buffer.
    Both are exact, no row is dropped, nothing is set: the same rows meet
    the same weights in the same tiles. The counter ``buffer_rows`` says
    which ran: ``rung`` or ``N x min(k, count)``."""
    N, k = top_e.shape
    off, count = held
    with jax.named_scope("router"):
        local = top_e - off
        here = (local >= 0) & (local < count)
        weight = jnp.where(here, top_w, 0.0)                    # (N, k)
        slot_local, slot_here = local, here
        if count < k:
            taken = local[:, :, None] == jnp.arange(count)      # (N, k, count)
            weight = jnp.where(taken, weight[:, :, None], 0.0).sum(1)
            slot_here = taken.any(1)
            slot_local = jnp.broadcast_to(jnp.arange(count), (N, count))
    slots = slot_local.shape[1]
    rung = _rung(N, k, count, total)
    with jax.named_scope("moe_dispatch"):
        # group ``count`` is "none of the experts held here": it sorts last
        with jax.named_scope("row_index"):
            group = jnp.where(slot_here, slot_local, count).reshape(-1)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(N * slots, dtype=jnp.int32), unique_indices=True,
                mode="promise_in_bounds").reshape(N, slots)
            sizes = (group[None, :] == jnp.arange(count + 1)[:, None]).sum(
                1, dtype=jnp.int32)
        weight, order, back, sizes = map(
            checkpoint_name, (weight, order, back, sizes), _ROUTE_NAMES)
        m = m.astype(dtype)
    if rung is None:
        out = _on_rows(N * slots, ffn, m, weight, experts, order, back, sizes)
        buffer_rows = jnp.int32(N * slots)
    else:
        with jax.named_scope("moe_dispatch"), jax.named_scope("row_index"):
            # strictly: the rung keeps a row of the "none" group
            fits = sizes[:count].sum() < rung
            buffer_rows = jnp.where(fits, rung, N * slots).astype(jnp.int32)
        with jax.named_scope("experts"):
            # cast here, so that the matrices' gradient leaves the
            # conditional in the compute dtype and meets its update outside
            experts = jax.tree.map(lambda w: w.astype(dtype), experts)
        out = _tiered(ffn, rung, fits, m, weight, experts, order, back, sizes)
    counters = {"rows_per_expert": sizes[:count],
                "choices_here": sizes[:count].sum(),
                "buffer_rows": buffer_rows,
                "tokens_without_expert": N - here.any(-1).sum(),
                # each token's held experts in ascending order, -1 for a
                # choice that is held elsewhere
                "chosen": jnp.sort(jnp.where(here, top_e, -1), axis=-1)}
    return out, counters


def _experts(bp, m, r, cfg: MoEDecoderConfig):
    """The expert layer on normed activations ``m`` (N, hidden) with router
    logits ``r`` (N, experts_total): this family's router (softmax, top-k)
    and body (ReGLU) around ``routed_experts``."""
    with jax.named_scope("router"):
        top_e, top_w = _route(r, cfg)
    return routed_experts(m, top_e, top_w, cfg.experts_held,
                          cfg.experts_total, cfg.dtype, _grouped_ffn,
                          bp["experts"])


def _block(bp, x, positions, layer: int, cfg: MoEDecoderConfig):
    """One layer on the float32 residual stream x (B, T, hidden)."""
    B, T, H = x.shape
    with jax.named_scope("router"):
        # before the input norm and before attention, in float32
        r = jnp.dot(x.reshape(B * T, H), bp["router"],
                    precision=lax.Precision.HIGHEST)
    with jax.named_scope("attn_qkv"):
        a = _rmsnorm(x, bp["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = (
            (a @ bp[n].astype(cfg.dtype)).reshape(B, T, -1, cfg.head_dim)
            for n in ("q", "k", "v"))
    if cfg.rope_layout[layer]:
        with jax.named_scope("rope"):
            q, k = (_rope(t, positions, cfg.rope_theta) for t in (q, k))
    with jax.named_scope("attention"):
        o = _attention(
            *(checkpoint_name(t.transpose(0, 2, 1, 3), n)
              for t, n in zip((q, k, v), _QKV_NAMES)),
            cfg.window if cfg.window_layout[layer] else None, cfg)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    with jax.named_scope("attn_out"):
        y = x + jnp.dot(o, bp["o"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    with jax.named_scope("moe_dispatch"):
        m = _rmsnorm(y, bp["ln2"], cfg.rms_eps)
    out, counters = _experts(bp, m.reshape(B * T, H), r, cfg)
    with jax.named_scope("moe_combine"):
        return y + out.reshape(B, T, H), counters


def encode(params, token_ids, cfg: MoEDecoderConfig, positions=None):
    """Embedding, the blocks and the final norm: the float32 hidden states
    (B, T, hidden) and the routing counters, stacked over the layers."""
    from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
    if positions is None:
        positions = jnp.arange(token_ids.shape[1])
    keep = jax.checkpoint_policies.save_only_these_names(
        *FLASH_SAVED_NAMES, *_QKV_NAMES)
    with jax.default_matmul_precision("default"):
        with jax.named_scope("embed"):
            x = params["tok_emb"][token_ids]
        counters = []
        for layer, bp in enumerate(params["blocks"]):
            blk = functools.partial(_block, layer=layer, cfg=cfg)
            if cfg.remat:
                blk = jax.checkpoint(blk, policy=keep)
            x, c = blk(bp, x, positions)
            counters.append(c)
        with jax.named_scope("final_ln"):
            x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
    return x, jax.tree.map(lambda *c: jnp.stack(c), *counters)


def head_logits(params, x, cfg):
    """Compute-dtype logits of final-normed hidden states ``x``."""
    with jax.default_matmul_precision("default"), jax.named_scope("lm_head"):
        return x.astype(cfg.dtype) @ params["lm_head"].astype(cfg.dtype)


def _logits(params, token_ids, cfg, positions=None):
    """Compute-dtype logits of every position, and the counters."""
    x, counters = encode(params, token_ids, cfg, positions)
    return head_logits(params, x, cfg), counters


def _one_chip(mesh: Optional[Mesh]):
    if mesh is not None:
        raise NotImplementedError(
            "the routed-expert decoder runs one chip's share without its "
            "exchange; the sharded step (all-to-all over the 'expert' axis "
            "of param_pspecs) is not built")


def forward(params, token_ids, cfg: MoEDecoderConfig,
            mesh: Optional[Mesh] = None, positions=None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) float32."""
    _one_chip(mesh)
    return _logits(params, token_ids, cfg, positions)[0].astype(jnp.float32)


def lm_loss_and_counters(params, batch, cfg: MoEDecoderConfig,
                         mesh: Optional[Mesh] = None):
    """Weighted LM cross-entropy of ``batch`` (tokens, targets, weights;
    next-token training shifts the targets and weighs the last position 0)
    through ``bert.loss_from_logits``, and the routing counters of the
    step: per layer, the rows each held expert saw, the token-choices that
    landed here, the rows of the buffer the layer ran on (``buffer_rows``),
    the tokens none of whose experts is held, and every token's held
    experts (``chosen``, (layers, B*T, k) int32)."""
    _one_chip(mesh)
    logits, counters = _logits(params, batch["tokens"], cfg)
    return loss_from_logits(logits, batch), counters


def lm_loss(params, batch, cfg: MoEDecoderConfig,
            mesh: Optional[Mesh] = None):
    return lm_loss_and_counters(params, batch, cfg, mesh)[0]


bert.register_family(MoEDecoderConfig, types.SimpleNamespace(
    init_params=init_params, param_pspecs=param_pspecs, forward=forward,
    lm_loss=lm_loss, loss_and_aux=lm_loss_and_counters))
