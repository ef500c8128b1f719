"""Plain reference of the causal decoder with routed experts: RMSNorm, no
biases, grouped-query attention, rotary positions or none and a sliding
window or none by layer, and a layer of gated (ReGLU) experts whose router
reads the layer's input before the input norm and before attention.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a loop over all the router's
experts with a mask, a dense T x T mask for the causal band, K and V
repeated for the query groups, no kernel, no sort, and no code of the
program. It reads the program's parameter tree (``tok_emb``, ``blocks[i]``
with ``ln1``, ``q``, ``k``, ``v``, ``o``, ``ln2``, ``router``, ``experts``
{``gate``, ``up``, ``down``}, ``ln_f``, ``lm_head``), because the weights are
what the two sides share.

For layer ``l`` with input ``x``: ``r = x W_r``; ``a = rmsnorm(x)``; q, k, v
from ``a``; rotate-half RoPE on q and k where ``rope_layout[l]``; causal
scores ``q_h . k_{h // group} / sqrt(D)``, key ``j`` visible to query ``i``
iff ``0 <= i - j`` and, where ``window_layout[l]``, ``i - j < window``;
``y = x + concat(o_h) W_o``; ``m = rmsnorm(y)``; ``p = softmax(r)``; S = the
``experts_per_token`` largest; ``w_e = p_e / sum_S p``; ``out = y + sum over
e in S of w_e (relu(m W_g,e) * (m W_u,e)) W_d,e``.

**The share.** The parameter tree holds ``experts_count`` experts, which are
the router's experts ``experts_offset ..``: only their terms are summed, and
what the absent experts would add is left out, as in the program. With
``experts_count == experts_total`` this is the uncut layer.

**Choices and margins.** A top-k choice is a discontinuity: where the k-th
and the (k+1)-th router logit are close, two precisions pick different
experts and both are right. ``layer`` reports, per position, the held
experts it chose (ascending, -1 for a choice held elsewhere) and the
margin: the least by which a held expert's router logit would have to move
to enter or leave the chosen set (a chosen one down to ``r_(k+1)``, another
up to ``r_(k)``), in units of the spread of that position's router logits
(their standard deviation over the experts). Swapping two absent experts
changes no term, so they set no margin. ``check`` gives the choices of
every layer and the smallest margin over the layers, so that a comparison can excuse a differing choice where
the reference says it was close, leave such a position out of its logit
check, and only out of that.

One sequence at a time, queries in blocks, one layer per jitted call: the
timed sizes (T = 8192) fit beside the weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

BLOCK = 512     # queries (attention) and rows (head) per block


def _rmsnorm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, positions, theta):
    """x (T, heads, D): pairs (i, i + D/2) rotated by positions * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                          / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], -1)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    return x * cos + rotated * sin


def _attention(q, k, v, window):
    """q (T, heads, D); k, v (T, kv_heads, D); float32 (T, heads * D)."""
    T, heads, D = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    out = []
    for start in range(0, T, BLOCK):
        rows = slice(start, min(T, start + BLOCK))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(D)
        s = jnp.where(visible[rows][None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out).reshape(T, heads * D)


def _experts(m, r, experts, per_token, offset, normalise):
    """The held experts' part of the layer's sum, and the margins."""
    total = r.shape[-1]
    held = experts["gate"].shape[0]
    p = jax.nn.softmax(r, -1)
    ranked = jnp.argsort(-p, axis=-1)               # ties: lower index first
    chosen = ranked[:, :per_token]
    p_chosen = jnp.take_along_axis(p, chosen, -1)
    if normalise:
        p_chosen = p_chosen / p_chosen.sum(-1, keepdims=True)
    out = jnp.zeros_like(m)
    for e in range(total):
        if not offset <= e < offset + held:
            continue                # an absent expert: its term is left out
        w = jnp.where(chosen == e, p_chosen, 0.0).sum(-1)
        ep = {n: experts[n][e - offset] for n in ("gate", "up", "down")}
        f = (jax.nn.relu(m @ ep["gate"]) * (m @ ep["up"])) @ ep["down"]
        out = out + w[:, None] * f
    edge = ranked[:, per_token - 1:per_token + 1]   # the k-th and (k+1)-th
    r_in, r_out = jnp.split(jnp.take_along_axis(r, edge, -1), 2, -1)
    here = r[:, offset:offset + held]
    to_cut = jnp.where(here >= r_in, here - r_out, r_in - here)
    margin = to_cut.min(-1) / r.std(-1)
    held_choice = (chosen >= offset) & (chosen < offset + held)
    return out, margin, jnp.sort(jnp.where(held_choice, chosen, -1), -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "rope", "theta", "eps",
    "per_token", "offset", "normalise"))
def _layer(bp, x, positions, *, heads, kv_heads, head_dim, window, rope,
           theta, eps, per_token, offset, normalise):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    T = x.shape[0]
    r = x @ bp["router"]
    a = _rmsnorm(x, bp["ln1"], eps)
    q = (a @ bp["q"]).reshape(T, heads, head_dim)
    k = (a @ bp["k"]).reshape(T, kv_heads, head_dim)
    v = (a @ bp["v"]).reshape(T, kv_heads, head_dim)
    if rope:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    y = x + _attention(q, k, v, window) @ bp["o"]
    m = _rmsnorm(y, bp["ln2"], eps)
    part, margin, chosen = _experts(m, r, bp["experts"], per_token, offset,
                                    normalise)
    return y + part, y, margin, chosen


def layer(bp, x, positions, index: int, sizes: dict):
    """Layer ``index`` on one sequence x (T, hidden) float32: the layer's
    output, the stream after attention (``y``), the margins (T,) and the
    held experts chosen (T, k)."""
    with jax.default_matmul_precision("highest"):
        return _layer(
            bp, x, positions, heads=int(sizes["heads"]),
            kv_heads=int(sizes["kv_heads"]), head_dim=int(sizes["head_dim"]),
            window=(int(sizes["window"])
                    if sizes["window_layout"][index] else None),
            rope=bool(sizes["rope_layout"][index]),
            theta=float(sizes["rope_theta"]), eps=float(sizes["rms_eps"]),
            per_token=int(sizes["experts_per_token"]),
            offset=int(sizes.get("experts_offset", 0)),
            normalise=bool(sizes.get("norm_topk_prob", True)))


def hidden(params, tokens, sizes: dict, positions=None):
    """Final-normed hidden states (B, T, hidden), per position the smallest
    margin over the layers (B, T), and the held experts chosen
    (layers, B, T, k)."""
    T = tokens.shape[1]
    if positions is None:
        positions = jnp.arange(T)
    xs, margins, choices = [], [], []
    for seq in tokens:
        x = params["tok_emb"][seq].astype(jnp.float32)
        margin, chosen = jnp.full((T,), jnp.inf), []
        for index, bp in enumerate(params["blocks"]):
            x, _, m, c = layer(bp, x, positions, index, sizes)
            margin = jnp.minimum(margin, m)
            chosen.append(c)
        xs.append(_rmsnorm(x, params["ln_f"], float(sizes["rms_eps"])))
        margins.append(margin)
        choices.append(jnp.stack(chosen))
    return jnp.stack(xs), jnp.stack(margins), jnp.stack(choices, axis=1)


@jax.jit
def _nll(x, lm_head, targets):
    """Per-position negative log-likelihood of x (T, hidden), by blocks."""
    head = lm_head.astype(jnp.float32)
    out = []
    for start in range(0, x.shape[0], BLOCK):
        rows = slice(start, min(x.shape[0], start + BLOCK))
        logp = jax.nn.log_softmax(x[rows] @ head, -1)
        out.append(-jnp.take_along_axis(
            logp, targets[rows][:, None], -1)[:, 0])
    return jnp.concatenate(out)


def check(params, batch, at, sizes: dict):
    """One forward pass for everything a comparison needs: the weighted
    cross-entropy of ``batch`` (tokens, targets, weights) over all its
    positions, the float32 logits (B, K, vocab) at positions ``at`` (B, K),
    and of every position the smallest margin (B, T) and the held experts
    chosen (layers, B, T, k)."""
    with jax.default_matmul_precision("highest"):
        x, margin, chosen = hidden(params, batch["tokens"], sizes)
        nll = jnp.stack([_nll(xb, params["lm_head"], tb)
                         for xb, tb in zip(x, batch["targets"])])
        w = batch["weights"]
        rows = jnp.take_along_axis(x, at[:, :, None], axis=1)
        return {"loss": (nll * w).sum() / jnp.maximum(w.sum(), 1.0),
                "logits": rows @ params["lm_head"].astype(jnp.float32),
                "margin": margin, "chosen": chosen}


def logits_at(params, tokens, at, sizes: dict):
    """Float32 logits (B, K, vocab) at positions ``at`` (B, K)."""
    zeros = jnp.zeros(tokens.shape, jnp.float32)
    return check(params, {"tokens": tokens, "targets": tokens,
                          "weights": zeros}, at, sizes)["logits"]


def loss(params, batch, sizes: dict):
    """Weighted cross-entropy of the plain model on ``batch``, float32."""
    at = jnp.zeros((batch["tokens"].shape[0], 1), jnp.int32)
    return check(params, batch, at, sizes)["loss"]
