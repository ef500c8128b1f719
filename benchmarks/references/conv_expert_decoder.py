"""Plain reference of the gated short-convolution / attention decoder with a
leading dense layer and routed SwiGLU experts (``model_type`` ``lfm2_moe``):
every layer is two residual steps, ``h = x + mixer(rmsnorm(x; g_op))`` and
``y = h + ffn(rmsnorm(h; g_ffn))``, the mixer by ``mixers`` (``conv`` or
``full_attention``), the feed-forward part dense in the first
``dense_layers`` layers and routed in the others; RMSNorm, no bias anywhere.
Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the convolution as an explicit
sum of shifted copies, a causal mask over whole rows of scores with K and V
repeated for the query groups, a loop over the held experts with a mask, no
kernel, no sort, no checkpointing and no code of the program. It reads the
program's parameter tree, because the weights are what the two sides share:
``tok_emb``, ``ln_f``, ``lm_head`` and ``blocks[i]`` with ``ln_op``,
``ln_ffn`` and, by kind, ``in``, ``conv``, ``out``; or ``q``, ``k``, ``v``,
``o``, ``q_norm``, ``k_norm``; and ``mlp`` {gate, up, down}; or ``router``,
``router_bias``, ``experts`` {gate, up, down}.

``conv``, with ``u = rmsnorm(x)``: ``[B | C | X] = u W_in`` (three equal
chunks in that order); ``Z = B * X``; ``V_t = sum_j w_(K-1-j) Z_(t-j)`` for
``j = 0 .. K-1`` with zeros before the first position (the taps' last row is
on the current position), no bias, no activation; ``(C * V) W_out``.

``full_attention``: ``q, k, v = u W_q, u W_k, u W_v``; q and k normalised per
head over the head's dimensions (``g_q``, ``g_k``), then rotated (rotate-half
over the whole head, ``rope_theta``); scores ``q_h . k_(h // group) /
sqrt(D)``, key ``j`` visible to query ``i`` iff ``j <= i``;
``concat(o_h) W_o``.

Dense: ``(silu(m W_gate) * (m W_up)) W_down``. Routed:
``s = sigmoid(m W_r)``; S = the ``experts_per_token`` largest of ``s + b``;
``w_e = routed_scale * s_e / (sum_S s + 1e-6)``; ``sum over e in S, e held,
of w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e``.

**The share.** The parameter tree holds ``experts_count`` experts, the
router's experts ``experts_offset ..``: only their terms are summed, what
the absent experts would add is left out, as in the program, and a tree that
holds fewer experts than the router has outputs gives the router no gradient
(of the held experts' terms alone it would be a sum that says nothing of the
absent ones). On an uncut tree this is the uncut model.

**Choices and margins.** A top-k choice is a discontinuity: where a held
expert's selection score is close to the cut, two precisions pick different
experts and both are right. Per position and expert layer: the held experts
chosen (ascending, -1 for a choice held elsewhere) and how close the choice
was, the least by which a held expert's selection score ``s + b`` would have
to move to enter or leave the chosen set, in units of the standard deviation
of that position's selection scores. A position's stream holds earlier
positions' choices: the convolution reads the two positions before it and
attention every earlier one. ``check`` therefore gives, as a position's
**margin**, the closest call among all the choices that position can see:
the smallest over the expert layers and over the positions up to it (a
running minimum along the sequence).

One sequence at a time, one layer per jitted call, attention's queries and
the head's rows in blocks: the timed sizes (T = 8192) fit beside the
weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 512     # queries (attention) and rows (head) per block


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _conv_mixer(bp, u):
    """u (T, hidden) -> the mixer's output (T, hidden)."""
    T, K = u.shape[0], bp["conv"].shape[0]
    b, c, x = jnp.split(u @ bp["in"], 3, axis=-1)
    z = b * x
    v = jnp.zeros_like(z)
    for j in range(K):          # position t reads z at t - j, zeros before 0
        shifted = jnp.concatenate([jnp.zeros_like(z[:j]), z[:T - j]])
        v = v + bp["conv"][K - 1 - j] * shifted
    return (c * v) @ bp["out"]


def _rope(x, theta):
    """x (T, heads, D): pairs (i, i + D/2) rotated by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(bp, u, heads, theta, eps):
    """u (T, hidden) -> the mixer's output (T, hidden)."""
    T, D = u.shape[0], bp["q_norm"]["scale"].shape[0]
    q = (u @ bp["q"]).reshape(T, heads, D)
    k, v = ((u @ bp[n]).reshape(T, -1, D) for n in ("k", "v"))
    q = _rope(_rmsnorm(q, bp["q_norm"]["scale"], eps), theta)
    k = _rope(_rmsnorm(k, bp["k_norm"]["scale"], eps), theta)
    k, v = (jnp.repeat(t, heads // t.shape[1], axis=1) for t in (k, v))
    visible = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    out = []
    for start in range(0, T, BLOCK):
        rows = slice(start, min(T, start + BLOCK))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(D)
        s = jnp.where(visible[rows][None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out).reshape(T, -1) @ bp["o"]


def _dense(bp, m):
    mlp = bp["mlp"]
    return (jax.nn.silu(m @ mlp["gate"]) * (m @ mlp["up"])) @ mlp["down"]


def _experts(bp, m, per_token, offset, normalise, scale):
    """m (T, hidden) normed -> the held experts' part of the sum, of every
    position the margin, and the held experts chosen."""
    held = bp["experts"]["gate"].shape[0]
    s = jax.nn.sigmoid(m @ bp["router"])
    if held < bp["router"].shape[1]:    # a share does not train its router
        s = lax.stop_gradient(s)
    select = s + bp["router_bias"]
    ranked = jnp.argsort(-select, axis=-1)          # ties: lower index first
    chosen = ranked[:, :per_token]
    w = jnp.take_along_axis(s, chosen, -1)
    if normalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    w = scale * w
    out = jnp.zeros_like(m)
    for e in range(held):           # the absent experts' terms are left out
        w_e = jnp.where(chosen == offset + e, w, 0.0).sum(-1)
        ep = {n: bp["experts"][n][e] for n in ("gate", "up", "down")}
        f = (jax.nn.silu(m @ ep["gate"]) * (m @ ep["up"])) @ ep["down"]
        out = out + w_e[:, None] * f
    edge = ranked[:, per_token - 1:per_token + 1]   # the k-th and (k+1)-th
    s_in, s_out = jnp.split(jnp.take_along_axis(select, edge, -1), 2, -1)
    here = select[:, offset:offset + held]
    to_cut = jnp.where(here >= s_in, here - s_out, s_in - here)
    margin = to_cut.min(-1) / select.std(-1)
    held_choice = (chosen >= offset) & (chosen < offset + held)
    return out, margin, jnp.sort(jnp.where(held_choice, chosen, -1), -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "theta", "eps", "per_token", "offset", "normalise", "scale"))
def _layer(bp, x, *, heads, theta, eps, per_token, offset, normalise, scale):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    u = _rmsnorm(x, bp["ln_op"]["scale"], eps)
    h = x + (_conv_mixer(bp, u) if "conv" in bp
             else _attend(bp, u, heads, theta, eps))
    m = _rmsnorm(h, bp["ln_ffn"]["scale"], eps)
    if "mlp" in bp:
        return h + _dense(bp, m), None, None
    part, margin, chosen = _experts(bp, m, per_token, offset, normalise,
                                    scale)
    return h + part, margin, chosen


def layer(bp, x, sizes: dict):
    """One layer on one sequence x (T, hidden) float32, its kinds read from
    the keys of ``bp``: the layer's output and, for a routed layer, the
    margins (T,) and the held experts chosen (T, k) (else None, None)."""
    with jax.default_matmul_precision("highest"):
        return _layer(
            bp, x, heads=int(sizes["heads"]),
            theta=float(sizes.get("rope_theta", 1e6)),
            eps=float(sizes.get("rms_eps", 1e-5)),
            per_token=int(sizes["experts_per_token"]),
            offset=int(sizes.get("experts_offset", 0)),
            normalise=bool(sizes.get("norm_topk_prob", True)),
            scale=float(sizes.get("routed_scale", 1.0)))


def hidden(params, tokens, sizes: dict):
    """Final-normed hidden states (B, T, hidden), per position the smallest
    margin over the expert layers and the positions up to it (B, T), and
    the held experts chosen (expert layers, B, T, k)."""
    T = tokens.shape[1]
    xs, margins, choices = [], [], []
    for seq in tokens:
        x = params["tok_emb"][seq].astype(jnp.float32)
        margin, chosen = jnp.full((T,), jnp.inf), []
        for bp in params["blocks"]:
            x, m, c = layer(bp, x, sizes)
            if c is not None:
                margin = jnp.minimum(margin, m)
                chosen.append(c)
        xs.append(_rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                           float(sizes.get("rms_eps", 1e-5))))
        margins.append(lax.cummin(margin))
        choices.append(jnp.stack(chosen) if chosen
                       else jnp.zeros((0, T, 1), jnp.int32))
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
    and of every position the margin (B, T; the module's docstring) and the
    held experts chosen (expert layers, B, T, k)."""
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
