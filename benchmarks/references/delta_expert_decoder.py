"""Plain reference of the gated delta-rule / gated attention decoder with
routed SwiGLU experts beside a shared expert (``model_type``
``solar_open2``): every layer is two residual steps,
``h = x + mixer(rmsnorm(x; g1))`` and ``y = h + experts(rmsnorm(h; g2))``,
the mixer a gated delta-rule linear-attention layer (KDA, arXiv:2510.26692)
or grouped-query softmax attention with an output gate; RMSNorm, no bias but
the router's selection bias, no positional encoding. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
the state recurrence **one position at a time** exactly as written below (a
``lax.scan`` over the sequence: no chunks, no cumulative sums, no triangular
system), the convolutions as explicit sums of shifted copies, a causal mask
over whole rows of scores with K and V repeated for the query groups, a loop
over the held experts with a mask, no kernel, no sort, no checkpointing and
no code of the program. It reads the program's parameter tree, because the
weights are what the two sides share: ``tok_emb``, ``ln_f``, ``lm_head`` and
``blocks[i]`` with ``ln1``, ``ln2``, ``router``, ``router_bias``,
``experts`` {gate, up, down}, ``shared`` {gate, up, down} and, by kind,
``q``, ``k``, ``v``, ``conv`` {q, k, v}, ``f_down``, ``f_up``, ``A_log``,
``dt_bias``, ``beta``, ``g_down``, ``g_up``, ``norm``, ``o``; or ``q``,
``gate``, ``k``, ``v``, ``o``.

Delta rule, with ``u = rmsnorm(x; g1)``, per head ``h``, ``d`` the head's
width, ``conv`` causal and depthwise with the taps' last row on the current
position, zeros before position 0 and no bias::

    q = l2norm(silu(conv(u W_q))),  k = l2norm(silu(conv(u W_k)))
    v = silu(conv(u W_v))                 l2norm(x) = x / sqrt(|x|^2 + 1e-6)
    g = -exp(A_log[h]) softplus((u W_fd) W_fu + dt_bias)     per channel
    beta = 2 sigmoid(u W_b)               (1 sigmoid where neg_eigval is off)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d),  S_0 = 0
    out = [rmsnorm(o_t; gamma) * sigmoid((u W_gd) W_gu)] W_o

Attention: scores ``q_h . k_(h // group) / sqrt(D)``, key ``j`` visible to
query ``i`` iff ``j <= i``; ``[concat(o_h) * sigmoid(u W_gate)] W_o``.

Experts, with ``m = rmsnorm(h; g2)``: ``s = sigmoid(m W_r)``; S = the
``experts_per_token`` largest of ``s + b``; ``w_e = routed_scale * s_e /
sum_S s``; ``sum over e in S, e held, of w_e (silu(m W1_e) * (m W3_e)) W2_e
+ (silu(m Ws1) * (m Ws3)) Ws2``.

**The share.** The parameter tree holds what one chip holds: its heads'
columns of every projection, ``experts_count`` experts (the router's experts
``experts_offset ..``), its columns of the shared expert. Only their terms
are computed; what the other chips would add is left out, as in the
program, and a tree that holds fewer experts than the router has outputs
gives the router no gradient. On an uncut tree this is the uncut model.

**Choices and margins** as in ``hybrid_ssm_expert_decoder``: per position
and layer the held experts chosen (ascending, -1 for a choice held
elsewhere) and how close the choice was, the least by which a held expert's
selection score ``s + b`` would have to move to enter or leave the chosen
set, in units of the standard deviation of that position's selection
scores. The delta-rule state carries every earlier position's choices
forward and attention reads them, so ``check`` gives, as a position's
**margin**, the smallest over the layers and over the positions up to it.

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


def _l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _conv(x, taps):
    """x (T, channels); taps (K, channels), the last on the current
    position: position t reads x at t - j through taps[K - 1 - j]."""
    T, K = x.shape[0], taps.shape[0]
    out = jnp.zeros_like(x)
    for j in range(K):
        shifted = jnp.concatenate([jnp.zeros_like(x[:j]), x[:T - j]])
        out = out + taps[K - 1 - j] * shifted
    return out


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time: ``q``, ``k``, ``v``, ``g``
    (T, heads, d), ``beta`` (T, heads); ``q`` as it meets the state (scaled).
    Returns ``o_t = S_t^T q_t`` (T, heads, d)."""
    def position(S, step):
        q_t, k_t, v_t, g_t, b_t = step
        S = jnp.exp(g_t)[:, :, None] * S                    # Diag(exp(g)) S
        held = jnp.einsum("hd,hde->he", k_t, S)             # S^T k
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - held)[:, None, :]
        return S, jnp.einsum("hde,hd->he", S, q_t)

    heads, d = q.shape[1:]
    _, o = lax.scan(position, jnp.zeros((heads, d, d), jnp.float32),
                    (q, k, v, g, beta))
    return o


def _delta_mixer(bp, u, eps, neg_eigval):
    """u (T, hidden) normed -> the mixer's output (T, hidden)."""
    T = u.shape[0]
    heads, d = bp["A_log"].shape[0], bp["norm"]["scale"].shape[0]
    q, k, v = (jax.nn.silu(_conv(u @ bp[n], bp["conv"][n])
                           ).reshape(T, heads, d) for n in "qkv")
    q, k = _l2norm(q) / math.sqrt(d), _l2norm(k)
    g = -jnp.exp(bp["A_log"])[:, None] * jax.nn.softplus(
        (u @ bp["f_down"]) @ bp["f_up"] + bp["dt_bias"]).reshape(T, heads, d)
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(u @ bp["beta"])
    o = _rmsnorm(delta_rule(q, k, v, g, beta), bp["norm"]["scale"], eps)
    gate = jax.nn.sigmoid((u @ bp["g_down"]) @ bp["g_up"])
    return (o.reshape(T, -1) * gate) @ bp["o"]


def _attend(bp, u, heads):
    """u (T, hidden) normed -> the mixer's output (T, hidden)."""
    T = u.shape[0]
    q = (u @ bp["q"]).reshape(T, heads, -1)
    D = q.shape[-1]
    k, v = ((u @ bp[n]).reshape(T, -1, D) for n in ("k", "v"))
    k, v = (jnp.repeat(t, heads // t.shape[1], axis=1) for t in (k, v))
    visible = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    out = []
    for start in range(0, T, BLOCK):
        rows = slice(start, min(T, start + BLOCK))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(D)
        s = jnp.where(visible[rows][None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out).reshape(T, -1)
    return (o * jax.nn.sigmoid(u @ bp["gate"])) @ bp["o"]


def _swiglu(m, w):
    return (jax.nn.silu(m @ w["gate"]) * (m @ w["up"])) @ w["down"]


def _experts(bp, m, per_token, offset, normalise, scale):
    """m (T, hidden) normed -> the held experts' part of the sum plus the
    shared expert's, of every position the margin, and the held experts
    chosen."""
    held = bp["experts"]["gate"].shape[0]
    s = jax.nn.sigmoid(m @ bp["router"])
    if held < bp["router"].shape[1]:    # a share does not train its router
        s = lax.stop_gradient(s)
    select = s + bp["router_bias"]
    ranked = jnp.argsort(-select, axis=-1)          # ties: lower index first
    chosen = ranked[:, :per_token]
    w = jnp.take_along_axis(s, chosen, -1)
    if normalise:
        w = w / w.sum(-1, keepdims=True)
    w = scale * w
    out = _swiglu(m, bp["shared"])
    for e in range(held):           # the absent experts' terms are left out
        w_e = jnp.where(chosen == offset + e, w, 0.0).sum(-1)
        out = out + w_e[:, None] * _swiglu(
            m, {n: bp["experts"][n][e] for n in ("gate", "up", "down")})
    edge = ranked[:, per_token - 1:per_token + 1]   # the k-th and (k+1)-th
    s_in, s_out = jnp.split(jnp.take_along_axis(select, edge, -1), 2, -1)
    here = select[:, offset:offset + held]
    to_cut = jnp.where(here >= s_in, here - s_out, s_in - here)
    margin = to_cut.min(-1) / select.std(-1)
    held_choice = (chosen >= offset) & (chosen < offset + held)
    return out, margin, jnp.sort(jnp.where(held_choice, chosen, -1), -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "neg_eigval", "per_token", "offset", "normalise",
    "scale"))
def _layer(bp, x, *, heads, eps, neg_eigval, per_token, offset, normalise,
           scale):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    u = _rmsnorm(x, bp["ln1"]["scale"], eps)
    h = x + (_delta_mixer(bp, u, eps, neg_eigval) if "conv" in bp
             else _attend(bp, u, heads))
    m = _rmsnorm(h, bp["ln2"]["scale"], eps)
    part, margin, chosen = _experts(bp, m, per_token, offset, normalise,
                                    scale)
    return h + part, margin, chosen


def layer(bp, x, sizes: dict):
    """One layer on one sequence x (T, hidden) float32, its mixer read from
    the keys of ``bp``: the layer's output, the margins (T,) and the held
    experts chosen (T, k)."""
    with jax.default_matmul_precision("highest"):
        return _layer(
            bp, x, heads=int(sizes["heads"]),
            eps=float(sizes.get("rms_eps", 1e-5)),
            neg_eigval=bool(sizes.get("neg_eigval", True)),
            per_token=int(sizes["experts_per_token"]),
            offset=int(sizes.get("experts_offset", 0)),
            normalise=bool(sizes.get("norm_topk_prob", True)),
            scale=float(sizes.get("routed_scale", 1.0)))


def hidden(params, tokens, sizes: dict):
    """Final-normed hidden states (B, T, hidden), per position the smallest
    margin over the layers and the positions up to it (B, T), and the held
    experts chosen (layers, B, T, k)."""
    T = tokens.shape[1]
    xs, margins, choices = [], [], []
    for seq in tokens:
        x = params["tok_emb"][seq].astype(jnp.float32)
        margin, chosen = jnp.full((T,), jnp.inf), []
        for bp in params["blocks"]:
            x, m, c = layer(bp, x, sizes)
            margin = jnp.minimum(margin, m)
            chosen.append(c)
        xs.append(_rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                           float(sizes.get("rms_eps", 1e-5))))
        margins.append(lax.cummin(margin))
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
    and of every position the margin (B, T; the module's docstring) and the
    held experts chosen (layers, B, T, k)."""
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
