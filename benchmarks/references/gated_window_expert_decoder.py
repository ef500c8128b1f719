"""Plain reference of the window/full-attention decoder with a per-head
output gate and routed SwiGLU experts beside a shared expert (``model_type``
``laguna``): every layer is two residual steps,
``y = x + attention(rmsnorm(x; g1))`` and ``out = y + ffn(rmsnorm(y; g2))``;
RMSNorm, no bias. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a dense causal mask over whole
rows of scores (queries in blocks, for memory) with K and V repeated for the
query groups, the rotary as cos and sin tables, a loop over the held experts
with a mask, no kernel, no sort, no checkpointing and no code of the
program. It reads the program's parameter tree, because the weights are what
the two sides share: ``tok_emb``, ``ln_f``, ``lm_head`` and ``blocks[i]``
with ``ln1``, ``q``, ``k``, ``v``, ``o``, ``gate``, ``ln2`` and either
``mlp`` {gate, up, down} (a leading dense layer) or ``router``, ``experts``
{gate, up, down} and ``shared`` {gate, up, down}.

Layer ``l``, of kind window (``window_layout[l]``) or full, with
``heads_per_layer[l]`` query heads over ``kv_heads`` key/value heads of
``head_dim``::

    a = rmsnorm(x; g1);  q, k, v = a W_q, a W_k, a W_v
    window: q, k turned over the whole head at rope_theta
    full:   the first global_rotary_fraction of the head turned at
            YaRN's frequencies (global_rope_theta, yarn_factor, the original
            length, beta_fast, beta_slow), cos and sin times the attention
            factor; the rest of the head as it is
    o_h = softmax(q_h . k_(h // group) / sqrt(D) over the visible keys) v
          key j visible to query i iff 0 <= i - j, and on a window layer
          i - j < window
    y = x + concat_h(o_h * sigmoid(a W_gate)_h) W_o
    m = rmsnorm(y; g2)
    dense (l < dense_layers): out = y + (silu(m W1) * (m W3)) W2
    else: p = softmax(m W_r); S = the experts_per_token largest;
          w_e = routed_scale p_e / sum_S p
          out = y + sum over e in S held of w_e (silu(m W1_e) * (m W3_e)) W2_e
                  + (silu(m Ws1) * (m Ws3)) Ws2

Rotary: pairs ``(i, i + n/2)`` of the ``n`` turned dimensions, by the angle
``position * f_i``. YaRN's ``f_i``, as the published ``rope_type: yarn``
computes them: ``b_i = theta^(2i/n)``; the pair that turns ``r`` times over
the original length is ``n ln(original / (2 pi r)) / (2 ln theta)``; ``low``
is that of ``beta_fast`` rounded down (at least 0), ``high`` that of
``beta_slow`` rounded up (at most n - 1); ``ramp_i = clip((i - low) / (high -
low), 0, 1)``; ``f_i = ramp_i / (factor b_i) + (1 - ramp_i) / b_i``.

**The share.** The parameter tree holds what one chip holds: its heads'
columns of ``W_q``, ``W_gate`` and rows of ``W_o``, its key/value head, its
columns of the shared expert and of the dense MLP, ``experts_count`` experts
(the router's experts ``experts_offset ..``). Only their terms are computed;
what the other chips would add is left out, as in the program, and where
``freeze_share_router`` is set a tree that holds fewer experts than the
router has outputs gives the router no gradient. On an uncut tree this is
the uncut model.

**Choices and margins** as in ``routed_expert_decoder``: per position and
expert layer the held experts chosen (ascending, -1 for a choice held
elsewhere) and the least by which a held expert's router logit would have
to move to enter or leave the chosen set, in units of the standard deviation
of that position's logits; ``check`` gives the smallest over the layers.

One sequence at a time, one layer per jitted call: the timed sizes
(T = 8192) fit beside the weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 512     # queries (attention) and rows (head) per block


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_frequencies(n, theta, factor, original, beta_fast, beta_slow):
    """The ``n / 2`` frequencies of YaRN (the module's docstring)."""
    b = theta ** (np.arange(0, n, 2) / n)

    def pair(turns):
        return n * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), n - 1)
    if high == low:
        high = high + 0.001
    ramp = np.minimum(np.maximum((np.arange(n // 2) - low) / (high - low),
                                 0.0), 1.0)
    return ramp / (factor * b) + (1.0 - ramp) / b


def _turn(x, positions, freqs, gain):
    """x (T, heads, D): the first 2 * len(freqs) dimensions turned in pairs
    (i, i + len(freqs)), cos and sin times ``gain``, the rest as they are."""
    n = 2 * freqs.shape[0]
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * gain
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * gain
    part = x[..., :n]
    half = n // 2
    rotated = jnp.concatenate([-part[..., half:], part[..., :half]], -1)
    return jnp.concatenate([part * cos + rotated * sin, x[..., n:]], -1)


def _attention(q, k, v, window):
    """q (T, heads, D); k, v (T, kv_heads, D); float32 (T, heads, D)."""
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
    return jnp.concatenate(out)


def _swiglu(m, w):
    return (jax.nn.silu(m @ w["gate"]) * (m @ w["up"])) @ w["down"]


def _experts(bp, m, per_token, offset, normalise, scale, freeze):
    """The held experts' part of the sum plus the shared expert's, of every
    position the margin, and the held experts chosen."""
    held = bp["experts"]["gate"].shape[0]
    r = m @ bp["router"]
    if freeze and held < r.shape[1]:    # a share does not train its router
        r = lax.stop_gradient(r)
    p = jax.nn.softmax(r, -1)
    ranked = jnp.argsort(-p, axis=-1)               # ties: lower index first
    chosen = ranked[:, :per_token]
    w = jnp.take_along_axis(p, chosen, -1)
    if normalise:
        w = w / w.sum(-1, keepdims=True)
    w = scale * w
    out = _swiglu(m, bp["shared"]) if "shared" in bp else jnp.zeros_like(m)
    for e in range(held):           # the absent experts' terms are left out
        w_e = jnp.where(chosen == offset + e, w, 0.0).sum(-1)
        out = out + w_e[:, None] * _swiglu(
            m, {n: bp["experts"][n][e] for n in ("gate", "up", "down")})
    edge = ranked[:, per_token - 1:per_token + 1]   # the k-th and (k+1)-th
    r_in, r_out = jnp.split(jnp.take_along_axis(r, edge, -1), 2, -1)
    here = r[:, offset:offset + held]
    to_cut = jnp.where(here >= r_in, here - r_out, r_in - here)
    margin = to_cut.min(-1) / r.std(-1)
    held_choice = (chosen >= offset) & (chosen < offset + held)
    return out, margin, jnp.sort(jnp.where(held_choice, chosen, -1), -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "turned", "eps", "per_token",
    "offset", "normalise", "scale", "freeze"))
def _layer(bp, x, positions, freqs, gain, *, heads, kv_heads, head_dim,
           window, turned, eps, per_token, offset, normalise, scale, freeze):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    T = x.shape[0]
    a = _rmsnorm(x, bp["ln1"]["scale"], eps)
    q = (a @ bp["q"]).reshape(T, heads, head_dim)
    k = (a @ bp["k"]).reshape(T, kv_heads, head_dim)
    v = (a @ bp["v"]).reshape(T, kv_heads, head_dim)
    if turned:
        q, k = _turn(q, positions, freqs, gain), _turn(k, positions, freqs,
                                                       gain)
    o = _attention(q, k, v, window)
    if "gate" in bp:
        o = o * jax.nn.sigmoid(a @ bp["gate"])[:, :, None]
    y = x + o.reshape(T, heads * head_dim) @ bp["o"]
    m = _rmsnorm(y, bp["ln2"]["scale"], eps)
    if "mlp" in bp:
        return y + _swiglu(m, bp["mlp"]), None, None
    part, margin, chosen = _experts(bp, m, per_token, offset, normalise,
                                    scale, freeze)
    return y + part, margin, chosen


def rotary(index: int, sizes: dict):
    """Layer ``index``'s rotary: (frequencies, factor on cos and sin, turned
    dimensions), or None where the layer has none."""
    if not sizes["rope_layout"][index]:
        return None
    D = int(sizes["head_dim"])
    if sizes["window_layout"][index]:
        theta, n, yarn = float(sizes["rope_theta"]), D, None
    else:
        theta = float(sizes.get("global_rope_theta") or sizes["rope_theta"])
        n = int(D * float(sizes.get("global_rotary_fraction", 1.0)))
        yarn = sizes.get("yarn_factor")
    if yarn is None:
        return 1.0 / theta ** (np.arange(0, n, 2) / n), 1.0, n
    gain = sizes.get("yarn_attention_factor")
    if gain is None:
        gain = 0.1 * math.log(float(yarn)) + 1.0
    return yarn_frequencies(
        n, theta, float(yarn), int(sizes["yarn_original_max"]),
        float(sizes.get("yarn_beta_fast", 32.0)),
        float(sizes.get("yarn_beta_slow", 1.0))), float(gain), n


def layer(bp, x, positions, index: int, sizes: dict):
    """Layer ``index`` on one sequence x (T, hidden) float32: the layer's
    output, and of an expert layer the margins (T,) and the held experts
    chosen (T, k) (None, None on a dense layer)."""
    turn = rotary(index, sizes)
    freqs, gain, turned = (np.zeros(1), 1.0, 0) if turn is None else turn
    per_layer = sizes.get("heads_per_layer")
    heads = per_layer[index] if per_layer else sizes["heads"]
    with jax.default_matmul_precision("highest"):
        return _layer(
            bp, x, positions, jnp.asarray(freqs, jnp.float32),
            jnp.float32(gain), heads=int(heads),
            kv_heads=int(sizes["kv_heads"]), head_dim=int(sizes["head_dim"]),
            window=(int(sizes["window"])
                    if sizes["window_layout"][index] else None),
            turned=int(turned), eps=float(sizes["rms_eps"]),
            per_token=int(sizes["experts_per_token"]),
            offset=int(sizes.get("experts_offset", 0)),
            normalise=bool(sizes.get("norm_topk_prob", True)),
            scale=float(sizes.get("routed_scale", 1.0)),
            freeze=bool(sizes.get("freeze_share_router", False)))


def hidden(params, tokens, sizes: dict):
    """Final-normed hidden states (B, T, hidden), per position the smallest
    margin over the expert layers (B, T), and the held experts chosen
    (expert layers, B, T, k)."""
    T = tokens.shape[1]
    positions = jnp.arange(T)
    xs, margins, choices = [], [], []
    for seq in tokens:
        x = params["tok_emb"][seq].astype(jnp.float32)
        margin, chosen = jnp.full((T,), jnp.inf), []
        for index, bp in enumerate(params["blocks"]):
            x, m, c = layer(bp, x, positions, index, sizes)
            if c is not None:
                margin = jnp.minimum(margin, m)
                chosen.append(c)
        xs.append(_rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                           float(sizes["rms_eps"])))
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
    chosen (expert layers, B, T, k)."""
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
