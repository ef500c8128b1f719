"""Plain reference of the hybrid state-space / attention / latent-expert
decoder (``model_type`` ``nemotron_h``): every layer is
``x + mixer(rmsnorm(x; g))`` with one mixer a layer by the letters of
``pattern`` (``M`` Mamba-2, ``*`` attention, ``E`` experts), RMSNorm, no bias
but the convolution's, no positional encoding. Straightforward ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``: the state
recurrence **one position at a time** (a ``lax.scan`` over the sequence, no
chunks, no cumulative sums), a dense T x T causal mask with K and V repeated
for the query groups, a loop over the held experts with a mask, no kernel,
no sort, and no code of the program. It reads the program's parameter tree,
because the weights are what the two sides share: ``tok_emb``, ``ln_f``,
``lm_head`` and ``blocks[i]`` with ``ln`` and, by kind, ``in`` {z, x, B, C,
dt}, ``conv`` and ``conv_bias`` {x, B, C}, ``dt_bias``, ``A_log``, ``D``,
``norm``, ``out``; or ``q``, ``k``, ``v``, ``o``; or ``router``,
``router_bias``, ``down``, ``up``, ``experts`` {w1, w2}, ``shared`` {w1, w2}.

``M``, with ``u = rmsnorm(x)``: ``z, X, B, C, dt = u W_z, u W_x, u W_B,
u W_C, u W_dt``; X, B and C each through a causal depthwise convolution of
``K`` taps (the last on the current position) with bias, then silu;
``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
``h_t = exp(delta_t A) h_(t-1) + delta_t X_t (x) B_t`` with head ``h`` reading
group ``h // (heads / groups)``; ``y_t = h_t C_t + D X_t``; ``y * silu(z)``
normalised over each group's channels, times ``g_norm``; ``W_out``.

``*``: scores ``q_h . k_(h // group) / sqrt(D)``, key ``j`` visible to query
``i`` iff ``j <= i``; ``concat(o_h) W_o``.

``E``: ``s = sigmoid(u W_r)``; S = the ``experts_per_token`` largest of
``s + b``; ``w_e = routed_scale * s_e / sum_S s``; ``l = u W_down``;
``(sum over e in S, e held, of w_e relu(l W1_e)^2 W2_e) W_up
+ relu(u Ws1)^2 Ws2``.

**The share.** The parameter tree holds what one chip holds: its heads'
columns of every projection, ``experts_count`` experts (the router's experts
``experts_offset ..``), its columns of the shared expert. Only their terms
are computed; what the other chips would add is left out, as in the
program, and a tree that holds fewer experts than the router has outputs
gives the router no gradient (of the held experts' terms alone it would be
a sum that says nothing of the absent ones). On an uncut tree this is the
uncut model.

**Choices and margins.** A top-k choice is a discontinuity: where a held
expert's selection score is close to the cut, two precisions pick different
experts and both are right. Per position and expert layer: the held experts
chosen (ascending, -1 for a choice held elsewhere) and how close the choice
was, the least by which a held expert's selection score ``s + b`` would have
to move to enter or leave the chosen set, in units of the standard deviation
of that position's selection scores. In this family a position's stream
holds every earlier position's choices (the state-space layers carry them
forward, attention reads them), so a choice that differs at an earlier
position moves the scores of all later ones. ``check`` therefore gives, as
a position's **margin**, the closest call among all the choices that
position can see: the smallest over the expert layers **and over the
positions up to it** (a running minimum along the sequence). A comparison
may excuse a differing choice where that margin is small, and not where
every choice the position can see was clear.

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


def _conv(x, taps, bias):
    """x (T, channels); taps (K, channels), the last on the current
    position; every channel convolved with its own taps."""
    K, channels = taps.shape
    out = lax.conv_general_dilated(
        x.T[None], taps.T[:, None, :], window_strides=(1,),
        padding=[(K - 1, 0)], feature_group_count=channels)
    return out[0].T + bias


@functools.partial(jax.jit, static_argnames=("heads", "groups", "eps"))
def _mamba(bp, x, *, heads, groups, eps):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    T = x.shape[0]
    u = _rmsnorm(x, bp["ln"]["scale"], eps)
    z = u @ bp["in"]["z"]
    X, Bm, Cm = (jax.nn.silu(_conv(u @ bp["in"][n], bp["conv"][n],
                                   bp["conv_bias"][n])) for n in "xBC")
    delta = jax.nn.softplus(u @ bp["in"]["dt"] + bp["dt_bias"])   # (T, heads)
    A = -jnp.exp(bp["A_log"])
    X = X.reshape(T, heads, -1)
    per_group = heads // groups
    Bm, Cm = (jnp.repeat(t.reshape(T, groups, -1), per_group, axis=1)
              for t in (Bm, Cm))                             # (T, heads, N)

    def position(h, step):
        x_t, b_t, c_t, d_t = step
        h = jnp.exp(d_t * A)[:, None, None] * h \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    state = jnp.zeros((heads, X.shape[-1], Bm.shape[-1]), jnp.float32)
    _, y = lax.scan(position, state, (X, Bm, Cm, delta))
    y = y + bp["D"][:, None] * X
    y = (y.reshape(T, -1) * jax.nn.silu(z)).reshape(T, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + eps)
    return x + (y.reshape(T, -1) * bp["norm"]["scale"]) @ bp["out"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _attend(bp, x, *, heads, kv_heads, eps):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    T = x.shape[0]
    u = _rmsnorm(x, bp["ln"]["scale"], eps)
    q = (u @ bp["q"]).reshape(T, heads, -1)
    k, v = ((u @ bp[n]).reshape(T, kv_heads, -1) for n in ("k", "v"))
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    visible = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    out = []
    for start in range(0, T, BLOCK):
        rows = slice(start, min(T, start + BLOCK))
        s = jnp.einsum("qhd,khd->hqk", q[rows], k) / math.sqrt(q.shape[-1])
        s = jnp.where(visible[rows][None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return x + jnp.concatenate(out).reshape(T, -1) @ bp["o"]


@functools.partial(jax.jit, static_argnames=(
    "per_token", "offset", "normalise", "scale", "eps"))
def _experts(bp, x, *, per_token, offset, normalise, scale, eps):
    """The layer's output, and of every position the margin and the held
    experts chosen."""
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    held = bp["experts"]["w1"].shape[0]
    u = _rmsnorm(x, bp["ln"]["scale"], eps)
    s = jax.nn.sigmoid(u @ bp["router"])
    if held < bp["router"].shape[1]:    # a share does not train its router
        s = lax.stop_gradient(s)
    select = s + bp["router_bias"]
    ranked = jnp.argsort(-select, axis=-1)          # ties: lower index first
    chosen = ranked[:, :per_token]
    w = jnp.take_along_axis(s, chosen, -1)
    if normalise:
        w = w / w.sum(-1, keepdims=True)
    w = scale * w
    latent = u @ bp["down"]
    routed = jnp.zeros_like(latent)
    for e in range(held):           # the absent experts' terms are left out
        w_e = jnp.where(chosen == offset + e, w, 0.0).sum(-1)
        f = jnp.square(jax.nn.relu(latent @ bp["experts"]["w1"][e])) \
            @ bp["experts"]["w2"][e]
        routed = routed + w_e[:, None] * f
    shared = jnp.square(jax.nn.relu(u @ bp["shared"]["w1"])) \
        @ bp["shared"]["w2"]
    edge = ranked[:, per_token - 1:per_token + 1]   # the k-th and (k+1)-th
    s_in, s_out = jnp.split(jnp.take_along_axis(select, edge, -1), 2, -1)
    here = select[:, offset:offset + held]
    to_cut = jnp.where(here >= s_in, here - s_out, s_in - here)
    margin = to_cut.min(-1) / select.std(-1)
    held_choice = (chosen >= offset) & (chosen < offset + held)
    return (x + routed @ bp["up"] + shared, margin,
            jnp.sort(jnp.where(held_choice, chosen, -1), -1))


def layer(bp, x, kind: str, sizes: dict):
    """One layer of kind ``M``, ``*`` or ``E`` on one sequence x (T, hidden)
    float32: the layer's output and, for ``E``, the margins (T,) and the
    held experts chosen (T, k) (else None, None)."""
    eps = float(sizes.get("rms_eps", 1e-5))
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            return _mamba(bp, x, heads=int(sizes["mamba_heads"]),
                          groups=int(sizes["mamba_groups"]), eps=eps), \
                None, None
        if kind == "*":
            return _attend(bp, x, heads=int(sizes["heads"]),
                           kv_heads=int(sizes["kv_heads"]), eps=eps), \
                None, None
        return _experts(
            bp, x, per_token=int(sizes["experts_per_token"]),
            offset=int(sizes.get("experts_offset", 0)),
            normalise=bool(sizes.get("norm_topk_prob", True)),
            scale=float(sizes.get("routed_scale", 5.0)), eps=eps)


def hidden(params, tokens, sizes: dict):
    """Final-normed hidden states (B, T, hidden), per position the smallest
    margin over the expert layers and the positions up to it (B, T), and
    the held experts chosen (expert layers, B, T, k)."""
    T = tokens.shape[1]
    kinds = sizes["pattern"][:int(sizes["layers"])]
    xs, margins, choices = [], [], []
    for seq in tokens:
        x = params["tok_emb"][seq].astype(jnp.float32)
        margin, chosen = jnp.full((T,), jnp.inf), []
        for kind, bp in zip(kinds, params["blocks"]):
            x, m, c = layer(bp, x, kind, sizes)
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
