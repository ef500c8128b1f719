"""Plain reference of the pre-LayerNorm transformer the three first
configurations share (GPT-2's block: LayerNorm before attention and before
the MLP, learned positions, biases, tanh-GELU, a final LayerNorm and an
untied output head). Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching tricks, and no code of the program. It reads the program's
parameter tree (``tok_emb``, ``pos_emb``, ``blocks[i]`` with ``ln1``,
``qkv``, ``attn_out``, ``ln2``, ``mlp_in``, ``mlp_out``, ``ln_f``,
``lm_head``), because the weights are what the two sides share.

One block is jitted and called layer by layer, so a deep model compiles one
small program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "causal", "eps"))
def _block(bp, x, heads, causal, eps):
    bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
    B, T, H = x.shape
    D = H // heads
    h = _layernorm(x, bp["ln1"], eps)
    qkv = h @ bp["qkv"]["kernel"] + bp["qkv"]["bias"]
    q, k, v = (t.reshape(B, T, heads, D) for t in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    if causal:
        keep = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(keep[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(B, T, H) @ bp["attn_out"]["kernel"] \
        + bp["attn_out"]["bias"]
    h = _layernorm(x, bp["ln2"], eps)
    h = _gelu_tanh(h @ bp["mlp_in"]["kernel"] + bp["mlp_in"]["bias"])
    return x + h @ bp["mlp_out"]["kernel"] + bp["mlp_out"]["bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _embed(tok_emb, pos_emb, tokens, eps):
    del eps
    T = tokens.shape[1]
    return (tok_emb[tokens] + pos_emb[:T][None]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, lm_head, x, at, eps):
    # logits only at the positions ``at`` (B, K): the full (B, T, vocab)
    # tensor is never built
    rows = jnp.take_along_axis(x, at[:, :, None], axis=1)
    return _layernorm(rows, ln_f, eps) @ lm_head.astype(jnp.float32)


def logits_at(params, tokens, at, sizes: dict):
    """Float32 logits (B, K, vocab) of the plain model at positions ``at``
    (B, K) of ``tokens`` (B, T)."""
    eps = float(sizes["layernorm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["tok_emb"], params["pos_emb"], tokens, eps)
        for bp in params["blocks"]:
            x = _block(bp, x, heads=int(sizes["heads"]),
                       causal=bool(sizes["causal"]), eps=eps)
        return _head(params["ln_f"], params["lm_head"], x, at, eps)


def loss(params, batch, sizes: dict):
    """Weighted cross-entropy of the plain model on ``batch`` (tokens,
    targets, weights), in float32."""
    B, T = batch["tokens"].shape
    at = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    logits = logits_at(params, batch["tokens"], at, sizes)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None], -1)[..., 0]
    w = batch["weights"]
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
