"""Sequence / context parallelism — ring attention and Ulysses-style
all-to-all attention.

No reference equivalent exists (SURVEY.md §5.7: the reference predates context
parallelism; long sequences get truncated-BPTT only). This is the TPU-native
*extension* the rebuild treats as first-class: attention over sequences sharded
across a ``context`` mesh axis, K/V blocks rotating over ICI via ppermute with
online-softmax accumulation (ring attention), or head-resharding via all_to_all
(Ulysses). Both compose with data/tensor parallelism through shard_map.

Public entry points:
- ``ring_flash_attention(q, k, v, axis_name, causal)`` — the default ring:
  per-pair streamed Pallas kernels + second-ring-pass backward,
  O(T_local) memory both directions; call inside shard_map
- ``ring_attention(q, k, v, axis_name, causal)``     — einsum reference ring
  (any-order differentiable; backward saves rotated k/v copies)
- ``ulysses_attention(q, k, v, axis_name, causal)``  — all-to-all head
  resharding; local full-T attention routes through the streamed kernel
- ``zigzag_ring_flash_attention`` / ``zigzag_ring_self_attention`` —
  load-BALANCED causal ring (zigzag chunk layout: constant per-device
  work where the plain causal ring leaves early devices idle)
- ``ring_self_attention(mesh, q, k, v, ...)``        — whole-array convenience
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import CONTEXT_AXIS


def _block_attn_update(q, k, v, m, l, o, scale, mask=None):
    """One online-softmax block update (flash-attention accumulation).
    q: (B,H,Tq,D), k/v: (B,H,Tk,D); m/l: (B,H,Tq,1); o: (B,H,Tq,D)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard: fully-masked block rows produce -inf max -> exp(nan); clamp
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    o_new = alpha * o + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str = CONTEXT_AXIS, causal: bool = False):
    """Ring attention over a sharded sequence axis. Call INSIDE shard_map with
    q,k,v local blocks of shape (B, H, T_local, D); the global sequence is
    axis_size * T_local. K/V blocks rotate around the ring (ppermute over ICI)
    while each device accumulates its queries' attention online — O(T_local)
    memory per device, exact full-attention result."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=q.dtype))

    q_pos = my_idx * T + jnp.arange(T)

    def body(i, carry):
        o, l, m, k_blk, v_blk = carry
        kv_idx = (my_idx - i) % axis_size  # block currently held
        if causal:
            k_pos = kv_idx * T + jnp.arange(T)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None, :, :]
        else:
            mask = None
        m, l, o = _block_attn_update(q, k_blk, v_blk, m, l, o, scale, mask)
        perm = _ring_perm(axis_size)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return o, l, m, k_blk, v_blk

    o0 = jnp.zeros_like(q)
    l0 = jnp.zeros((B, H, T, 1), dtype=q.dtype)
    m0 = jnp.full((B, H, T, 1), -jnp.inf, dtype=q.dtype)
    o, l, m, _, _ = lax.fori_loop(0, axis_size, body, (o0, l0, m0, k, v))
    return o / jnp.maximum(l, 1e-30)


def ulysses_attention(q, k, v, axis_name: str = CONTEXT_AXIS,
                      causal: bool = False, use_kernel: Optional[bool] = None):
    """All-to-all ("Ulysses") sequence parallelism: reshard from
    sequence-sharded to head-sharded via all_to_all, run full attention on the
    complete sequence for the local head subset, reshard back. Requires
    num_heads % axis_size == 0. Call INSIDE shard_map with (B, H, T_local, D).

    ``use_kernel``: the local full-T attention is a per-device computation,
    so it routes through the streamed Pallas flash kernel (scores stay in
    VMEM instead of a (B, H_local, T, T) HBM tensor at GLOBAL T) when the
    resolved block fits the kernel envelope. None = auto (kernel on TPU,
    einsum elsewhere/in tests that need exact einsum semantics); False
    pins einsum; True forces the kernel in interpret mode off-TPU.
    ``flash_attention`` itself honors ``higher_order_attention()``."""
    axis_size = lax.psum(1, axis_name)
    # (B,H,T_local,D) -> gather seq, scatter heads -> (B,H_local,T,D)
    q = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    D = q.shape[-1]
    T = q.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    from deeplearning4j_tpu.ops.pallas_kernels import (flash_attention,
                                                       flash_envelope_ok)
    fits = flash_envelope_ok(T)
    if use_kernel and not fits:
        raise ValueError(
            f"ulysses_attention: use_kernel=True but global T={T} is "
            "outside the streamed kernel's block envelope; pad the "
            "sequence or drop to use_kernel=None/False")
    if use_kernel is None:
        use_kernel = on_tpu and fits
    if use_kernel:
        o = flash_attention(q, k, v, causal, None, None, None, not on_tpu)
        return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, dtype=q.dtype))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    # back: gather heads, scatter seq
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1, tiled=True)


# ------------------------------------------------- Pallas-backed ring
#
# ring_attention above is the einsum reference: exact, any-shape, but each
# ring step materializes a (T_local, T_local) score tensor in HBM, and
# reverse-mode through its scan saves every ROTATED k/v copy — backward
# memory is O(T_global) per device, quietly defeating the ring's purpose.
# ring_flash_attention replaces both: the per-pair block attention is the
# streamed Pallas flash kernel (scores stay in VMEM), and a custom VJP
# runs the backward as a SECOND ring pass (dk/dv partial sums rotate with
# their k/v blocks; p is rebuilt from the saved global logsumexp), so both
# directions are O(T_local) memory per device. Per-pair kernels are the
# same _launch_bwd_dq/_launch_bwd_dkv the single-device backward uses for
# a head past its fused kernel's VMEM (pallas_kernels.fused_bwd_fits).


def _merge_partial(o, lse, o_b, lse_b):
    """Combine two normalized attention partials (o, lse) -> (o, lse).
    All fp32; lse shaped (BH, 1, T), o shaped (BH, T, D)."""
    m = jnp.maximum(lse, lse_b)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - m_safe))
    w_b = jnp.where(jnp.isneginf(lse_b), 0.0, jnp.exp(lse_b - m_safe))
    denom = jnp.maximum(w + w_b, 1e-30)
    wT, wbT, dT = (x.transpose(0, 2, 1) for x in (w, w_b, denom))
    o_new = (o * wT + o_b * wbT) / dT
    lse_new = m_safe + jnp.log(denom)
    lse_new = jnp.where(jnp.isneginf(m), m, lse_new)
    return o_new, lse_new


def _ring_perm(axis_size):
    return [(j, (j + 1) % axis_size) for j in range(axis_size)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, interpret)
    return out


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, interpret):
    from deeplearning4j_tpu.ops.pallas_kernels import _flash_forward

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    q3 = q.reshape(B * H, T, D)

    def pair(k_blk, v_blk, pair_causal):
        o_b, lse_b = _flash_forward(
            q3, k_blk.reshape(B * H, T, D), v_blk.reshape(B * H, T, D),
            causal=pair_causal, block_q=None, block_k=None, scale=None,
            interpret=interpret)
        return o_b.astype(jnp.float32), lse_b

    # step 0 always holds the device's own (diagonal) block: causal there
    # means the standard lower-triangular mask in the local frame
    o, lse = pair(k, v, causal)
    if axis_size > 1:
        def body(i, carry):
            o, lse, k_blk, v_blk = carry
            k_blk = lax.ppermute(k_blk, axis_name, _ring_perm(axis_size))
            v_blk = lax.ppermute(v_blk, axis_name, _ring_perm(axis_size))
            kv_idx = (my_idx - i) % axis_size
            if causal:
                # kv_idx > my_idx: a strictly-future block — contributes
                # nothing; branch skips the kernel entirely (conditional
                # HLO, only the taken side executes)
                o_b, lse_b = lax.cond(
                    kv_idx < my_idx,
                    lambda ops: pair(ops[0], ops[1], False),
                    lambda ops: (jnp.zeros((B * H, T, D), jnp.float32),
                                 jnp.full((B * H, 1, T), -jnp.inf,
                                          jnp.float32)),
                    (k_blk, v_blk))
            else:
                o_b, lse_b = pair(k_blk, v_blk, False)
            o, lse = _merge_partial(o, lse, o_b, lse_b)
            return o, lse, k_blk, v_blk

        o, lse, _, _ = lax.fori_loop(1, axis_size, body, (o, lse, k, v))
    out = o.astype(q.dtype).reshape(B, H, T, D)
    return out, lse


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, interpret)
    return out, (q, k, v, out, lse)


def _pair_grads3(q3, k3, v3, do3, lse, delta, pair_causal, interpret):
    """One (q-shard, k/v-shard) pair's (dq, dk, dv) in fp32 — the shared
    building block of the ring and zigzag backward passes. Operands are
    (BH, T, D) with lse/delta (BH, 1, T) in the GLOBAL softmax frame."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _launch_bwd_dq, _launch_bwd_dkv, _resolve_flash_blocks)
    T, D = q3.shape[1], q3.shape[2]
    # route through _resolve_flash_blocks (not bare auto_flash_block) so the
    # backward tile is self-guarding: a whole-T degenerate block beyond the
    # VMEM envelope raises the actionable error instead of a Mosaic OOM
    bq, bk = _resolve_flash_blocks(T, None, None)
    sc = 1.0 / (D ** 0.5)
    dq_c = _launch_bwd_dq(q3, k3, v3, do3, lse, delta, pair_causal,
                          bq, bk, sc, interpret)
    dk_c, dv_c = _launch_bwd_dkv(q3, k3, v3, do3, lse, delta,
                                 pair_causal, bq, bk, sc, interpret)
    return (dq_c.astype(jnp.float32), dk_c.astype(jnp.float32),
            dv_c.astype(jnp.float32))


def _ring_flash_bwd_rule(axis_name, causal, interpret, res, g):
    q, k, v, out, lse = res
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    q3 = q.reshape(B * H, T, D)
    do3 = g.reshape(B * H, T, D).astype(q.dtype)
    delta = jnp.sum(do3.astype(jnp.float32)
                    * out.reshape(B * H, T, D).astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, T)

    def pair_grads(k_blk, v_blk, pair_causal):
        return _pair_grads3(q3, k_blk.reshape(B * H, T, D),
                            v_blk.reshape(B * H, T, D), do3, lse, delta,
                            pair_causal, interpret)

    # second ring pass: dk/dv partial sums ride the ring WITH their k/v
    # block; after axis_size rotations each block (and its accumulated
    # gradient) is home. dq accumulates locally.
    dq, dk, dv = pair_grads(k, v, causal)

    if axis_size > 1:
        zeros3 = jnp.zeros((B * H, T, D), jnp.float32)

        def body(i, carry):
            dq, k_blk, v_blk, dk_blk, dv_blk = carry
            k_blk = lax.ppermute(k_blk, axis_name, _ring_perm(axis_size))
            v_blk = lax.ppermute(v_blk, axis_name, _ring_perm(axis_size))
            dk_blk = lax.ppermute(dk_blk, axis_name, _ring_perm(axis_size))
            dv_blk = lax.ppermute(dv_blk, axis_name, _ring_perm(axis_size))
            kv_idx = (my_idx - i) % axis_size
            if causal:
                dq_c, dk_c, dv_c = lax.cond(
                    kv_idx < my_idx,
                    lambda ops: pair_grads(ops[0], ops[1], False),
                    lambda ops: (zeros3, zeros3, zeros3),
                    (k_blk, v_blk))
            else:
                dq_c, dk_c, dv_c = pair_grads(k_blk, v_blk, False)
            return (dq + dq_c, k_blk, v_blk, dk_blk + dk_c, dv_blk + dv_c)

        dq, _, _, dk, dv = lax.fori_loop(
            1, axis_size, body, (dq, k, v, dk, dv))
        # one more hop brings each dk/dv partial sum back to its owner
        dk = lax.ppermute(dk, axis_name, _ring_perm(axis_size))
        dv = lax.ppermute(dv, axis_name, _ring_perm(axis_size))

    shape = (B, H, T, D)
    return (dq.astype(q.dtype).reshape(shape),
            dk.astype(k.dtype).reshape(shape),
            dv.astype(v.dtype).reshape(shape))


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_attention(q, k, v, axis_name: str = CONTEXT_AXIS,
                         causal: bool = False,
                         interpret: Optional[bool] = None):
    """Ring attention whose per-pair block attention is the streamed Pallas
    flash kernel — call INSIDE shard_map with (B, H, T_local, D) shards,
    like :func:`ring_attention` (which remains the einsum reference).
    Exact full-attention result; O(T_local) memory per device in BOTH
    directions (the einsum ring's scan backward saves every rotated k/v
    copy — O(T_global)). First-order autodiff only, like the kernels it
    launches. For causal masking, strictly-future blocks skip their kernel
    launch entirely (conditional HLO), matching the einsum ring's
    all-False-mask semantics at less cost; the inherent tail-device load
    imbalance of a plain (non-zigzag) causal ring remains. Under
    :func:`deeplearning4j_tpu.ops.pallas_kernels.higher_order_attention`
    this falls back to the any-order-differentiable einsum ring, same as
    the single-device kernels fall back to their XLA reference."""
    from deeplearning4j_tpu.ops import pallas_kernels as _pk
    if _pk._HIGHER_ORDER:
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ring_flash(q, k, v, axis_name, causal, interpret)


def ring_self_attention(mesh: Mesh, q, k, v, causal: bool = False,
                        axis_name: str = CONTEXT_AXIS, impl: str = "ring"):
    """Whole-array convenience: q,k,v (B, H, T, D) with T divisible by the
    context axis size; shard_maps the chosen implementation over the mesh.
    impl: 'ring' (einsum), 'ring_flash' (Pallas per-pair kernels),
    'ulysses' (all-to-all)."""
    fn = {"ring": ring_attention, "ring_flash": ring_flash_attention,
          "ulysses": ulysses_attention}[impl]
    spec = P(None, None, axis_name, None)
    mapped = shard_map(
        functools.partial(fn, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return mapped(q, k, v)


# --------------------------------------------- zigzag (balanced) causal ring
#
# A plain causal ring is load-imbalanced: device 0's queries see one k/v
# block, device n-1's see all n — the tail device gates every step. The
# zigzag layout (as in striped/zigzag ring attention) splits the sequence
# into 2n chunks and gives device d the PAIR (chunk d, chunk 2n-1-d): its
# low stripe sees d+1 chunks, its high stripe 2n-d, so every device does
# a constant ~(2n+1) half-stripe attentions per full ring — balanced.
# Per ring step, of the four (q-stripe, kv-stripe) pairs exactly one of
# (lo, lo)/(hi, hi) is live for s != d (plus both diagonals at s == d),
# (hi, lo) is always fully visible, and (lo, hi) is always future/hidden.
# Causal-only by construction — non-causal needs no balancing; use
# ring_flash_attention.


def zigzag_indices(T: int, n: int) -> np.ndarray:
    """Gather indices putting a length-T sequence into the zigzag layout
    for an n-device context axis: device d's shard is [chunk d ; chunk
    2n-1-d] of the 2n equal chunks. Apply with x[..., idx, :]; invert
    with np.argsort(idx)."""
    if T % (2 * n):
        raise ValueError(
            f"zigzag layout needs T divisible by 2*axis_size; got T={T}, "
            f"n={n}")
    c = T // (2 * n)
    order = []
    for d in range(n):
        order.extend(range(d * c, (d + 1) * c))
        order.extend(range((2 * n - 1 - d) * c, (2 * n - d) * c))
    return np.asarray(order)


def _zz_flash_fwd_impl(q, k, v, axis_name, interpret):
    from deeplearning4j_tpu.ops.pallas_kernels import _flash_forward

    n = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    Th = Tl // 2
    BH = B * H

    def halves(x):
        x3 = x.reshape(BH, Tl, D)
        return x3[:, :Th], x3[:, Th:]

    q_lo, q_hi = halves(q)

    def vis(qs):
        def f(ops):
            o, l = _flash_forward(qs, ops[0], ops[1], causal=False,
                                  block_q=None, block_k=None, scale=None,
                                  interpret=interpret)
            return o.astype(jnp.float32), l
        return f

    def diag(qs):
        def f(ops):
            o, l = _flash_forward(qs, ops[0], ops[1], causal=True,
                                  block_q=None, block_k=None, scale=None,
                                  interpret=interpret)
            return o.astype(jnp.float32), l
        return f

    def hidden(ops):
        return (jnp.zeros((BH, Th, D), jnp.float32),
                jnp.full((BH, 1, Th), -jnp.inf, jnp.float32))

    def step(i, carry):
        o_lo, l_lo, o_hi, l_hi, k_blk, v_blk = carry
        k_lo, k_hi = halves(k_blk)
        v_lo, v_hi = halves(v_blk)
        s = (d - i) % n
        # rel: 0 hidden (s > d), 1 diagonal (s == d), 2 visible (s < d)
        rel = jnp.where(s > d, 0, jnp.where(s == d, 1, 2))
        ob, lb = lax.switch(rel, [hidden, diag(q_lo), vis(q_lo)],
                            (k_lo, v_lo))
        o_lo, l_lo = _merge_partial(o_lo, l_lo, ob, lb)
        ob, lb = lax.switch(rel, [vis(q_hi), diag(q_hi), hidden],
                            (k_hi, v_hi))
        o_hi, l_hi = _merge_partial(o_hi, l_hi, ob, lb)
        ob, lb = vis(q_hi)((k_lo, v_lo))      # always fully visible
        o_hi, l_hi = _merge_partial(o_hi, l_hi, ob, lb)
        perm = _ring_perm(n)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return o_lo, l_lo, o_hi, l_hi, k_blk, v_blk

    z = jnp.zeros((BH, Th, D), jnp.float32)
    ninf = jnp.full((BH, 1, Th), -jnp.inf, jnp.float32)
    o_lo, l_lo, o_hi, l_hi, _, _ = lax.fori_loop(
        0, n, step, (z, ninf, z, ninf, k, v))
    out = jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype) \
        .reshape(B, H, Tl, D)
    return out, (l_lo, l_hi)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _zigzag_ring(q, k, v, axis_name, interpret):
    out, _ = _zz_flash_fwd_impl(q, k, v, axis_name, interpret)
    return out


def _zz_fwd_rule(q, k, v, axis_name, interpret):
    out, (l_lo, l_hi) = _zz_flash_fwd_impl(q, k, v, axis_name, interpret)
    return out, (q, k, v, out, l_lo, l_hi)


def _zz_bwd_rule(axis_name, interpret, res, g):
    q, k, v, out, l_lo, l_hi = res
    n = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    Th = Tl // 2
    BH = B * H

    def halves(x):
        x3 = x.reshape(BH, Tl, D)
        return x3[:, :Th], x3[:, Th:]

    q_lo, q_hi = halves(q)
    do_lo, do_hi = (h.astype(q.dtype) for h in halves(g))
    out_lo, out_hi = halves(out)

    def delta_of(do_s, out_s):
        return jnp.sum(do_s.astype(jnp.float32)
                       * out_s.astype(jnp.float32),
                       axis=-1).reshape(BH, 1, Th)

    d_lo, d_hi = delta_of(do_lo, out_lo), delta_of(do_hi, out_hi)
    z3 = jnp.zeros((BH, Th, D), jnp.float32)

    def grads(qs, do_s, lse_s, del_s, pair_causal):
        def f(ops):
            return _pair_grads3(qs, ops[0], ops[1], do_s, lse_s, del_s,
                                pair_causal, interpret)
        return f

    def hidden(ops):
        return z3, z3, z3

    def step(i, carry):
        dq_lo, dq_hi, k_blk, v_blk, dk_blk, dv_blk = carry
        k_lo, k_hi = halves(k_blk)
        v_lo, v_hi = halves(v_blk)
        dk_lo, dk_hi = dk_blk[:, :Th], dk_blk[:, Th:]
        dv_lo, dv_hi = dv_blk[:, :Th], dv_blk[:, Th:]
        s = (d - i) % n
        rel = jnp.where(s > d, 0, jnp.where(s == d, 1, 2))
        a, b, c_ = lax.switch(
            rel, [hidden, grads(q_lo, do_lo, l_lo, d_lo, True),
                  grads(q_lo, do_lo, l_lo, d_lo, False)], (k_lo, v_lo))
        dq_lo, dk_lo, dv_lo = dq_lo + a, dk_lo + b, dv_lo + c_
        a, b, c_ = lax.switch(
            rel, [grads(q_hi, do_hi, l_hi, d_hi, False),
                  grads(q_hi, do_hi, l_hi, d_hi, True), hidden],
            (k_hi, v_hi))
        dq_hi, dk_hi, dv_hi = dq_hi + a, dk_hi + b, dv_hi + c_
        a, b, c_ = grads(q_hi, do_hi, l_hi, d_hi, False)((k_lo, v_lo))
        dq_hi, dk_lo, dv_lo = dq_hi + a, dk_lo + b, dv_lo + c_
        perm = _ring_perm(n)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk_blk = lax.ppermute(jnp.concatenate([dk_lo, dk_hi], axis=1),
                              axis_name, perm)
        dv_blk = lax.ppermute(jnp.concatenate([dv_lo, dv_hi], axis=1),
                              axis_name, perm)
        return dq_lo, dq_hi, k_blk, v_blk, dk_blk, dv_blk

    big_z = jnp.zeros((BH, Tl, D), jnp.float32)
    dq_lo, dq_hi, _, _, dk, dv = lax.fori_loop(
        0, n, step, (z3, z3, k, v, big_z, big_z))
    # after n process+rotate rounds each dk/dv partial sum is back home
    shape = (B, H, Tl, D)
    dq = jnp.concatenate([dq_lo, dq_hi], axis=1)
    return (dq.astype(q.dtype).reshape(shape),
            dk.astype(k.dtype).reshape(shape),
            dv.astype(v.dtype).reshape(shape))


_zigzag_ring.defvjp(_zz_fwd_rule, _zz_bwd_rule)


def zigzag_ring_flash_attention(q, k, v, axis_name: str = CONTEXT_AXIS,
                                interpret: Optional[bool] = None):
    """Load-balanced CAUSAL ring attention — call INSIDE shard_map with
    shards in the zigzag layout (:func:`zigzag_indices`; or use
    :func:`zigzag_ring_self_attention`, which handles the permutation).
    Per-pair compute is the streamed Pallas kernels with the same
    second-ring-pass backward as :func:`ring_flash_attention`; unlike the
    plain causal ring, every device does constant work per step.
    First-order autodiff only."""
    from deeplearning4j_tpu.ops import pallas_kernels as _pk
    if _pk._HIGHER_ORDER:
        raise NotImplementedError(
            "zigzag ring is first-order only; under higher_order_attention()"
            " use zigzag_ring_self_attention (which falls back to the exact"
            " reference) or the einsum ring on a contiguous layout")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _zigzag_ring(q, k, v, axis_name, interpret)


def zigzag_ring_self_attention(mesh: Mesh, q, k, v,
                               axis_name: str = CONTEXT_AXIS):
    """Whole-array convenience for the balanced causal ring: permutes the
    sequence into the zigzag layout, shard_maps, inverse-permutes the
    output. q/k/v: (B, H, T, D) with T divisible by 2 * axis size."""
    from deeplearning4j_tpu.ops import pallas_kernels as _pk
    if _pk._HIGHER_ORDER:
        # any-order-differentiable fallback that STAYS sequence-parallel:
        # the einsum ring on the contiguous layout (single-device reference
        # attention would materialize the full (T, T) scores the SP design
        # exists to avoid)
        return ring_self_attention(mesh, q, k, v, causal=True,
                                   axis_name=axis_name, impl="ring")
    n = mesh.shape[axis_name]
    T = q.shape[2]
    idx_np = zigzag_indices(T, n)
    idx = jnp.asarray(idx_np)
    inv = jnp.asarray(np.argsort(idx_np))
    spec = P(None, None, axis_name, None)
    mapped = shard_map(
        functools.partial(zigzag_ring_flash_attention, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = mapped(q[:, :, idx], k[:, :, idx], v[:, :, idx])
    return out[:, :, inv]


def reference_attention(q, k, v, causal: bool = False):
    """Single-device full attention — the numerics oracle for SP tests."""
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.asarray(D, dtype=q.dtype))
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
