"""Pallas TPU kernels for attention, the memory-bound hot spot XLA cannot
fuse away (ref: the reference's libnd4j hand-written CUDA attention kernels
— SURVEY.md §2.1 'custom kernel' row; guide:
/opt/skills/guides/pallas_guide.md):

- ``flash_attention`` — blocked online-softmax attention. The (T, T) score
  matrix never materializes in HBM in EITHER direction: the forward streams
  k/v-blocks per q-block with the running max/denominator recurrence (and
  saves the per-row logsumexp); the backward rebuilds p from the saved
  logsumexp. Where a head's buffers fit the kernels' VMEM limit
  (``fused_bwd_fits``: T=8,192 at a head of 128 does, T=16,384 does not)
  it is ONE kernel, ``flash_bwd_dkv``, that walks k-blocks, streams
  q-blocks and makes dq, dk and dv from scores rebuilt once a block pair;
  a longer head, and the ring backward's shard pairs
  (parallel/sequence_parallel.py), take two passes (``flash_bwd_dq`` over
  q-blocks, ``flash_bwd_dkv`` over k-blocks), each rebuilding the scores.
  O(T) memory, causal masking supported. Under differentiation the
  forward's two results that the backward reads again, the output and the
  logsumexp, carry ``checkpoint_name`` names (``FLASH_SAVED_NAMES``): a
  ``jax.checkpoint`` whose policy is ``save_only_these_names`` of them keeps
  both, and its replay runs no forward kernel. Note: like hand-written CUDA
  attention kernels, the Pallas backward is first-order only — grad-of-grad
  through it raises; enter :func:`higher_order_attention` to route the
  public kernels to the fully-differentiable XLA reference instead.

The kernels run in interpret mode on CPU (how the test suite exercises
them) and compile natively on TPU. Use ``flash_attention(...,
interpret=True)`` off-TPU.

Measured on one TPU v5e chip (bf16, H=12, D=64): at T=512 the round-4
whole-head VMEM kernel (``mha_attention_packed`` below — fwd AND bwd Pallas,
scores never in HBM, no head transposes) beats XLA's fused attention 5.7 ms
vs 9.4 ms per layer fwd+bwd and lifts the BERT-base bench 135.4k -> 164.8k
tok/s; the streamed ``flash_attention`` recurrence here only wins at long
context (T=8192, B=2: ~48x faster than full attention, which OOMs one batch
size higher). ``attention_impl='flash'`` routes T<=1024 to the VMEM kernel
and longer T to the streamed one; under a dp/tp mesh the same kernels run
per-device via shard_map (batch over 'data', heads over 'model' — both
embarrassingly parallel, zero extra collectives; round 5). A monolithic
pallas_call over sharded operands would instead force GSPMD all-gathers,
which is why the kernels are never called on globally-sharded values
directly. Sequence-sharded ('context') meshes route to ring/Ulysses
(parallel/sequence_parallel.py), which shard longer-still sequences
across chips.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

_NEG_INF = -1e30

# what ``_tpu_params`` grants a kernel of v5e's 128 MiB of VMEM, and what the
# fused streamed backward's buffers are reckoned against
_VMEM_LIMIT_BYTES = 64 * 2 ** 20

# The ``name=`` of every ``pl.pallas_call`` in this module, in the order of
# their first call sites: the name a kernel's device-trace event carries, so
# a metric finds it after any refactor (PERF.md section 3 lists which metric
# reads which). A name may belong to two calls: ``flash_bwd_dkv`` is the
# dk/dv pass of the two-pass backward and the fused backward, which is that
# pass grown by the dq product.
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                "mha_packed_fwd", "mha_packed_bwd",
                "paged_decode_attention")

# The ``jax.ad_checkpoint.checkpoint_name`` names of the two results of
# ``flash_fwd`` that the backward reads again: the attention output (for
# delta = rowsum(dO * O), an XLA reduction before the kernel) and the
# per-row logsumexp (float32; the fused ``flash_bwd_dkv``, or
# ``flash_bwd_dq`` and ``flash_bwd_dkv`` past its envelope). A block under
# ``jax.checkpoint(..., policy=save_only_these_names(*FLASH_SAVED_NAMES))``
# keeps them, and its replay in the backward pass then holds no ``flash_fwd``.
FLASH_SAVED_NAMES = ("flash_out", "flash_lse")

# --- higher-order autodiff escape hatch -------------------------------
# The Pallas attention backwards are custom-VJP kernels: FIRST-ORDER ONLY.
# Differentiating through them again raises JAX's standard "can't apply
# forward-mode autodiff (jvp) to a custom_vjp function" error. For
# grad-of-grad experiments (Hessian-vector products, influence functions),
# enter ``higher_order_attention()``: the public kernels then route to the
# plain-XLA ``_attention_reference`` path, which is differentiable to any
# order (at the cost of materializing the (T, T) scores).
_HIGHER_ORDER = False


@jax.custom_jvp
def _first_order_only(x):
    """Identity marker baked into the kernels' saved-residual path. After
    the first (reverse-mode) differentiation inlines the custom-VJP, a
    second differentiation would otherwise reach a raw pallas_call and die
    with an inscrutable internal error (observed: ``safe_zip() argument 2 is
    longer``); this marker's JVP rule intercepts that with an error naming
    the escape hatch."""
    return x


@_first_order_only.defjvp
def _first_order_only_jvp(primals, tangents):
    raise NotImplementedError(
        "grad-of-grad through the Pallas attention kernels is unsupported — "
        "their custom-VJP backward is first-order only. Wrap the computation "
        "in deeplearning4j_tpu.ops.pallas_kernels.higher_order_attention() "
        "to route attention to the fully differentiable XLA reference "
        "implementation.")


@contextlib.contextmanager
def higher_order_attention():
    """Context manager: route ``flash_attention`` / ``mha_attention_packed``
    / ``mha_attention`` to the fully-differentiable XLA reference
    implementation so grad-of-grad works. Outside this context the Pallas
    custom-VJP kernels are used and second-order autodiff raises.

    The flag is read at TRACE time: a ``jax.jit``-compiled function bakes in
    whichever path was active when it was first traced and keeps it for the
    life of its cache entry, regardless of later enter/exit. Enter this
    context before the first call of the jitted function you want on the
    reference path, and ``jax.clear_caches()`` if you need to switch an
    already-traced function back to the Pallas kernels."""
    global _HIGHER_ORDER
    prev = _HIGHER_ORDER
    _HIGHER_ORDER = True
    try:
        yield
    finally:
        _HIGHER_ORDER = prev


def _causal_block_mask(s, q_off, k_off, window=None):
    """Mask a (BQ, BK) score block at absolute offsets (q_off, k_off): key j
    is visible to query i iff ``0 <= i - j`` and, with a ``window``,
    ``i - j < window`` (the window counts the query's own position)."""
    bq, bk = s.shape
    qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = qpos >= kpos
    if window is not None:
        keep &= qpos - kpos < window
    return jnp.where(keep, s, _NEG_INF)


def _first_k_block(q_off, block_k: int, window):
    """The first k-block a q-block at ``q_off`` sees: block 0, or the block
    that holds its first row's oldest key inside the window."""
    if window is None:
        return 0
    return jnp.maximum(q_off - (window - 1), 0) // block_k


# ------------------------------------------------------------ flash attn


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, scale: float, window=None):
    # dots take NATIVE-dtype operands (bf16 at bench) with fp32
    # accumulation, matching the packed kernel's convention. Measured
    # NEUTRAL on v5e vs the old fp32 pre-cast (round-5
    # streamed-kernel sweep: Mosaic already feeds the MXU bf16 for
    # operands upcast from bf16) — kept for consistency, not speed;
    # softmax stays fp32
    q = q_ref[0]                                      # (BQ, D)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    bq, d = q.shape
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    nkb = t // block_k

    def scores(j):
        # j is clamped by callers so the last iteration's prefetch stays
        # in-bounds (the wasted dot is one block out of t/block_k)
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        return jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def body(j, carry):
        # software-pipelined (round 5, same as the packed kernel): block
        # j's scores arrive via the carry; block j+1's QK^T dot issues
        # BEFORE this block's softmax so MXU and VPU work overlap
        m, l, acc, s = carry
        s_next = scores(jnp.minimum(j + 1, nkb - 1))
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        if causal:
            s = _causal_block_mask(s, qi * bq, j * block_k, window)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new, s_next

    # causal: blocks strictly above the diagonal contribute nothing — stop
    # the stream at the q-block's diagonal block; a window starts it at the
    # first block the band reaches
    if causal:
        upper = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, nkb)
    else:
        upper = nkb
    lower = _first_k_block(qi * bq, block_k, window)
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc, _ = jax.lax.fori_loop(lower, upper, body,
                                     (m0, l0, acc0, scores(lower)))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _merge_heads(*arrays):
    """(B, H, T, D) -> (B*H, T, D), each array with its own head count."""
    return tuple(x.reshape((-1,) + x.shape[2:]) for x in arrays)


def _kv_group(q, k, window, causal) -> int:
    """Query heads per kv head of merged (BH, T, D) / (BKV, T, D) operands:
    query head ``h`` reads kv head ``h // group``, by index map."""
    assert q.shape[0] % k.shape[0] == 0, (q.shape, k.shape)
    assert window is None or (causal and window > 0), (window, causal)
    return q.shape[0] // k.shape[0]


def _flash_forward(q, k, v, *, causal: bool, block_q: int, block_k: int,
                   scale: Optional[float], interpret: bool, window=None):
    out_shape = q.shape
    if q.ndim == 4:
        q, k, v = _merge_heads(q, k, v)
    bh, t, d = q.shape
    group = _kv_group(q, k, window, causal)
    bq, bk = _resolve_flash_blocks(t, block_q, block_k)
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    kern = functools.partial(_flash_kernel, block_k=bk, causal=causal,
                             scale=sc, window=window)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, t // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, t, d), lambda b_, i: (b_ // group, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b_, i: (b_ // group, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda b_, i: (b_, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v)
    return out.reshape(out_shape), lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool, scale: float,
                         window=None):
    """dQ pass: one q-block per grid step, stream k/v-blocks.
    ds = p * (dp - delta), dq = scale * ds @ k  with p rebuilt from the
    saved logsumexp (no (T, T) materialization). Dots run on NATIVE-dtype
    operands (measured neutral vs fp32 pre-cast — see _flash_kernel)."""
    q = q_ref[0]                                      # (BQ, D)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]                      # (BQ, 1)
    delta = delta_ref[0, 0][:, None]
    bq, d = q.shape
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    nkb = t // block_k

    def scores(j):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        return k, jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    def body(j, carry):
        dq, (k, s) = carry  # pipelined: next block's QK^T before exp; the
        #                     k tile rides the carry so it loads only once
        nxt = scores(jnp.minimum(j + 1, nkb - 1))
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        if causal:
            s = _causal_block_mask(s, qi * bq, j * block_k, window)
        p = jnp.exp(s - lse)                          # (BQ, BK), rows sum<=1
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq = dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dq, nxt

    upper = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, nkb) \
        if causal else nkb
    lower = _first_k_block(qi * bq, block_k, window)
    dq, _ = jax.lax.fori_loop(lower, upper, body,
                              (jnp.zeros((bq, d), jnp.float32),
                               scores(lower)))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_of_k_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, ki, *,
                    block_q: int, causal: bool, scale: float, window,
                    dq_acc=None):
    """dk and dv (float32) of k-block ``ki`` from one query head: stream the
    q-blocks that see it and rebuild s, the mask, p, dp and ds for each;
    dv = p^T @ do, dk = ds^T @ (scale*q). With ``dq_acc`` (the head's (T, D)
    float32 scratch) every pair also adds ds @ k into its q-block's rows.
    Dots run on NATIVE-dtype operands (measured neutral vs fp32 pre-cast —
    see _flash_kernel)."""
    k = k_ref[0]                                      # (BK, D)
    v = v_ref[0]
    bk, d = k.shape
    nqb = q_ref.shape[1] // block_q

    def scores(i):
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return qs, jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    def body(i, carry):
        dk, dv, (q, s) = carry   # pipelined: next q-block's QK^T before exp
        nxt = scores(jnp.minimum(i + 1, nqb - 1))
        rows = pl.ds(i * block_q, block_q)
        do = do_ref[0, rows, :]
        lse = lse_ref[0, 0, rows][:, None]
        delta = delta_ref[0, 0, rows][:, None]
        if causal:
            s = _causal_block_mask(s, i * block_q, ki * bk, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        if dq_acc is not None:
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return dk, dv, nxt

    # causal: q-blocks strictly before this k-block's diagonal see none of
    # it, nor do those wholly past the window of its last key
    lower = (ki * bk) // block_q if causal else 0
    upper = nqb if window is None else jnp.minimum(
        (ki * bk + bk + window - 2) // block_q + 1, nqb)
    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv, _ = jax.lax.fori_loop(lower, upper, body, (z, z, scores(lower)))
    return dk, dv


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                          causal: bool, scale: float, window=None):
    """dK/dV pass: one k-block and one query head of its kv head's group per
    grid step, stream q-blocks (:func:`_dkv_of_k_block`), summed over the
    group's query heads (the innermost grid axis) in float32 scratch."""
    dk, dv = _dkv_of_k_block(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, pl.program_id(1),
        block_q=block_q, causal=causal, scale=scale, window=window)
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _first_head():
        dk_acc[...] = dk
        dv_acc[...] = dv

    @pl.when(g > 0)
    def _next_head():
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(g == pl.num_programs(2) - 1)
    def _store():
        # dL/dk = ds^T @ (scale*q) — q was loaded pre-scaled, so no extra
        # factor
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                            *, block_q: int, causal: bool, scale: float,
                            window=None):
    """The dK/dV pass grown by one product: one k-block of one query head
    per grid step (k-blocks innermost, the kv head's group outside them),
    with s, the mask, p, dp and ds rebuilt ONCE for each visible (q-block,
    k-block) pair (:func:`_dkv_of_k_block`). Beside dv and dk, ds @ k adds
    into the head's (T, D) float32 ``dq_acc``, which stays in VMEM over the
    head's k-blocks (zeroed at the first, scaled and written at the last).
    dk/dv add up over the group's heads in (T, D) float32 scratch and are
    written with the group's last head. Every sum runs in the order of the
    two-pass kernels: dq over ascending k-blocks, dk/dv over ascending
    q-blocks and then the group's heads."""
    g, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _new_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk, dv = _dkv_of_k_block(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, ki, block_q=block_q,
        causal=causal, scale=scale, window=window, dq_acc=dq_acc)
    keys = pl.ds(ki * k_ref.shape[1], k_ref.shape[1])

    @pl.when(g == 0)
    def _first_head():
        dk_acc[keys, :] = dk
        dv_acc[keys, :] = dv

    @pl.when(g > 0)
    def _next_head():
        dk_acc[keys, :] += dk
        dv_acc[keys, :] += dv

    @pl.when(g == pl.num_programs(1) - 1)
    def _store_dkv():
        # q was loaded pre-scaled, so dk = ds^T @ (scale*q) needs no factor
        dk_ref[0, keys, :] = dk_acc[keys, :].astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv_acc[keys, :].astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _store_dq():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _attention_reference(q, k, v, causal, scale, window=None):
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    group = q.shape[-3] // k.shape[-3]
    if group > 1:       # grouped-query heads: K and V repeated in memory
        k, v = (jnp.repeat(x, group, axis=-3) for x in (k, v))
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    if causal:
        t = q.shape[-2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t, t), bool), -window)
        s = jnp.where(mask, s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", w, v.astype(jnp.float32)).astype(q.dtype)


def auto_flash_block(t: int) -> int:
    """Largest divisor of t of the form min(512, t)/2^k — 512 is the
    measured fwd+bwd optimum of the streamed kernels on v5e (T=8192 sweep,
    BASELINE_r5_longcontext.json: 128->58.6, 256->32.1, 512->25.0 ms/layer;
    no swept config beat 512x512). Small blocks pay per-block loop/mask
    overhead ~2.4x; blocks past 512 regress mildly (1024x1024: 26.0;
    asymmetric mixes 26.2-28.4).
    Always returns a divisor: falls back to t itself (single whole-T
    block) for lengths with no power-of-2 structure, matching the old
    ``min(block, t)`` clamp's behavior on short odd sequences; callers
    resolving a ``None`` block reject the degenerate fallback beyond
    t=1024 (whole-(T, T) score tiles blow VMEM) rather than launch it."""
    blk = min(512, t)
    while blk > 8 and t % blk:
        blk //= 2
    return blk if blk and t % blk == 0 else t


def flash_envelope_ok(t: int) -> bool:
    """True when ``auto_flash_block(t)`` yields a block the streamed
    kernels are known-good for: 8-sublane aligned and within the
    (blk, T)-score-tile VMEM bound. The ONE encoding of the routing
    envelope — the model streamed route, the ring route, and Ulysses all
    consume it, so the three sites cannot drift."""
    blk = auto_flash_block(t)
    return blk % 8 == 0 and blk <= 1024


def fused_bwd_fits(t: int, d: int, dtype, block_q: int, block_k: int) -> bool:
    """True when the fused streamed backward (``_launch_bwd_fused``) fits
    the kernels' VMEM limit for a head of (T, D): the one rule by which
    ``flash_attention``'s backward picks one kernel or two, read from the
    shapes alone. Counted from above: every pipelined block twice (q and do
    whole, the dq, dk and dv blocks whole, k and v by block, the logsumexp
    and delta rows padded to 8 sublanes), the three (T, D) float32
    accumulators, and the score-sized float32 temporaries of one block
    pair. In bfloat16 at D=128 and 512 x 512 blocks that is 41.4 MB at
    T=8,192 and 76.0 at T=16,384 against the limit's 67.1, where the
    installed Mosaic allocates 36.4 and 66.1 (compiled for a described v5e
    under a falling limit); at D=64 and T=8,192, 24.4 for its 19.3."""
    item = jnp.dtype(dtype).itemsize
    blocks = 2 * ((2 + 1 + 2) * t * d * item + 2 * block_k * d * item
                  + 2 * 8 * t * 4)
    scratch = 3 * t * d * 4
    work = 6 * block_q * block_k * 4
    return blocks + scratch + work <= _VMEM_LIMIT_BYTES


def _resolve_flash_blocks(t: int, block_q, block_k):
    """None -> auto_flash_block with a guard: if auto-resolution
    degenerates to a whole-T block beyond the VMEM-safe envelope, raise an
    actionable error (the old fixed-128 default produced a bare divisor
    AssertionError here). Explicit blocks stay caller's choice.
    Non-8-aligned whole-T blocks WITHIN the envelope are allowed: Mosaic
    masks partial tiles — hardware-verified on v5e at T=100 and T=900,
    fwd+bwd, parity vs the einsum reference."""
    bq = auto_flash_block(t) if block_q is None else min(block_q, t)
    bk = auto_flash_block(t) if block_k is None else min(block_k, t)
    if (block_q is None and bq > 1024) or (block_k is None and bk > 1024):
        raise ValueError(
            f"flash_attention: T={t} has no power-of-2 block structure, so "
            "the auto block degenerates to a whole-T score tile that "
            "cannot fit VMEM; pass explicit block_q/block_k dividing T, "
            "pad the sequence, or use reference attention")
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_kernel(q, k, v, causal=False, block_q=None, block_k=None,
                            scale=None, interpret=False, window=None):
    out, _ = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, scale=scale, interpret=interpret,
                            window=window)
    return out


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    scale=None, interpret=False, window=None):
    """(B, H, T, D) or (BH, T, D) attention; T must divide by the blocks
    (block_q/block_k None = :func:`auto_flash_block`, the measured v5e
    optimum). ``k`` and ``v`` may have fewer heads than ``q`` (grouped-query
    attention: query head ``h`` reads kv head ``h // (H // KV)`` through the
    kernels' index maps, K and V are never repeated in memory). ``window``
    (causal only) makes key ``j`` visible to query ``i`` iff
    ``0 <= i - j < window``: blocks wholly outside the band are skipped, its
    edge blocks are masked. Forward AND backward stream blocks through VMEM
    with the online-softmax recurrence — O(T) memory in both directions.
    The backward is one kernel where a head's (T, D) buffers fit VMEM
    (:func:`fused_bwd_fits`; it rebuilds scores and exponentials once a
    visible block pair and makes dq, dk and dv from them) and two passes
    beyond (dq over q-blocks, dk/dv over k-blocks, each rebuilding them);
    the route follows from T, D and the dtype alone, and both give the same
    gradients: every sum runs in the same order. This is the
    long-context path (round 2's backward recomputed full attention in
    fp32 via XLA, materializing the (T, T) scores the forward avoided).
    The backward reads the forward's output and its (B*H, 1, T) float32
    logsumexp; under differentiation both carry the ``checkpoint_name``
    names ``FLASH_SAVED_NAMES``. A caller that rematerialises the code
    around this call keeps them with ``jax.checkpoint(f, policy=
    jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED_NAMES))``
    (``models/moe_decoder.py`` ``encode``): the replay then runs no
    ``flash_fwd``. Under any other checkpoint, or none, the names do
    nothing. First-order autodiff only — see :func:`higher_order_attention` for
    grad-of-grad."""
    if _HIGHER_ORDER:
        return _attention_reference(q, k, v, causal, scale, window)
    return _flash_attention_kernel(q, k, v, causal, block_q, block_k,
                                   scale, interpret, window)


def _flash_fwd(q, k, v, causal, block_q, block_k, scale, interpret, window):
    q, k, v = map(_first_order_only, (q, k, v))
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, scale=scale,
                              interpret=interpret, window=window)
    # the caller reads the tagged ``out`` too: a checkpoint that keeps both
    # names needs nothing of this rule again when it replays its body
    out, lse = map(checkpoint_name, (out, lse), FLASH_SAVED_NAMES)
    return out, (q, k, v, out, lse)


def _launch_bwd_dq(q, k, v, do, lse, delta, causal, bq, bk, sc, interpret,
                   window=None):
    """One dq pallas_call for a (q-shard, k/v-shard) pair: (BH, T, D)
    queries, (BKV, T, D) keys and values, lse/delta (BH, 1, T) fp32 in the
    GLOBAL softmax frame. Shared by the single-device backward and the
    ring-attention backward (where the pair's k/v arrived over ICI)."""
    bh, t, d = q.shape
    group = _kv_group(q, k, window, causal)
    qblk = pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0))
    kfull = pl.BlockSpec((1, t, d), lambda b_, i: (b_ // group, 0, 0))
    qvec = pl.BlockSpec((1, 1, bq), lambda b_, i: (b_, 0, i))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=bk, causal=causal,
                          scale=sc, window=window),
        grid=(bh, t // bq),
        in_specs=[qblk, kfull, kfull, qblk, qvec, qvec],
        out_specs=qblk,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v, do, lse, delta)


def _launch_bwd_dkv(q, k, v, do, lse, delta, causal, bq, bk, sc, interpret,
                    window=None):
    """One dk/dv pallas_call for a (q-shard, k/v-shard) pair — see
    :func:`_launch_bwd_dq`. The grid's innermost axis walks the query heads
    of a kv head's group, whose contributions add up in scratch. Runs with
    :func:`_launch_bwd_dq` in the ring backward and for a head past the
    fused kernel's VMEM (:func:`fused_bwd_fits`); inside it
    :func:`_launch_bwd_fused` makes all three gradients under this name."""
    from jax.experimental.pallas import tpu as pltpu
    bkv, t, d = k.shape
    group = _kv_group(q, k, window, causal)
    kblk = pl.BlockSpec((1, bk, d), lambda b_, i, g: (b_, i, 0))
    qfull = pl.BlockSpec((1, t, d), lambda b_, i, g: (b_ * group + g, 0, 0))
    tvec = pl.BlockSpec((1, 1, t), lambda b_, i, g: (b_ * group + g, 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=bq, causal=causal,
                          scale=sc, window=window),
        grid=(bkv, t // bk, group),
        in_specs=[qfull, kblk, kblk, qfull, tvec, tvec],
        out_specs=[kblk, kblk],
        out_shape=[jax.ShapeDtypeStruct((bkv, t, d), k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_bwd_dkv",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v, do, lse, delta)


def _launch_bwd_fused(q, k, v, do, lse, delta, causal, bq, bk, sc, interpret,
                      window=None):
    """dq, dk and dv of whole (BH, T, D) / (BKV, T, D) operands from ONE
    pallas_call that rebuilds the scores once a visible block pair (see
    :func:`_flash_bwd_fused_kernel`). The grid walks a kv head's group and,
    innermost, its k-blocks, so a query head's q, do, logsumexp and delta
    are fetched once and its dq accumulates on the chip. The call bears the
    name ``flash_bwd_dkv``: it is that kernel grown by the dq product, and
    the name is what the benchmark's attention metrics find it by."""
    from jax.experimental.pallas import tpu as pltpu
    bh, t, d = q.shape
    bkv = k.shape[0]
    group = _kv_group(q, k, window, causal)
    kblk = pl.BlockSpec((1, bk, d), lambda b_, g, i: (b_, i, 0))
    kfull = pl.BlockSpec((1, t, d), lambda b_, g, i: (b_, 0, 0))
    qfull = pl.BlockSpec((1, t, d), lambda b_, g, i: (b_ * group + g, 0, 0))
    tvec = pl.BlockSpec((1, 1, t), lambda b_, g, i: (b_ * group + g, 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, block_q=bq, causal=causal,
                          scale=sc, window=window),
        grid=(bkv, group, t // bk),
        in_specs=[qfull, kblk, kblk, qfull, tvec, tvec],
        out_specs=[qfull, kfull, kfull],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
        + [jax.ShapeDtypeStruct((bkv, t, d), k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)] * 3,
        interpret=interpret,
        name="flash_bwd_dkv",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v, do, lse, delta)


def _flash_bwd(causal, block_q, block_k, scale, interpret, window, res, g):
    q, k, v, out, lse = res
    q_shape, k_shape = q.shape, k.shape
    if q.ndim == 4:
        q, k, v, out, g = _merge_heads(q, k, v, out, g)
    bh, t, d = q.shape
    bq, bk = _resolve_flash_blocks(t, block_q, block_k)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    do = g.astype(q.dtype)
    # delta_i = rowsum(dO_i * O_i): the softmax-backward correction term,
    # one cheap fused elementwise reduction in XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, t)
    args = (q, k, v, do, lse, delta, causal, bq, bk, sc, interpret, window)
    if fused_bwd_fits(t, d, q.dtype, bq, bk):
        dq, dk, dv = _launch_bwd_fused(*args)
    else:   # a head too long for the fused kernel's VMEM: two passes
        dq = _launch_bwd_dq(*args)
        dk, dv = _launch_bwd_dkv(*args)
    return dq.reshape(q_shape), dk.reshape(k_shape), dv.reshape(k_shape)


_flash_attention_kernel.defvjp(_flash_fwd, _flash_bwd)


# ------------------- whole-head VMEM attention, packed (B, T, H*D) layout
#
# At BERT-scale sequence lengths the flash recurrence is the wrong tool: a
# single head's full (T, T) score matrix fits comfortably in VMEM (T=512
# fp32 -> 1 MB of the ~16 MB budget), so blocking over K only adds loop
# overhead. This kernel computes each head's ENTIRE attention -- scores,
# softmax, and the P@V matmul -- on-chip, one batch element per grid step,
# heads unrolled over static lane slices. The backward is the same shape:
# recompute S from q/k (cheap, MXU), rebuild P from the saved logsumexp,
# and emit dq/dk/dv without any (T, T) HBM materialization. Two things make
# it beat XLA's fused attention at short T where the round-2 streamed
# kernel lost: the XLA path writes/reads the score tensor ~6x per layer
# (fwd softmax + backward chain; by XLA's cost analysis of the B=96/T=512
# step, BASELINE_r4_profile.json: 212.0 GB accessed with XLA attention
# against 110.5 GB with this kernel), and consuming the packed projection
# layout directly means the (B, H, T, D) head transposes (6 physical
# (B, T, 768) copies per layer) never materialize.


def packed_kernel_shape_ok(t: int) -> bool:
    """Shape envelope of :func:`mha_attention_packed`: the whole (T, T)
    fp32 score block must fit VMEM next to its operands (T <= ~1024 on
    v5e's budget) and T must tile the 8-sublane dimension. The ONE place
    this envelope is encoded — models/bert.py's ``_use_packed_kernel`` and
    the layer-DSL ``multiHeadDotProductAttention`` auto-route both consume
    it, so the two call sites cannot drift."""
    return t % 8 == 0 and t <= 1024


def active_global_mesh():
    """The mesh of the enclosing ``jax.set_mesh`` context, or None. The
    packed/streamed kernels are monolithic pallas_calls: invoked on
    globally-sharded values they force GSPMD all-gathers (the
    module-header invariant), so auto-routing call sites that cannot see
    an explicit ``mesh`` argument (the layer DSL under ParallelWrapper's
    sharded fit) use this to detect sharded tracing and take the einsum
    path. ``get_abstract_mesh`` is the one public probe that answers both
    inside and outside a jit trace (``get_mesh`` raises under jit); the
    package's own sharded callers (ParallelWrapper, ParallelInference,
    InferenceEngine) open the context with ``jax.set_mesh``, which is
    what it reports. A legacy ``with mesh:`` block is not seen."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _causal_mask(s):
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


def _mha_packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                           heads: int, scale: float, causal: bool):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]              # (T, H*D) bf16
    t, hd = q.shape
    d = hd // heads
    # fold the softmax scale into q: one (T, H*D) multiply instead of a
    # (T, T) elementwise pass per head (the kernel is VPU-bound, not
    # MXU-bound, at D=64 — every removed (T, T) pass counts)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def score(h):
        sl = slice(h * d, (h + 1) * d)
        s = jax.lax.dot_general(qs[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return _causal_mask(s) if causal else s

    # software-pipelined heads loop (round 5): head h+1's QK^T dot issues
    # BEFORE head h's softmax so the scheduler overlaps MXU and VPU work —
    # the naive order measured exactly matmul-time + softmax-time (zero
    # overlap); this ordering cut fwd 2.06 -> 1.58 ms/layer at bench shapes
    # (round 5's standalone kernel timing on the chip, before PR 21)
    s = score(0)
    for h in range(heads):
        s_next = score(h + 1) if h + 1 < heads else None
        sl = slice(h * d, (h + 1) * d)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.float32)
        o = jax.lax.dot_general(p.astype(q.dtype), v[:, sl],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0, :, sl] = (o / l).astype(o_ref.dtype)
        lse_ref[0, h] = (m + jnp.log(l))[:, 0]
        s = s_next


def _mha_packed_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                           dq_ref, dk_ref, dv_ref, *, heads: int,
                           scale: float, causal: bool):
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    t, hd = q.shape
    d = hd // heads
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def score(h):
        sl = slice(h * d, (h + 1) * d)
        s = jax.lax.dot_general(qs[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return _causal_mask(s) if causal else s

    # same software pipelining as the forward: next head's score rebuild
    # (MXU) issues before this head's exp/ds chain (VPU)
    s = score(0)
    for h in range(heads):
        s_next = score(h + 1) if h + 1 < heads else None
        sl = slice(h * d, (h + 1) * d)
        qh, kh, vh, doh = qs[:, sl], k[:, sl], v[:, sl], do[:, sl]
        p = jnp.exp(s - lse_ref[0, h][:, None])
        pb = p.astype(q.dtype)
        dv = jax.lax.dot_general(pb, doh, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
        ds = (p * (dp - delta)).astype(q.dtype)
        # s = (scale*q) k^T, so dL/dk = ds^T (scale*q) = ds^T qs (exact) and
        # dL/dq = scale * (ds k) — the scale re-applies on the small (T, D)
        # result, not a (T, T) pass
        dq = jax.lax.dot_general(ds, kh, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        dk = jax.lax.dot_general(ds, qh, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
        s = s_next


def _tpu_params():
    # the whole-(T,T)-in-VMEM design needs more than the 16 MB default
    # scoped-vmem budget once double-buffered (B=48/T=512 bwd measured
    # 16.46 MB — one fusion away from the cliff); v5e has 128 MB VMEM
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _mha_packed_forward(q, k, v, heads, *, causal, scale, interpret):
    b, t, hd = q.shape
    assert hd % heads == 0, (hd, heads)
    d = hd // heads
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    blk = pl.BlockSpec((1, t, hd), lambda i: (i, 0, 0))
    vec = pl.BlockSpec((1, heads, t), lambda i: (i, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_mha_packed_fwd_kernel, heads=heads, scale=sc,
                          causal=causal),
        grid=(b,),
        in_specs=[blk, blk, blk],
        out_specs=[blk, vec],
        out_shape=[jax.ShapeDtypeStruct((b, t, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, t), jnp.float32)],
        interpret=interpret,
        name="mha_packed_fwd",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v)
    return o, lse


def _packed_reference(q, k, v, heads, causal, scale):
    """XLA reference attention on the packed (B, T, H*D) layout —
    differentiable to any order; the higher_order_attention() route."""
    b, t, hd = q.shape
    d = hd // heads

    def hsplit(x):
        return x.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

    o = _attention_reference(hsplit(q), hsplit(k), hsplit(v), causal, scale)
    return o.transpose(0, 2, 1, 3).reshape(b, t, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mha_packed_kernel(q, k, v, heads, causal=False, scale=None,
                       interpret=False):
    o, _ = _mha_packed_forward(q, k, v, heads, causal=causal, scale=scale,
                               interpret=interpret)
    return o


def mha_attention_packed(q, k, v, heads, causal=False, scale=None,
                         interpret=False):
    """Attention on the packed projection layout (B, T, heads*head_dim) —
    no (B, H, T, D) transpose ever materializes, and the per-head (T, T)
    scores live only in VMEM (fwd and bwd both Pallas). The softmax
    probabilities are float32: the backward rebuilds p as exp(s - lse) from
    the saved logsumexp, bitwise what the forward normalized. First-order
    autodiff only — see :func:`higher_order_attention` for grad-of-grad."""
    if _HIGHER_ORDER:
        return _packed_reference(q, k, v, heads, causal, scale)
    return _mha_packed_kernel(q, k, v, heads, causal, scale, interpret)


def _mha_packed_fwd_rule(q, k, v, heads, causal, scale, interpret):
    q, k, v = map(_first_order_only, (q, k, v))
    o, lse = _mha_packed_forward(q, k, v, heads, causal=causal, scale=scale,
                                 interpret=interpret)
    return o, (q, k, v, lse)


def _mha_packed_bwd_rule(heads, causal, scale, interpret, res, g):
    q, k, v, lse = res
    b, t, hd = q.shape
    d = hd // heads
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    blk = pl.BlockSpec((1, t, hd), lambda i: (i, 0, 0))
    vec = pl.BlockSpec((1, heads, t), lambda i: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_mha_packed_bwd_kernel, heads=heads, scale=sc,
                          causal=causal),
        grid=(b,),
        in_specs=[blk, blk, blk, blk, vec],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((b, t, hd), q.dtype)] * 3,
        interpret=interpret,
        name="mha_packed_bwd",
        compiler_params=None if interpret else _tpu_params(),
    )(q, k, v, g.astype(q.dtype), lse)
    return dq, dk, dv


_mha_packed_kernel.defvjp(_mha_packed_fwd_rule, _mha_packed_bwd_rule)


def mha_attention(q, k, v, causal=False, scale=None, interpret=False):
    """Whole-head-in-VMEM attention for (B, H, T, D) or (BH, T, D) layouts,
    T such that a (T, T) fp32 block fits VMEM (T <= ~1024). Thin wrapper
    over :func:`mha_attention_packed` with one head per grid step — fwd AND
    bwd are Pallas; the (T, T) scores never touch HBM in either direction."""
    orig_rank = q.ndim
    if orig_rank == 4:
        b, h, t, d = q.shape
        q, k, v = (x.reshape(b * h, t, d) for x in (q, k, v))
    o = mha_attention_packed(q, k, v, 1, causal, scale, interpret)
    if orig_rank == 4:
        o = o.reshape(b, h, t, d)
    return o


# ------------------------------------------- fused paged decode attention
#
# The serving decode hot path (models/bert.py make_paged_decode_step): one
# query token per slot attends over that slot's block-table rows in the
# shared KV block pool. The XLA gather route materializes pool[tables] —
# a (slots, L, heads, head_dim) tensor — in HBM every step just to read it
# once, which is exactly the memcpy-bound single-token read vLLM's
# PagedAttention kernel (SOSP '23 §4.3) exists to break. This kernel
# streams each slot's K/V blocks from the pool straight through VMEM
# (scalar-prefetched block table drives the BlockSpec index map, so the
# DMA engine chases the table) with the online-softmax recurrence in
# scratch — the (slots, L) view never exists in HBM in either layout.
# int8 pools dequantize on the fly inside the same pass (per-token,
# per-head symmetric scales stored beside the pool), so quantized storage
# doubles resident streams without a separate dequant materialization.
# Forward-only by design: decode never differentiates.


def _paged_decode_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                         block_size: int, scale: float, quantized: bool):
    """One (slot, block) grid step. Scratch carries the running
    max/denominator/accumulator across a slot's blocks (the grid iterates
    blocks minor-most, so a slot's steps are consecutive); the output
    block is written once, on the slot's last block. Fully-masked tail
    blocks skip their compute (the DMA still lands, but dead table
    entries point at the scratch block — one block-sized read)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s_idx = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[s_idx]                              # attend 0..pos incl.

    # block j holds global positions [j*B, (j+1)*B); skip blocks wholly
    # past the slot's write position (their scores would all mask to
    # -inf and contribute nothing — position 0 is always unmasked, so
    # block 0 always runs and the running max is always real)
    @pl.when(j * block_size <= pos)
    def _update():
        # q is ONE row per head, so both contractions are a VPU
        # multiply-and-reduce over the block in its stored (B, H, D)
        # layout (heads on sublanes, head_dim on lanes) -- Mosaic has no
        # matmul whose left side lacks a free dimension, and the kernel
        # is bound by the K/V block stream either way. Scores keep a
        # trailing unit lane dim, (B, H, 1), so they broadcast against
        # the (B, H, D) blocks and reduce over B into the (H, 1)/(H, D)
        # scratch without any relayout.
        qf = q_ref[0].astype(jnp.float32) * scale     # (H, D)
        kf = k_ref[0].astype(jnp.float32)             # (B, H, D)
        vf = v_ref[0].astype(jnp.float32)
        if quantized:
            kf = kf * ks_ref[0][:, :, None]           # (B, H) scales
            vf = vf * vs_ref[0][:, :, None]
        # s_blk[b, h] = sum_d q[h, d] * k[b, h, d]
        s_blk = jnp.sum(kf * qf[None], axis=-1, keepdims=True)  # (B, H, 1)
        gpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 0)
        s_blk = jnp.where(gpos <= pos, s_blk, _NEG_INF)
        m = m_ref[...]                                # (H, 1)
        m_new = jnp.maximum(m, s_blk.max(axis=0))
        p = jnp.exp(s_blk - m_new[None])              # (B, H, 1)
        alpha = jnp.exp(m - m_new)
        l_new = l_ref[...] * alpha + p.sum(axis=0)
        # acc[h, d] += sum_b p[b, h] * v[b, h, d]
        acc_new = acc_ref[...] * alpha + jnp.sum(p * vf, axis=0)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, pos, *,
                           block_size: int, scale: Optional[float] = None,
                           k_scale=None, v_scale=None, interpret=False):
    """Fused paged decode attention: q (S, H, D) single-token queries,
    k_pool/v_pool (NB, B, H, D) shared block pools, tables (S, nbmax)
    int32 physical block ids, pos (S,) int32 per-slot write positions
    (the query attends to global positions 0..pos inclusive, mirroring
    the gather path's causal mask). Returns (S, H, D) in q's dtype.

    With ``k_scale``/``v_scale`` ((NB, B, H) fp32 per-token-per-head
    scales) the pools are int8 and dequantization fuses into the block
    stream — the fp-sized K/V never exists anywhere, HBM or VMEM-resident
    beyond one block. The block table is SCALAR-PREFETCHED: the BlockSpec
    index map reads it, so each grid step's DMA fetches exactly the
    physical block the table names — the (S, L) gathered view is never
    materialized. Dead/short slots' tail table entries should name the
    pool's scratch block (the serving convention), costing one redundant
    block read but no compute (the kernel skips fully-masked blocks).

    Runs in interpret mode off-TPU (the test suite's route) and compiles
    natively on TPU. Forward-only — decode never differentiates; wrap in
    ``jax.lax.stop_gradient`` if it ever lands under one."""
    S, H, D = q.shape
    NB, B, _, _ = k_pool.shape
    if B != block_size:
        raise ValueError(
            f"pool block dim {B} != block_size {block_size}")
    nbmax = tables.shape[1]
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    # a python float stays weakly typed: a numpy float64 scale would
    # promote the fp32 kernel math to fp64 under jax_enable_x64
    sc = float(scale) if scale is not None else 1.0 / (D ** 0.5)

    def tab_map(s, j, tab, _pos):
        return (tab[s, j], 0, 0, 0)

    def stab_map(s, j, tab, _pos):
        return (tab[s, j], 0, 0)

    def q_map(s, j, tab, _pos):
        return (s, 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, D), q_map),
        pl.BlockSpec((1, B, H, D), tab_map),
        pl.BlockSpec((1, B, H, D), tab_map),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, B, H), stab_map),
                     pl.BlockSpec((1, B, H), stab_map)]
        operands += [k_scale, v_scale]

    from jax.experimental.pallas import tpu as pltpu
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nbmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), q_map),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)])
    kern = functools.partial(_paged_decode_kernel, block_size=block_size,
                             scale=sc, quantized=quantized)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
        compiler_params=None if interpret else _tpu_params(),
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)


def paged_decode_attention_reference(q, k_pool, v_pool, tables, pos, *,
                                     block_size: int,
                                     scale: Optional[float] = None,
                                     k_scale=None, v_scale=None):
    """Gather-based XLA reference for :func:`paged_decode_attention`:
    materializes pool[tables] into the (S, L, H, D) view and runs plain
    masked softmax attention in fp32 — the parity oracle the kernel tests
    compare against, and the shape of the serving gather route."""
    S, H, D = q.shape
    L = tables.shape[1] * block_size
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    gk = k_pool[tables].reshape(S, L, H, D).astype(jnp.float32)
    gv = v_pool[tables].reshape(S, L, H, D).astype(jnp.float32)
    if k_scale is not None:
        gk = gk * k_scale[tables].reshape(S, L, H)[..., None]
        gv = gv * v_scale[tables].reshape(S, L, H)[..., None]
    s = jnp.einsum("shd,slhd->shl", q.astype(jnp.float32), gk) * sc
    mask = jnp.arange(L)[None, :] <= pos[:, None]          # (S, L)
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shl,slhd->shd", p, gv).astype(q.dtype)

