"""NN / CNN / RNN / loss / image op definitions.

Covers the reference's declarable custom-op inventory for neural nets
(libnd4j include/ops/declarable/generic: conv2d, lstmLayer, batchnorm, softmax,
attention, image_resize, ... and org.nd4j.linalg.api.ops.impl.layers.*) as
registry entries over jnp/lax. Convs and matmuls lower to the MXU via XLA;
recurrences are expressed with lax.scan so XLA compiles one fused loop instead
of the reference's per-timestep op dispatch.

Layout convention: CNN ops default to NCHW with OIHW kernels (the reference's
default); NHWC is available via ``data_format`` for TPU-preferred layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.registry import op

# -------------------------------------------------------------- activations
# (ref: org.nd4j.linalg.activations.impl.* — ~25 classes)

op("relu", "nn")(jax.nn.relu)
op("relu6", "nn")(jax.nn.relu6)
op("leakyRelu", "nn")(lambda x, alpha=0.01: jax.nn.leaky_relu(x, alpha))
op("elu", "nn")(jax.nn.elu)
op("selu", "nn")(jax.nn.selu)
op("celu", "nn")(jax.nn.celu)
op("gelu", "nn")(lambda x, approximate=True: jax.nn.gelu(x, approximate=approximate))
op("sigmoid", "nn")(jax.nn.sigmoid)
op("hardSigmoid", "nn")(jax.nn.hard_sigmoid)
op("hardTanh", "nn")(lambda x: jnp.clip(x, -1.0, 1.0))
op("softmax", "nn")(lambda x, axis=-1: jax.nn.softmax(x, axis=axis))
op("logSoftmax", "nn")(lambda x, axis=-1: jax.nn.log_softmax(x, axis=axis))
op("softplus", "nn")(jax.nn.softplus)
op("softsign", "nn")(jax.nn.soft_sign)
op("swish", "nn")(jax.nn.silu)
op("mish", "nn")(jax.nn.mish)
op("prelu", "nn")(lambda x, alpha: jnp.where(x >= 0, x, alpha * x))
op("thresholdRelu", "nn")(lambda x, theta=1.0: jnp.where(x > theta, x, 0.0))
op("rationalTanh", "nn")(
    lambda x: 1.7159 * jnp.tanh(2.0 * x / 3.0))
op("rectifiedTanh", "nn")(lambda x: jnp.maximum(0.0, jnp.tanh(x)))
op("gumbelSoftmax", "nn")(
    lambda key, logits, temperature=1.0, axis=-1: jax.nn.softmax(
        (logits + jax.random.gumbel(key, logits.shape)) / temperature, axis=axis))


@op("linear", "nn")
def linear(x, w, b=None):
    """Dense affine: x @ w (+ b). w: (in, out)."""
    y = jnp.matmul(x, w)
    return y + b if b is not None else y


@op("layerNorm", "nn")
def layer_norm(x, gain=None, bias=None, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if gain is not None:
        y = y * gain
    if bias is not None:
        y = y + bias
    return y


@op("batchNorm", "nn")
def batch_norm(x, mean, var, gamma=None, beta=None, eps=1e-5, axis=1):
    """Inference-mode batch norm over channel ``axis`` (ref: batchnorm op)."""
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    y = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps)
    if gamma is not None:
        y = y * gamma.reshape(shape)
    if beta is not None:
        y = y + beta.reshape(shape)
    return y


@op("lrn", "nn")
def local_response_normalization(x, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    """LRN over channel dim of NCHW input (ref: LocalResponseNormalization)."""
    sq = x * x
    pad = depth_radius
    padded = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    windows = sum(padded[:, i:i + x.shape[1]] for i in range(2 * depth_radius + 1))
    return x / jnp.power(bias + alpha * windows, beta)


@op("dotProductAttention", "nn")
def dot_product_attention(q, k, v, mask=None, scaled=True):
    """(ref: dot_product_attention / multi_head_dot_product_attention custom op)
    q,k,v: (..., seq, head_dim); mask: broadcastable to (..., q_seq, k_seq)."""
    d = q.shape[-1]
    scores = jnp.einsum("...qd,...kd->...qk", q, k)
    if scaled:
        scores = scores / jnp.sqrt(jnp.asarray(d, dtype=scores.dtype))
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


@op("scaledDotProductAttentionFused", "nn")
def scaled_dot_product_attention_fused(q, k, v, mask=None, scale=None,
                                       causal=False, use_kernel=None):
    """Kernel-backed scaled-dot-product attention on split-head
    (B, H, T, D) layouts — the target op of the SameDiff attention-fusion
    rewrite (``SameDiff.fuseAttention``): an imported graph's
    matmul->scale->softmax->matmul chain collapses onto this, so the
    (B, H, T, T) score tensor stays in VMEM instead of round-tripping HBM
    between four graph nodes. ``use_kernel``: None = auto, True forces a
    kernel (interpret mode off-TPU), False pins the einsum. First-order
    autodiff when a kernel is taken; the einsum path differentiates to any
    order.

    The auto gate is MEASURED, not assumed (round 5, imported attention
    fusion): on this split-head layout the per-(b, h) kernel
    grid only beats XLA's batched einsum once the per-row (T, T) block is
    large — (32, 12, T, 64) fwd+bwd: einsum 3.1/3.3/6.9/20.6 ms vs kernel
    3.2/4.0/7.1/9.4 at T=128/256/512/1024. Auto therefore takes the
    whole-head kernel at T >= 768, the STREAMED flash kernel past the
    whole-(T, T) VMEM envelope (T > 1024), and the einsum below — which is
    why fusing config #4's T=128 graph is perf-neutral by design there.

    ``mask`` is ADDITIVE, broadcast onto the (B, H, T, T) scores after
    scaling (the BERT-import convention: 0 for visible, a large negative
    number for padding). A masked call always takes the einsum path — the
    kernels support only causal/none masking — so for masked graphs the
    fusion is a node-collapse, not a kernel win."""
    B, H, T, D = q.shape
    from deeplearning4j_tpu.ops.pallas_kernels import (
        active_global_mesh, flash_attention, flash_envelope_ok,
        mha_attention, packed_kernel_shape_ok)
    on_tpu = jax.default_backend() == "tpu"
    same = mask is None and k.shape == q.shape and v.shape == q.shape
    whole_ok = same and packed_kernel_shape_ok(T)
    stream_ok = same and T > 1024 and flash_envelope_ok(T)
    if use_kernel and not (whole_ok or stream_ok):
        raise ValueError(
            f"scaledDotProductAttentionFused: use_kernel=True but shape "
            f"{q.shape} (mask={'set' if mask is not None else 'None'}) "
            f"fits neither the whole-head (unmasked, T % 8 == 0, T <= "
            f"1024, matching q/k/v) nor the streamed kernel envelope; "
            f"use_kernel=None/False for the einsum path")
    auto = use_kernel is None and on_tpu and active_global_mesh() is None
    take_whole = whole_ok and (use_kernel or (auto and T >= 768))
    take_stream = stream_ok and (use_kernel or auto)
    if take_whole:
        return mha_attention(q, k, v, causal, scale, not on_tpu)
    if take_stream:
        return flash_attention(q, k, v, causal, None, None, scale,
                               not on_tpu)
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    # matmul (not einsum) so leading dims BROADCAST exactly like the
    # original imported matmul chain — shared-across-batch/head k/v
    # remain valid after the fuseAttention rewrite, and static-shape
    # sentinels in SameDiff metadata can't manufacture a runtime mismatch
    s = jnp.matmul(q, jnp.swapaxes(k, -1, -2)) * sc
    if mask is not None:
        s = s + mask.astype(s.dtype)
    if causal:
        cm = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(cm[None, None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.matmul(p, v)


@op("multiHeadDotProductAttention", "nn")
def multi_head_attention(x_q, x_kv, wq, wk, wv, wo, num_heads, mask=None,
                         use_kernel=None):
    """Fused MHA: x_q (B,Tq,D), x_kv (B,Tk,D); wq/wk/wv: (D,O); wo: (O,O).
    Head dims derive from the PROJECTION width O, not the input width D —
    rectangular projections (nIn != nOut, e.g. SelfAttentionLayer with
    distinct sizes) are valid.

    ``use_kernel``: route the unmasked square (Tq == Tk) case through the
    packed whole-head VMEM Pallas kernel — the flagship-bench attention
    path (round 5): the (B, T, O) projections feed the kernel directly, so
    the (B, H, T, hd) head transposes never materialize and the per-head
    (T, T) scores stay on-chip. None (default) = auto: kernel on TPU,
    XLA einsum elsewhere (interpret-mode Pallas would slow CPU runs);
    True forces it (tests use interpret mode); False forces the einsum
    path. Masked / cross-length attention always uses the einsum path
    (the kernel supports only causal/none masking). Auto never routes to
    the kernel while a global mesh context is active (ParallelWrapper's
    sharded-jit fit): a monolithic pallas_call over sharded operands
    would force GSPMD all-gathers — the einsum path partitions cleanly
    instead. ``use_kernel=True`` overrides even that (single-device
    meshes, tests)."""
    B, Tq, _ = x_q.shape
    Tk = x_kv.shape[1]
    O = wq.shape[-1]
    hd = O // num_heads

    from deeplearning4j_tpu.ops.pallas_kernels import (
        active_global_mesh, mha_attention_packed, packed_kernel_shape_ok)
    eligible = (mask is None and Tq == Tk and packed_kernel_shape_ok(Tq)
                and O % num_heads == 0)
    on_tpu = jax.default_backend() == "tpu"
    auto = use_kernel is None and on_tpu and active_global_mesh() is None
    if eligible and (use_kernel or auto):
        qp = jnp.matmul(x_q, wq)
        kp = jnp.matmul(x_kv, wk)
        vp = jnp.matmul(x_kv, wv)
        out = mha_attention_packed(qp, kp, vp, num_heads, False, None,
                                   not on_tpu)
        return jnp.matmul(out, wo)

    def split(x, w, T):
        return jnp.matmul(x, w).reshape(B, T, num_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = split(x_q, wq, Tq), split(x_kv, wk, Tk), split(x_kv, wv, Tk)
    m = mask[:, None, None, :] if (mask is not None and mask.ndim == 2) else mask
    out = dot_product_attention(q, k, v, mask=m)
    out = out.transpose(0, 2, 1, 3).reshape(B, Tq, O)
    return jnp.matmul(out, wo)


@op("embeddingLookup", "nn")
def embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


# --------------------------------------------------------------------- CNN


def _dims(data_format, spatial):
    if spatial == 1:
        return ("NCH", "OIH", "NCH") if data_format == "NCW" else ("NHC", "HIO", "NHC")
    if spatial == 2:
        return ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC")
    return ("NCDHW", "OIDHW", "NCDHW") if data_format == "NCDHW" else ("NDHWC", "DHWIO", "NDHWC")


def _pad(padding, kernel, strides, dilation):
    if isinstance(padding, str):
        return padding  # 'SAME' | 'VALID'
    if isinstance(padding, int):
        padding = [padding] * len(kernel)
    return [(p, p) if isinstance(p, int) else tuple(p) for p in padding]


@op("conv2d", "cnn")
def conv2d(x, w, b=None, strides=(1, 1), padding="SAME", dilation=(1, 1),
           data_format="NCHW", groups=1):
    """2D convolution (ref: libnd4j generic/nn/convo/conv2d.cpp).
    x: NCHW, w: OIHW (out_ch, in_ch/groups, kh, kw) by default."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _dims(data_format, 2))
    out = lax.conv_general_dilated(
        x, w, window_strides=tuple(strides),
        padding=_pad(padding, w.shape[-2:], strides, dilation),
        rhs_dilation=tuple(dilation), dimension_numbers=dn,
        feature_group_count=groups)
    if b is not None:
        shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
        out = out + b.reshape(shape)
    return out


@op("conv1d", "cnn")
def conv1d(x, w, b=None, stride=1, padding="SAME", dilation=1, data_format="NCW"):
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _dims(data_format, 1))
    out = lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding=_pad(padding, w.shape[-1:], (stride,), (dilation,)),
        rhs_dilation=(dilation,), dimension_numbers=dn)
    if b is not None:
        shape = [1, -1, 1] if data_format == "NCW" else [1, 1, -1]
        out = out + b.reshape(shape)
    return out


@op("conv3d", "cnn")
def conv3d(x, w, b=None, strides=(1, 1, 1), padding="SAME", dilation=(1, 1, 1),
           data_format="NCDHW"):
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _dims(data_format, 3))
    out = lax.conv_general_dilated(
        x, w, window_strides=tuple(strides),
        padding=_pad(padding, w.shape[-3:], strides, dilation),
        rhs_dilation=tuple(dilation), dimension_numbers=dn)
    if b is not None:
        shape = [1, -1, 1, 1, 1] if data_format == "NCDHW" else [1, 1, 1, 1, -1]
        out = out + b.reshape(shape)
    return out


@op("deconv2d", "cnn")
def deconv2d(x, w, b=None, strides=(1, 1), padding="SAME", data_format="NCHW"):
    """Transposed conv (ref: deconv2d.cpp). w: (in_ch, out_ch, kh, kw) -> we
    accept OIHW-like (out=in_ch of fwd) by using conv_transpose semantics."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _dims(data_format, 2))
    out = lax.conv_transpose(
        x, w, strides=tuple(strides),
        padding=_pad(padding, w.shape[-2:], strides, (1, 1)),
        dimension_numbers=dn, transpose_kernel=True)
    if b is not None:
        shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
        out = out + b.reshape(shape)
    return out


@op("depthwiseConv2d", "cnn")
def depthwise_conv2d(x, w, b=None, strides=(1, 1), padding="SAME", dilation=(1, 1),
                     data_format="NCHW"):
    """w: (ch_mult*in_ch, 1, kh, kw) grouped conv with groups=in_ch."""
    in_ch = x.shape[1] if data_format == "NCHW" else x.shape[-1]
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _dims(data_format, 2))
    out = lax.conv_general_dilated(
        x, w, window_strides=tuple(strides),
        padding=_pad(padding, w.shape[-2:], strides, dilation),
        rhs_dilation=tuple(dilation), dimension_numbers=dn,
        feature_group_count=in_ch)
    if b is not None:
        shape = [1, -1, 1, 1] if data_format == "NCHW" else [1, 1, 1, -1]
        out = out + b.reshape(shape)
    return out


@op("separableConv2d", "cnn")
def separable_conv2d(x, depth_w, point_w, b=None, strides=(1, 1), padding="SAME",
                     data_format="NCHW"):
    y = depthwise_conv2d(x, depth_w, None, strides, padding, (1, 1), data_format)
    return conv2d(y, point_w, b, (1, 1), "VALID", (1, 1), data_format)


def _pool(x, kind, kernel, strides, padding, data_format="NCHW"):
    spatial = len(kernel)
    if data_format.startswith("NC"):
        window = (1, 1) + tuple(kernel)
        strides_full = (1, 1) + tuple(strides)
    else:
        window = (1,) + tuple(kernel) + (1,)
        strides_full = (1,) + tuple(strides) + (1,)
    if isinstance(padding, str):
        pads = lax.padtype_to_pads(x.shape, window, strides_full, padding)
    else:
        p = _pad(padding, kernel, strides, (1,) * spatial)
        pads = ([(0, 0), (0, 0)] + list(p)) if data_format.startswith("NC") else ([(0, 0)] + list(p) + [(0, 0)])
    if kind == "max":
        return lax.reduce_window(x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
                                 lax.max, window, strides_full, pads)
    if kind == "sum":
        return lax.reduce_window(x, 0.0, lax.add, window, strides_full, pads)
    # avg: divide by actual window size (count_include_pad=False, dl4j default)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides_full, pads)
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(ones, 0.0, lax.add, window, strides_full, pads)
    return s / counts


@op("maxPool2d", "cnn")
def max_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", data_format="NCHW"):
    return _pool(x, "max", kernel, strides or kernel, padding, data_format)


@op("avgPool2d", "cnn")
def avg_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", data_format="NCHW"):
    return _pool(x, "avg", kernel, strides or kernel, padding, data_format)


@op("maxPool1d", "cnn")
def max_pool1d(x, kernel=2, strides=None, padding="VALID", data_format="NCW"):
    return _pool(x, "max", (kernel,), (strides or kernel,), padding, data_format)


@op("avgPool1d", "cnn")
def avg_pool1d(x, kernel=2, strides=None, padding="VALID", data_format="NCW"):
    return _pool(x, "avg", (kernel,), (strides or kernel,), padding, data_format)


@op("maxPool3d", "cnn")
def max_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID", data_format="NCDHW"):
    return _pool(x, "max", kernel, strides or kernel, padding, data_format)


@op("avgPool3d", "cnn")
def avg_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID", data_format="NCDHW"):
    return _pool(x, "avg", kernel, strides or kernel, padding, data_format)


@op("globalAvgPool", "cnn")
def global_avg_pool(x, data_format="NCHW", keepdims=False):
    axes = tuple(range(2, x.ndim)) if data_format.startswith("NC") else tuple(range(1, x.ndim - 1))
    return jnp.mean(x, axis=axes, keepdims=keepdims)


@op("globalMaxPool", "cnn")
def global_max_pool(x, data_format="NCHW", keepdims=False):
    axes = tuple(range(2, x.ndim)) if data_format.startswith("NC") else tuple(range(1, x.ndim - 1))
    return jnp.max(x, axis=axes, keepdims=keepdims)


@op("upsampling2d", "cnn")
def upsampling2d(x, scale=(2, 2), data_format="NCHW"):
    if data_format == "NCHW":
        return jnp.repeat(jnp.repeat(x, scale[0], axis=2), scale[1], axis=3)
    return jnp.repeat(jnp.repeat(x, scale[0], axis=1), scale[1], axis=2)


@op("spaceToDepth", "cnn")
def space_to_depth(x, block_size, data_format="NCHW"):
    b = block_size
    if data_format == "NCHW":
        N, C, H, W = x.shape
        x = x.reshape(N, C, H // b, b, W // b, b)
        return x.transpose(0, 3, 5, 1, 2, 4).reshape(N, C * b * b, H // b, W // b)
    N, H, W, C = x.shape
    x = x.reshape(N, H // b, b, W // b, b, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(N, H // b, W // b, C * b * b)


@op("depthToSpace", "cnn")
def depth_to_space(x, block_size, data_format="NCHW"):
    b = block_size
    if data_format == "NCHW":
        N, C, H, W = x.shape
        x = x.reshape(N, b, b, C // (b * b), H, W)
        return x.transpose(0, 3, 4, 1, 5, 2).reshape(N, C // (b * b), H * b, W * b)
    N, H, W, C = x.shape
    x = x.reshape(N, H, W, b, b, C // (b * b))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(N, H * b, W * b, C // (b * b))


@op("zeroPadding2d", "cnn")
def zero_padding2d(x, padding, data_format="NCHW"):
    (pt, pb), (pl, pr) = padding
    if data_format == "NCHW":
        return jnp.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))


@op("cropping2d", "cnn")
def cropping2d(x, cropping, data_format="NCHW"):
    (ct, cb), (cl, cr) = cropping
    H = x.shape[2] if data_format == "NCHW" else x.shape[1]
    W = x.shape[3] if data_format == "NCHW" else x.shape[2]
    if data_format == "NCHW":
        return x[:, :, ct:H - cb, cl:W - cr]
    return x[:, ct:H - cb, cl:W - cr, :]


@op("im2col", "cnn")
def im2col(x, kernel, strides=(1, 1), padding="VALID"):
    """Patch extraction (ref: libnd4j im2col helper) — provided for parity;
    XLA convs don't need it."""
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=tuple(kernel), window_strides=tuple(strides),
        padding=padding, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches


# --------------------------------------------------------------------- RNN


@op("lstmCell", "rnn")
def lstm_cell(x, h_prev, c_prev, w_ih, w_hh, b):
    """One LSTM step. x:(B,I), h/c:(B,H), w_ih:(I,4H), w_hh:(H,4H), b:(4H,).
    Gate order: [input, forget, cell(g), output] (ref: lstmLayer gate layout)."""
    z = jnp.matmul(x, w_ih) + jnp.matmul(h_prev, w_hh) + b
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c = f * c_prev + i * g
    h = o * jnp.tanh(c)
    return h, c


@op("lstmLayer", "rnn")
def lstm_layer(x, h0, c0, w_ih, w_hh, b, time_major=False, reverse=False, mask=None):
    """Full-sequence LSTM via lax.scan — the whole recurrence compiles to one
    fused XLA loop (ref: libnd4j lstmLayer.cpp runs per-step kernels).
    x: (B,T,I) [or (T,B,I) if time_major]. Returns (outputs (B,T,H), (hT, cT))."""
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)  # -> (T,B,I)
    if mask is not None and not time_major:
        mask = jnp.swapaxes(mask, 0, 1)  # (T,B)
    if reverse:
        x = jnp.flip(x, axis=0)
        if mask is not None:
            mask = jnp.flip(mask, axis=0)

    def step(carry, inp):
        h_prev, c_prev = carry
        if mask is not None:
            xt, mt = inp
        else:
            xt, mt = inp, None
        h, c = lstm_cell(xt, h_prev, c_prev, w_ih, w_hh, b)
        if mt is not None:
            mt = mt[:, None]
            h = jnp.where(mt > 0, h, h_prev)
            c = jnp.where(mt > 0, c, c_prev)
        return (h, c), h

    xs = (x, mask) if mask is not None else x
    (hT, cT), ys = lax.scan(step, (h0, c0), xs)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    if not time_major:
        ys = jnp.swapaxes(ys, 0, 1)
    return ys, (hT, cT)


@op("gruCell", "rnn")
def gru_cell(x, h_prev, w_ih, w_hh, b_ih, b_hh):
    """One GRU step. w_ih:(I,3H), w_hh:(H,3H). Gate order: [reset, update, new]."""
    gi = jnp.matmul(x, w_ih) + b_ih
    gh = jnp.matmul(h_prev, w_hh) + b_hh
    ir, iz, inew = jnp.split(gi, 3, axis=-1)
    hr, hz, hnew = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(ir + hr)
    z = jax.nn.sigmoid(iz + hz)
    n = jnp.tanh(inew + r * hnew)
    return (1.0 - z) * n + z * h_prev


@op("gru", "rnn")
def gru_layer(x, h0, w_ih, w_hh, b_ih, b_hh, time_major=False):
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)

    def step(h, xt):
        h2 = gru_cell(xt, h, w_ih, w_hh, b_ih, b_hh)
        return h2, h2

    hT, ys = lax.scan(step, h0, x)
    if not time_major:
        ys = jnp.swapaxes(ys, 0, 1)
    return ys, hT


@op("simpleRnn", "rnn")
def simple_rnn(x, h0, w_ih, w_hh, b, activation=jnp.tanh, time_major=False):
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)

    def step(h, xt):
        h2 = activation(jnp.matmul(xt, w_ih) + jnp.matmul(h, w_hh) + b)
        return h2, h2

    hT, ys = lax.scan(step, h0, x)
    if not time_major:
        ys = jnp.swapaxes(ys, 0, 1)
    return ys, hT


# -------------------------------------------------------------------- loss
# (ref: org.nd4j.linalg.lossfunctions.impl.* — ~20 classes). All take
# (labels, predictions) and reduce to scalar mean unless average=False.


def _weighted_mean(per_example, weights, average=True):
    if weights is not None:
        per_example = per_example * weights
    return jnp.mean(per_example) if average else jnp.sum(per_example)


@op("mse", "loss")
def loss_mse(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.mean((preds - labels) ** 2, axis=-1), weights, average)


@op("mae", "loss")
def loss_mae(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.mean(jnp.abs(preds - labels), axis=-1), weights, average)


@op("mape", "loss")
def loss_mape(labels, preds, weights=None, average=True):
    return _weighted_mean(
        jnp.mean(jnp.abs((labels - preds) / jnp.maximum(jnp.abs(labels), 1e-8)), axis=-1) * 100.0,
        weights, average)


@op("msle", "loss")
def loss_msle(labels, preds, weights=None, average=True):
    return _weighted_mean(
        jnp.mean((jnp.log1p(jnp.maximum(preds, 0)) - jnp.log1p(jnp.maximum(labels, 0))) ** 2, axis=-1),
        weights, average)


@op("mcxent", "loss")
def loss_mcxent(labels, preds_logprob_or_prob, weights=None, average=True, from_logits=False,
                label_smoothing=0.0):
    """Multi-class cross-entropy against one-hot labels (ref: LossMCXENT)."""
    if label_smoothing > 0:
        k = labels.shape[-1]
        labels = labels * (1.0 - label_smoothing) + label_smoothing / k
    if from_logits:
        logp = jax.nn.log_softmax(preds_logprob_or_prob, axis=-1)
    else:
        logp = jnp.log(jnp.clip(preds_logprob_or_prob, 1e-10, 1.0))
    return _weighted_mean(-jnp.sum(labels * logp, axis=-1), weights, average)


@op("sparseMcxent", "loss")
def loss_sparse_mcxent(labels, logits, weights=None, average=True):
    """Integer-label cross-entropy from logits (ref: sparse_softmax_cross_entropy)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return _weighted_mean(nll, weights, average)


@op("binaryXent", "loss")
def loss_binary_xent(labels, preds, weights=None, average=True, from_logits=False):
    if from_logits:
        per = jnp.maximum(preds, 0) - preds * labels + jnp.log1p(jnp.exp(-jnp.abs(preds)))
    else:
        p = jnp.clip(preds, 1e-7, 1.0 - 1e-7)
        per = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log(1.0 - p))
    return _weighted_mean(jnp.mean(per, axis=-1), weights, average)


@op("hinge", "loss")
def loss_hinge(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.mean(jnp.maximum(0.0, 1.0 - labels * preds), axis=-1), weights, average)


@op("squaredHinge", "loss")
def loss_squared_hinge(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.mean(jnp.maximum(0.0, 1.0 - labels * preds) ** 2, axis=-1), weights, average)


@op("huber", "loss")
def loss_huber(labels, preds, delta=1.0, weights=None, average=True):
    d = jnp.abs(preds - labels)
    per = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return _weighted_mean(jnp.mean(per, axis=-1), weights, average)


@op("logCosh", "loss")
def loss_logcosh(labels, preds, weights=None, average=True):
    d = preds - labels
    per = d + jax.nn.softplus(-2.0 * d) - jnp.log(2.0)
    return _weighted_mean(jnp.mean(per, axis=-1), weights, average)


@op("poisson", "loss")
def loss_poisson(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.mean(preds - labels * jnp.log(jnp.maximum(preds, 1e-8)), axis=-1),
                          weights, average)


@op("kld", "loss")
def loss_kld(labels, preds, weights=None, average=True):
    p = jnp.clip(labels, 1e-10, 1.0)
    q = jnp.clip(preds, 1e-10, 1.0)
    return _weighted_mean(jnp.sum(p * jnp.log(p / q), axis=-1), weights, average)


@op("cosineProximity", "loss")
def loss_cosine_proximity(labels, preds, weights=None, average=True):
    num = jnp.sum(labels * preds, axis=-1)
    den = jnp.linalg.norm(labels, axis=-1) * jnp.linalg.norm(preds, axis=-1)
    return _weighted_mean(-num / jnp.maximum(den, 1e-12), weights, average)


@op("l1", "loss")
def loss_l1(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.sum(jnp.abs(preds - labels), axis=-1), weights, average)


@op("l2", "loss")
def loss_l2(labels, preds, weights=None, average=True):
    return _weighted_mean(jnp.sum((preds - labels) ** 2, axis=-1), weights, average)


@op("sparseMcxentWithMask", "loss")
def loss_sparse_mcxent_masked(labels, logits, mask, average=True):
    """Masked integer-label xent — the BERT MLM loss shape."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = nll * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom if average else jnp.sum(nll)


# -------------------------------------------------------------------- image
# (ref: libnd4j generic/parity_ops image ops + helpers/image_resize)


def _tf_resize_matrix(n_in, n_out, method, align_corners, half_pixel,
                      nearest_mode="floor", cubic_a=-0.5,
                      exclude_outside=False, roi=None,
                      pytorch_half_pixel=False):
    """1-D interpolation matrix (n_out, n_in) with TF/ONNX coordinate rules.

    half_pixel (TF2 default): src = (i+0.5)*in/out - 0.5 — what
    jax.image.resize implements. align_corners (TF1): src = i*(in-1)/(out-1).
    Neither (TF1 legacy default): src = i*in/out. ``nearest_mode``
    (non-align-corners nearest only): 'floor' (TF legacy) or
    'round_prefer_floor' (ONNX default — round, ties toward floor).
    ``method='cubic'`` uses the ONNX/Keys convolution kernel with coefficient
    ``cubic_a`` (-0.75 per ONNX spec, -0.5 = Keys/TF); ``exclude_outside``
    zeroes taps outside the image and renormalizes (ONNX attribute).
    ``roi=(start, end)`` (normalized) switches to ONNX tf_crop_and_resize
    coordinates; returns (matrix, valid) then, where ~valid rows must take
    the extrapolation value.
    """
    import numpy as _np
    i = _np.arange(n_out, dtype=_np.float64)
    if roi is not None:
        start, end = roi
        if n_out > 1:
            src = start * (n_in - 1) + i * (end - start) * (n_in - 1) / (n_out - 1)
        else:
            src = _np.full(1, 0.5 * (start + end) * (n_in - 1))
        valid = (src >= 0.0) & (src <= n_in - 1)
    elif align_corners:
        scale = (n_in - 1) / (n_out - 1) if n_out > 1 else 0.0
        src = i * scale
        valid = None
    elif half_pixel:
        # ONNX pytorch_half_pixel: a length-1 output samples coordinate 0,
        # not the center (the only place the two half-pixel variants differ)
        if pytorch_half_pixel and n_out == 1:
            src = _np.zeros(1)
        else:
            src = (i + 0.5) * (n_in / n_out) - 0.5
        valid = None
    else:
        src = i * (n_in / n_out)
        valid = None
    m = _np.zeros((n_out, n_in), _np.float32)
    if method == "nearest":
        if align_corners:
            # TF uses roundf (half away from zero), NOT banker's rounding
            idx = _np.floor(src + 0.5).astype(int)
        elif nearest_mode == "round_prefer_floor":
            idx = _np.ceil(src - 0.5).astype(int)
        else:  # floor
            idx = _np.floor(src).astype(int)
        idx = _np.clip(idx, 0, n_in - 1)
        m[_np.arange(n_out), idx] = 1.0
    elif method == "cubic":
        lo = _np.floor(src).astype(int)
        a = float(cubic_a)

        def kern(t):
            at = _np.abs(t)
            return _np.where(
                at <= 1.0, (a + 2) * at ** 3 - (a + 3) * at ** 2 + 1.0,
                _np.where(at < 2.0,
                          a * at ** 3 - 5 * a * at ** 2 + 8 * a * at - 4 * a,
                          0.0))

        rows = _np.arange(n_out)
        for k in (-1, 0, 1, 2):
            j = lo + k
            w = kern(src - j)
            inside = (j >= 0) & (j < n_in)
            if exclude_outside:
                w = _np.where(inside, w, 0.0)
            _np.add.at(m, (rows, _np.clip(j, 0, n_in - 1)), w)
        if exclude_outside:
            s = m.sum(axis=1, keepdims=True)
            m = m / _np.where(s == 0.0, 1.0, s)
    else:  # bilinear
        src = _np.clip(src, 0.0, n_in - 1)
        lo = _np.floor(src).astype(int)
        hi = _np.minimum(lo + 1, n_in - 1)
        frac = (src - lo).astype(_np.float32)
        m[_np.arange(n_out), lo] += 1.0 - frac
        # hi may equal lo at the border: += accumulates to exactly 1.0
        m[_np.arange(n_out), hi] += frac
    m = jnp.asarray(m.astype(_np.float32))
    if roi is not None:
        return m, _np.asarray(valid)
    return m


def _tf_resize(x, size, method, data_format, align_corners, half_pixel,
               nearest_mode="floor", cubic_a=-0.5, exclude_outside=False,
               roi=None, extrapolation_value=0.0, pytorch_half_pixel=False):
    if data_format == "NCHW":
        H, W = x.shape[2], x.shape[3]
    else:
        H, W = x.shape[1], x.shape[2]
    fast_ok = (roi is None and half_pixel and not align_corners
               and not (method == "cubic"
                        and (cubic_a != -0.5 or exclude_outside))
               and not (pytorch_half_pixel and min(size) == 1))
    if fast_ok:
        # identical to jax.image.resize's sampling — use the fused path
        if data_format == "NCHW":
            out_shape = (x.shape[0], x.shape[1], size[0], size[1])
        else:
            out_shape = (x.shape[0], size[0], size[1], x.shape[3])
        return jax.image.resize(x, out_shape, method=method)
    roi_h = roi_w = None
    if roi is not None:
        (roi_h, roi_w) = roi
    wh = _tf_resize_matrix(H, size[0], method, align_corners, half_pixel,
                           nearest_mode, cubic_a, exclude_outside, roi_h,
                           pytorch_half_pixel)
    ww = _tf_resize_matrix(W, size[1], method, align_corners, half_pixel,
                           nearest_mode, cubic_a, exclude_outside, roi_w,
                           pytorch_half_pixel)
    valid_h = valid_w = None
    if roi is not None:
        wh, valid_h = wh
        ww, valid_w = ww
    # precision="highest": interpolation weights must not round through the
    # accelerator's fast-matmul dtype (bf16/TF32-analog) — parity vs the TF
    # kernels is the contract here and the matrices are tiny
    if data_format == "NCHW":
        out = jnp.einsum("oh,nchw,pw->ncop", wh.astype(x.dtype), x,
                         ww.astype(x.dtype), precision="highest")
    else:
        out = jnp.einsum("oh,nhwc,pw->nopc", wh.astype(x.dtype), x,
                         ww.astype(x.dtype), precision="highest")
    if roi is not None:
        # ONNX tf_crop_and_resize: coordinates outside the image take the
        # extrapolation value
        vh = jnp.asarray(valid_h)
        vw = jnp.asarray(valid_w)
        mask = vh[:, None] & vw[None, :]
        if data_format == "NCHW":
            mask = mask[None, None, :, :]
        else:
            mask = mask[None, :, :, None]
        out = jnp.where(mask, out, jnp.asarray(extrapolation_value, x.dtype))
    return out


@op("resizeBilinear", "image")
def resize_bilinear(x, size, data_format="NCHW", align_corners=False,
                    half_pixel_centers=True, roi=None,
                    extrapolation_value=0.0, pytorch_half_pixel=False):
    """TF-semantics bilinear resize incl. the TF1 align_corners /
    legacy-coordinate modes (ref: helpers/image_resize computeInterpolation
    weights; TF kernels are the behavioral oracle in tests). ``roi`` =
    ((start_h, end_h), (start_w, end_w)) normalized switches to ONNX
    tf_crop_and_resize coordinates with ``extrapolation_value`` outside."""
    return _tf_resize(x, size, "bilinear", data_format, align_corners,
                      half_pixel_centers, roi=roi,
                      extrapolation_value=extrapolation_value,
                      pytorch_half_pixel=pytorch_half_pixel)


@op("resizeNearest", "image")
def resize_nearest(x, size, data_format="NCHW", align_corners=False,
                   half_pixel_centers=True, nearest_mode="floor", roi=None,
                   extrapolation_value=0.0, pytorch_half_pixel=False):
    return _tf_resize(x, size, "nearest", data_format, align_corners,
                      half_pixel_centers, nearest_mode, roi=roi,
                      extrapolation_value=extrapolation_value,
                      pytorch_half_pixel=pytorch_half_pixel)


@op("cropAndResize", "image")
def crop_and_resize(x, boxes, box_indices, crop_size):
    """x: NHWC; boxes: (n,4) normalized [y1,x1,y2,x2]."""
    x = jnp.asarray(x)  # numpy input would break x[idx] under the vmap trace

    def one(box, idx):
        y1, x1, y2, x2 = box
        img = x[idx]
        H, W = img.shape[0], img.shape[1]
        ys = y1 * (H - 1) + jnp.linspace(0.0, 1.0, crop_size[0]) * (y2 - y1) * (H - 1)
        xs = x1 * (W - 1) + jnp.linspace(0.0, 1.0, crop_size[1]) * (x2 - x1) * (W - 1)
        grid_y, grid_x = jnp.meshgrid(ys, xs, indexing="ij")
        coords = jnp.stack([grid_y, grid_x], axis=0)
        return jnp.stack([
            jax.scipy.ndimage.map_coordinates(img[..., c], coords, order=1, mode="nearest")
            for c in range(img.shape[-1])], axis=-1)

    return jax.vmap(one)(boxes, box_indices)


@op("adjustContrast", "image")
def adjust_contrast(x, factor):
    mean = jnp.mean(x, axis=(-3, -2), keepdims=True)
    return (x - mean) * factor + mean


@op("rgbToGrayscale", "image")
def rgb_to_grayscale(x):
    """NHWC RGB -> NHW1."""
    w = jnp.asarray([0.2989, 0.587, 0.114], dtype=x.dtype)
    return jnp.sum(x * w, axis=-1, keepdims=True)


@op("nonMaxSuppression", "image")
def non_max_suppression(boxes, scores, max_output, iou_threshold=0.5, score_threshold=-jnp.inf):
    """Greedy NMS with static output size (padded with -1) — XLA-friendly
    (ref: non_max_suppression.cpp returns dynamic count)."""
    n = boxes.shape[0]

    def iou(b1, b2):
        y1 = jnp.maximum(b1[0], b2[0]); x1 = jnp.maximum(b1[1], b2[1])
        y2 = jnp.minimum(b1[2], b2[2]); x2 = jnp.minimum(b1[3], b2[3])
        inter = jnp.maximum(0.0, y2 - y1) * jnp.maximum(0.0, x2 - x1)
        a1 = (b1[2] - b1[0]) * (b1[3] - b1[1])
        a2 = (b2[2] - b2[0]) * (b2[3] - b2[1])
        return inter / jnp.maximum(a1 + a2 - inter, 1e-9)

    def body(i, state):
        sel, active_scores = state
        best = jnp.argmax(active_scores)
        valid = active_scores[best] > score_threshold
        sel = sel.at[i].set(jnp.where(valid, best, -1))
        ious = jax.vmap(lambda b: iou(boxes[best], b))(boxes)
        suppress = (ious > iou_threshold) | (jnp.arange(n) == best)
        active_scores = jnp.where(suppress | ~valid, -jnp.inf, active_scores)
        return sel, active_scores

    sel0 = jnp.full((max_output,), -1, dtype=jnp.int32)
    sel, _ = lax.fori_loop(0, max_output, body, (sel0, scores))
    return sel
