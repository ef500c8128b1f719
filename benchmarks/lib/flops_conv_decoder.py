"""Operations and least bytes of the gated short-convolution / attention
decoder with a leading dense layer and routed SwiGLU experts, from sizes
alone: what one chip's share of the model needs for a trained token, and
what each of its kernels needs for a step. Beside ``flops.py``,
``flops_routed_decoder.py`` and ``flops_hybrid_decoder.py``, which stay as
they are.

A trained token needs, forward plus backward, 6 operations for every matrix
parameter it touches and 12 x head_dim for every (query, key) pair its
position can see in every query head of every attention layer. It touches,
by layer: a convolution mixer's two projections (hidden x 3 hidden and
hidden x hidden) or an attention mixer's four; then the dense MLP's three
matrices (the first ``dense_layers`` layers) or the router and, of the
experts held here, the expected number it is routed to,
``experts_per_token x held / total``; then the output head over this chip's
slice of the vocabulary. Embedding rows are lookups, and the convolution's
taps and gates, the per-head norms and the rotation are no matrix.
Recomputed operations (rematerialisation, the backward kernels' second pass
over the scores) do not count. **A share's router** (fewer experts held than
the router has outputs) is not trained by the program, so its matrix counts
2 operations a parameter, the forward product, and not 6.
"""
from __future__ import annotations

MFU_BASIS = ("analytic_model_flops: 6*N_matmul_touched (2 for a share's "
             "router) + 12*head_dim*heads*visible_pairs per token")
_BF16 = 2


def _mixers(sizes: dict) -> list:
    """The first letter of each layer's mixer: ``c`` or ``f``/``a``."""
    return [m[0] for m in sizes["mixers"][:sizes["layers"]]]


def conv_layers(sizes: dict) -> int:
    return _mixers(sizes).count("c")


def attention_layers(sizes: dict) -> int:
    return sizes["layers"] - conv_layers(sizes)


def expert_layers(sizes: dict) -> int:
    return sizes["layers"] - sizes["dense_layers"]


def expected_experts_per_token(sizes: dict) -> float:
    """Of a token's choices, how many land on an expert held here."""
    return (sizes["experts_per_token"] * sizes["experts_count"]
            / sizes["experts_total"])


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden"] * sizes["expert_dim"]


def router_params(sizes: dict) -> int:
    """The routers' matrices, over the expert layers."""
    return expert_layers(sizes) * sizes["hidden"] * sizes["experts_total"]


def matmul_params_touched(sizes: dict) -> float:
    h = sizes["hidden"]
    conv = 4 * h * h
    attention = 2 * h * sizes["head_dim"] * (sizes["heads"]
                                             + sizes["kv_heads"])
    routed = expert_layers(sizes) * expected_experts_per_token(sizes) \
        * expert_params(sizes)
    return (conv_layers(sizes) * conv + attention_layers(sizes) * attention
            + sizes["dense_layers"] * 3 * h * sizes["mlp_dim"]
            + router_params(sizes) + routed + h * sizes["vocab_size"])


def attention_flops_per_sequence(sizes: dict, seq_len: int) -> float:
    """Every causal pair, in every query head, of every attention layer."""
    pairs = seq_len * (seq_len + 1) // 2
    return (12.0 * sizes["head_dim"] * sizes["heads"] * pairs
            * attention_layers(sizes))


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    untrained = router_params(sizes) \
        if sizes["experts_count"] < sizes["experts_total"] else 0
    return (6.0 * matmul_params_touched(sizes) - 4.0 * untrained
            + attention_flops_per_sequence(sizes, seq_len) / seq_len)


def kernels_per_step(sizes: dict, batch: int, seq_len: int,
                     routed_rows=None) -> dict:
    """For each kernel of this block, the operations and the least bytes of
    one training step (forward and backward, every layer), as facts:
    ``<kernel>_flops_per_step`` and ``<kernel>_bytes_per_step``.

    ``experts_ffn``: the three grouped products of the held experts over
    the rows routed to them, summed over the expert layers: ``routed_rows``
    where the program counted them in the step that is read, else the
    expected number; at least, each pass reads its rows and the held
    experts' weights once and writes its result (forward, the rows'
    gradient, the weights' gradient), all in bfloat16.

    ``attn_stream``: the streamed attention kernels over the causal pairs;
    at least, forward reads q, k, v and writes o, backward reads q, k, v, o
    and do and writes dq, dk, dv.

    ``conv_gate``: the convolution mixers' gating between their two
    projections, ``C * conv(B * X)``: per channel, token and layer the two
    gates and the ``K`` taps' ``2 K - 1`` multiplies and adds, three times
    over for forward and backward; at least, forward reads B, C and X once
    and writes the gated rows, backward reads the three and the cotangent
    and writes three cotangents, all in bfloat16 (11 x hidden x 2 bytes).
    """
    tokens = batch * seq_len
    h, f, d = sizes["hidden"], sizes["expert_dim"], sizes["head_dim"]
    e_layers, held = expert_layers(sizes), sizes["experts_count"]
    rows = routed_rows if routed_rows is not None \
        else e_layers * tokens * expected_experts_per_token(sizes)
    weights = e_layers * held * expert_params(sizes)
    row_bytes = rows * (2 * h + 3 * f)      # in, gate, up, inner, out
    experts_bytes = _BF16 * (3 * weights + 3 * row_bytes)
    q_rows = tokens * sizes["heads"] * d
    kv_rows = tokens * sizes["kv_heads"] * d
    attn_bytes = attention_layers(sizes) * _BF16 * (
        (2 * q_rows + 2 * kv_rows) + (4 * q_rows + 4 * kv_rows))
    channels = conv_layers(sizes) * tokens * h
    gate_ops = 3.0 * (2 + 2 * sizes.get("conv_kernel", 3) - 1)
    return {
        "experts_ffn_flops_per_step": 6.0 * rows * expert_params(sizes),
        "experts_ffn_bytes_per_step": float(experts_bytes),
        "attn_stream_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len),
        "attn_stream_bytes_per_step": float(attn_bytes),
        "conv_gate_flops_per_step": gate_ops * channels,
        "conv_gate_bytes_per_step": float(_BF16 * 11 * channels),
    }
