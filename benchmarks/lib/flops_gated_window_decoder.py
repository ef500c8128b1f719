"""Operations and least bytes of the window/full-attention decoder with a
per-head output gate and routed SwiGLU experts beside a shared expert, from
sizes alone: what one chip's share of the model needs for a trained token,
and what each of its kernels needs for a step. Beside the other families'
flops modules, which stay as they are.

A trained token needs, forward plus backward, 6 operations for every matrix
parameter it touches and 12 x head_dim for every (query, key) pair its
position can see in every query head held here, layer by layer: a layer has
its own number of heads (``heads_per_layer``) and a window layer sees at most
``window`` keys, the query's own position among them. It touches, by layer:
``W_q``, ``W_k``, ``W_v``, ``W_o`` and the per-head gate ``W_gate``; then the
dense MLP's columns held here on a leading dense layer, or the router, the
shared expert's columns held here and, of the experts held here, the
expected number it is routed to, ``experts_per_token x held / total``; then
the output head over this chip's slice of the vocabulary. Embedding rows are
lookups, the norms, the rotary and the gate's product are no matrix.
Recomputed operations (rematerialisation, the backward kernels' second pass
over the scores) do not count. **A share's router** (fewer experts held than
the router has outputs, with ``freeze_share_router``) is not trained by the
program, so its matrix counts 2 operations a parameter, and not 6.
"""
from __future__ import annotations

MFU_BASIS = ("analytic_model_flops: 6*N_matmul_touched (2 for a frozen "
             "share's router) + 12*head_dim*heads_l*visible_pairs_l per "
             "token, layer by layer")
_BF16 = 2


def heads(sizes: dict, layer: int) -> int:
    """Query heads held here in ``layer``."""
    per_layer = sizes.get("heads_per_layer")
    return per_layer[layer] if per_layer else sizes["heads"]


def dense_layers(sizes: dict) -> int:
    return sizes.get("dense_layers", 0)


def expert_layers(sizes: dict) -> int:
    return sizes["layers"] - dense_layers(sizes)


def window_layers(sizes: dict) -> list:
    return [i for i in range(sizes["layers"]) if sizes["window_layout"][i]]


def expected_experts_per_token(sizes: dict) -> float:
    """Of a token's choices, how many land on an expert held here."""
    return (sizes["experts_per_token"] * sizes["experts_count"]
            / sizes["experts_total"])


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden"] * sizes["expert_dim"]


def columns(sizes: dict, width: str) -> int:
    """Columns of a published width (``shared_dim``, ``mlp_dim``) held
    here."""
    return sizes.get(width, 0) // sizes.get("model_share", 1)


def attention_matrix_params(sizes: dict, layer: int) -> int:
    """W_q, W_k, W_v, W_o and W_gate of ``layer``'s share."""
    h, d, q = sizes["hidden"], sizes["head_dim"], heads(sizes, layer)
    gate = h * q if sizes.get("head_gate") else 0
    return h * d * (2 * q + 2 * sizes["kv_heads"]) + gate


def router_params(sizes: dict) -> int:
    """The routers' matrices, over the expert layers."""
    return expert_layers(sizes) * sizes["hidden"] * sizes["experts_total"]


def untrained_router_params(sizes: dict) -> int:
    frozen = sizes.get("freeze_share_router") \
        and sizes["experts_count"] < sizes["experts_total"]
    return router_params(sizes) if frozen else 0


def matmul_params_touched(sizes: dict) -> float:
    h = sizes["hidden"]
    attention = sum(attention_matrix_params(sizes, i)
                    for i in range(sizes["layers"]))
    dense = dense_layers(sizes) * 3 * h * columns(sizes, "mlp_dim")
    ffn = expert_layers(sizes) * (
        3 * h * columns(sizes, "shared_dim")
        + expected_experts_per_token(sizes) * expert_params(sizes))
    return (attention + dense + ffn + router_params(sizes)
            + h * sizes["vocab_size"])


def parameters(sizes: dict) -> int:
    """Every parameter the share holds, as the program's pytree has them."""
    h = sizes["hidden"]
    attention = sum(attention_matrix_params(sizes, i)
                    for i in range(sizes["layers"]))
    dense = dense_layers(sizes) * 3 * h * columns(sizes, "mlp_dim")
    ffn = expert_layers(sizes) * (
        h * sizes["experts_total"] + 3 * h * columns(sizes, "shared_dim")
        + sizes["experts_count"] * expert_params(sizes))
    norms = 2 * h * sizes["layers"] + h
    return attention + dense + ffn + norms + 2 * h * sizes["vocab_size"]


def visible_pairs(seq_len: int, window=None) -> int:
    """Causal (query, key) pairs of one sequence, the query's own position
    counted; with a ``window``, only those with ``i - j < window``."""
    w = seq_len if window is None else min(seq_len, window)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_flops_per_sequence(sizes: dict, seq_len: int,
                                 layers=None) -> float:
    """12 x head_dim for every visible pair in every query head, summed over
    ``layers`` (None: all)."""
    total = 0
    for i in range(sizes["layers"]) if layers is None else layers:
        window = sizes["window"] if sizes["window_layout"][i] else None
        total += heads(sizes, i) * visible_pairs(seq_len, window)
    return 12.0 * sizes["head_dim"] * total


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    return (6.0 * matmul_params_touched(sizes)
            - 4.0 * untrained_router_params(sizes)
            + attention_flops_per_sequence(sizes, seq_len) / seq_len)


def _attention_bytes(sizes: dict, tokens: int, layers) -> float:
    """At least: forward reads q, k, v and writes o, backward reads q, k, v,
    o and do and writes dq, dk, dv, all in bfloat16."""
    d, kv = sizes["head_dim"], sizes["kv_heads"]
    total = 0
    for i in layers:
        q_rows, kv_rows = tokens * heads(sizes, i) * d, tokens * kv * d
        total += (2 * q_rows + 2 * kv_rows) + (4 * q_rows + 4 * kv_rows)
    return float(_BF16 * total)


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

    ``attn_stream``: the streamed attention kernels of every layer over the
    visible pairs, and their least bytes (``_attention_bytes``).

    ``attn_window``: the same of the window layers alone.
    """
    tokens = batch * seq_len
    h, f = sizes["hidden"], sizes["expert_dim"]
    layers, held = expert_layers(sizes), sizes["experts_count"]
    rows = routed_rows if routed_rows is not None \
        else layers * tokens * expected_experts_per_token(sizes)
    weights = layers * held * expert_params(sizes)
    row_bytes = rows * (2 * h + 3 * f)      # in, gate, up, inner, out
    experts_bytes = _BF16 * (3 * weights + 3 * row_bytes)
    every, windowed = range(sizes["layers"]), window_layers(sizes)
    return {
        "experts_ffn_flops_per_step": 6.0 * rows * expert_params(sizes),
        "experts_ffn_bytes_per_step": float(experts_bytes),
        "attn_stream_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len),
        "attn_stream_bytes_per_step": _attention_bytes(sizes, tokens, every),
        "attn_window_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len, windowed),
        "attn_window_bytes_per_step":
            _attention_bytes(sizes, tokens, windowed),
    }
