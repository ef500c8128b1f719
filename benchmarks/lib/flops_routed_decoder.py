"""Operations and least bytes of the causal decoder with routed experts,
from sizes alone: what one chip's share of the model needs for a trained
token, and what each of its kernels needs for a step. Beside ``flops.py``
(the pre-LayerNorm transformer's), which stays as it is.

A trained token needs, forward plus backward, 6 operations for every matrix
parameter it touches and 12 x head_dim for every (query, key) pair its
position can see in every query head. It touches, per layer: the four
attention projections, the router, and of the experts held here the
expected number it is routed to, ``experts_per_token x held / total`` (the
router is not trained to balance, and uniform random tokens spread evenly);
then the output head over this chip's slice of the vocabulary. Embedding
rows are lookups. Recomputed operations (rematerialisation, the backward
kernels' second pass over the scores) do not count.
"""
from __future__ import annotations

MFU_BASIS = ("analytic_model_flops: 6*N_matmul_touched + 12*head_dim*"
             "heads*visible_pairs per token")
_BF16 = 2


def expected_experts_per_token(sizes: dict) -> float:
    """Of a token's choices, how many land on an expert held here."""
    return (sizes["experts_per_token"] * sizes["experts_count"]
            / sizes["experts_total"])


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden"] * sizes["expert_dim"]


def matmul_params_touched(sizes: dict) -> float:
    h, d = sizes["hidden"], sizes["head_dim"]
    attention = 2 * h * d * (sizes["heads"] + sizes["kv_heads"])
    layer = (attention + h * sizes["experts_total"]
             + expected_experts_per_token(sizes) * expert_params(sizes))
    return sizes["layers"] * layer + h * sizes["vocab_size"]


def visible_pairs(sizes: dict, seq_len: int) -> int:
    """(query, key) pairs of one sequence summed over the layers: causal,
    and inside the window on the layers that have one."""
    total = 0
    for layer in range(sizes["layers"]):
        w = seq_len
        if sizes["window_layout"][layer]:
            w = min(seq_len, sizes["window"])
        total += w * (w + 1) // 2 + (seq_len - w) * w
    return total


def attention_flops_per_sequence(sizes: dict, seq_len: int) -> float:
    return (12.0 * sizes["head_dim"] * sizes["heads"]
            * visible_pairs(sizes, seq_len))


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    return (6.0 * matmul_params_touched(sizes)
            + attention_flops_per_sequence(sizes, seq_len) / seq_len)


def kernels_per_step(sizes: dict, batch: int, seq_len: int,
                     routed_rows=None) -> dict:
    """For each kernel of this block, the operations and the least bytes of
    one training step (forward and backward, every layer), as facts:
    ``<kernel>_flops_per_step`` and ``<kernel>_bytes_per_step``.

    ``experts_ffn``: the three grouped products of the held experts over
    the rows routed to them, summed over the layers: ``routed_rows`` where
    the program counted them in the step that is read (a router that is
    being trained does not stay balanced), else the expected number; at
    least, each pass reads its rows and the held experts' weights once and
    writes its result (forward, the rows' gradient, the weights' gradient),
    all in bfloat16.

    ``attn_stream``: the streamed attention kernels over the visible pairs;
    at least, forward reads q, k, v and writes o, backward reads q, k, v, o
    and do and writes dq, dk, dv.
    """
    tokens = batch * seq_len
    h, f, d = sizes["hidden"], sizes["expert_dim"], sizes["head_dim"]
    layers, held = sizes["layers"], sizes["experts_count"]
    rows = routed_rows if routed_rows is not None \
        else layers * tokens * expected_experts_per_token(sizes)
    weights = layers * held * expert_params(sizes)
    row_bytes = rows * (2 * h + 3 * f)      # in, gate, up, hidden, out
    experts_bytes = _BF16 * (3 * weights + 3 * row_bytes)
    q_rows = tokens * sizes["heads"] * d
    kv_rows = tokens * sizes["kv_heads"] * d
    attn_bytes = layers * _BF16 * ((2 * q_rows + 2 * kv_rows)
                                   + (4 * q_rows + 4 * kv_rows))
    return {
        "experts_ffn_flops_per_step":
            6.0 * rows * expert_params(sizes),
        "experts_ffn_bytes_per_step": float(experts_bytes),
        "attn_stream_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len),
        "attn_stream_bytes_per_step": float(attn_bytes),
    }
