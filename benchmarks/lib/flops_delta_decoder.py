"""Operations and least bytes of the gated delta-rule / gated attention
decoder with routed SwiGLU experts beside a shared expert, from sizes alone:
what one chip's share of the model needs for a trained token, and what each
of its kernels needs for a step. Beside ``flops.py``,
``flops_routed_decoder.py``, ``flops_hybrid_decoder.py`` and
``flops_conv_decoder.py``, which stay as they are.

A trained token needs, forward plus backward, 6 operations for every matrix
parameter it touches, 12 x head_dim for every (query, key) pair its position
can see in every query head held here of every attention layer, and the
delta rule's recurrence as it is written, three times over. It touches, by
layer: a delta-rule mixer's ``W_q``, ``W_k``, ``W_v`` (hidden x heads x d
each), the two gates' bottlenecks (hidden x d and d x heads x d each),
``W_beta`` (hidden x heads) and ``W_o``; or an attention mixer's ``W_q``,
``W_gate``, ``W_k``, ``W_v`` and ``W_o``; then the router, the shared
expert's columns held here, and of the experts held here the expected number
it is routed to, ``experts_per_token x held / total``; then the output head
over this chip's slice of the vocabulary. Embedding rows are lookups, and
the taps, the norms and the gates' elementwise products are no matrix.
Recomputed operations (rematerialisation, the backward kernels' second pass
over the scores) do not count. **A share's router** (fewer experts held than
the router has outputs) is not trained by the program, so its matrix counts
2 operations a parameter, the forward product, and not 6.

**The recurrence**, per token, head and layer, on a state of d x d: the decay
``Diag(exp(g)) S`` (d^2 multiplies), what the state holds for the key
``S^T k`` (d^2 multiply-adds), the rank-one write ``S + beta k (v - S^T k)^T``
(d^2 multiply-adds) and the readout ``S^T q`` (d^2 multiply-adds): 7 d^2
operations forward, 21 d^2 with the backward pass. That is the recurrence
**as written**, not the chunked algorithm the program runs (its triangular
system, its scores over whole chunks and the masked halves of them are the
algorithm's own work), so a share of a roofline counted on this basis
cannot be flattered by them. **Its least bytes**: forward reads q, k, v
(bfloat16), g (float32, one a channel) and beta (float32, one a head) and
writes o (bfloat16): 12 d + 4 bytes a token and head; backward reads those
and the cotangent of o (12 d + 4) and writes the five gradients (10 d + 4).
The state never leaves the chip's fast memory in that count. On the v5e the
bytes bound it: 34 d + 12 = 4,364 bytes over 819 GB/s against 21 d^2 =
344,064 operations over 197 T/s.
"""
from __future__ import annotations

MFU_BASIS = ("analytic_model_flops: 6*N_matmul_touched (2 for a share's "
             "router) + 12*head_dim*heads*visible_pairs + "
             "21*delta_head_dim^2*delta_heads per delta-rule layer, per token")
_BF16 = 2


def attention_layers(sizes: dict) -> int:
    return sum(1 for i in sizes["attention_layers"] if i < sizes["layers"])


def delta_layers(sizes: dict) -> int:
    return sizes["layers"] - attention_layers(sizes)


def expected_experts_per_token(sizes: dict) -> float:
    """Of a token's choices, how many land on an expert held here."""
    return (sizes["experts_per_token"] * sizes["experts_count"]
            / sizes["experts_total"])


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden"] * sizes["expert_dim"]


def shared_columns(sizes: dict) -> int:
    return sizes["shared_dim"] // sizes.get("model_share", 1)


def router_params(sizes: dict) -> int:
    """The routers' matrices, over the layers."""
    return sizes["layers"] * sizes["hidden"] * sizes["experts_total"]


def delta_mixer_matrix_params(sizes: dict) -> int:
    h, d = sizes["hidden"], sizes["delta_head_dim"]
    inner = sizes["delta_heads"] * d
    return (3 * h * inner + 2 * (h * d + d * inner)
            + h * sizes["delta_heads"] + inner * h)


def attention_mixer_matrix_params(sizes: dict) -> int:
    return sizes["hidden"] * sizes["head_dim"] * (3 * sizes["heads"]
                                                  + 2 * sizes["kv_heads"])


def matmul_params_touched(sizes: dict) -> float:
    h = sizes["hidden"]
    ffn = (h * sizes["experts_total"] + 3 * h * shared_columns(sizes)
           + expected_experts_per_token(sizes) * expert_params(sizes))
    return (delta_layers(sizes) * delta_mixer_matrix_params(sizes)
            + attention_layers(sizes) * attention_mixer_matrix_params(sizes)
            + sizes["layers"] * ffn + h * sizes["vocab_size"])


def parameters(sizes: dict) -> int:
    """Every parameter the share holds, as the program's pytree has them."""
    h, d, k = sizes["hidden"], sizes["delta_head_dim"], sizes["conv_kernel"]
    inner = sizes["delta_heads"] * d
    delta = delta_mixer_matrix_params(sizes) + 3 * k * inner \
        + sizes["delta_heads"] + inner + d        # taps, A_log, dt_bias, norm
    ffn = (h * sizes["experts_total"] + sizes["experts_total"]
           + 3 * h * shared_columns(sizes)
           + sizes["experts_count"] * expert_params(sizes) + 2 * h)
    return (delta_layers(sizes) * delta
            + attention_layers(sizes) * attention_mixer_matrix_params(sizes)
            + sizes["layers"] * ffn + 2 * h * sizes["vocab_size"] + h)


def attention_flops_per_sequence(sizes: dict, seq_len: int) -> float:
    """Every causal pair, in every query head, of every attention layer."""
    pairs = seq_len * (seq_len + 1) // 2
    return (12.0 * sizes["head_dim"] * sizes["heads"] * pairs
            * attention_layers(sizes))


def recurrence_flops_per_token(sizes: dict) -> float:
    """The delta rule as written, forward and backward, in one layer."""
    return 21.0 * sizes["delta_heads"] * sizes["delta_head_dim"] ** 2


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    untrained = router_params(sizes) \
        if sizes["experts_count"] < sizes["experts_total"] else 0
    return (6.0 * matmul_params_touched(sizes) - 4.0 * untrained
            + attention_flops_per_sequence(sizes, seq_len) / seq_len
            + delta_layers(sizes) * recurrence_flops_per_token(sizes))


def kernels_per_step(sizes: dict, batch: int, seq_len: int,
                     routed_rows=None) -> dict:
    """For each kernel of this block, the operations and the least bytes of
    one training step (forward and backward, every layer), as facts:
    ``<kernel>_flops_per_step`` and ``<kernel>_bytes_per_step``.

    ``experts_ffn``: the three grouped products of the held experts over
    the rows routed to them, summed over the layers: ``routed_rows`` where
    the program counted them in the step that is read, else the expected
    number; at least, each pass reads its rows and the held experts' weights
    once and writes its result (forward, the rows' gradient, the weights'
    gradient), all in bfloat16.

    ``attn_stream``: the streamed attention kernels over the causal pairs;
    at least, forward reads q, k, v and writes o, backward reads q, k, v, o
    and do and writes dq, dk, dv.

    ``kda_scan``: the recurrence as the module's docstring counts it, its
    operations and its least bytes.
    """
    tokens = batch * seq_len
    h, f, d = sizes["hidden"], sizes["expert_dim"], sizes["head_dim"]
    layers, held = sizes["layers"], sizes["experts_count"]
    rows = routed_rows if routed_rows is not None \
        else layers * tokens * expected_experts_per_token(sizes)
    weights = layers * held * expert_params(sizes)
    row_bytes = rows * (2 * h + 3 * f)      # in, gate, up, inner, out
    experts_bytes = _BF16 * (3 * weights + 3 * row_bytes)
    q_rows = tokens * sizes["heads"] * d
    kv_rows = tokens * sizes["kv_heads"] * d
    attn_bytes = attention_layers(sizes) * _BF16 * (
        (2 * q_rows + 2 * kv_rows) + (4 * q_rows + 4 * kv_rows))
    scan_rows = delta_layers(sizes) * tokens
    scan_bytes = scan_rows * sizes["delta_heads"] \
        * (34 * sizes["delta_head_dim"] + 12)
    return {
        "experts_ffn_flops_per_step": 6.0 * rows * expert_params(sizes),
        "experts_ffn_bytes_per_step": float(experts_bytes),
        "attn_stream_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len),
        "attn_stream_bytes_per_step": float(attn_bytes),
        "kda_scan_flops_per_step":
            scan_rows * recurrence_flops_per_token(sizes),
        "kda_scan_bytes_per_step": float(scan_bytes),
    }
