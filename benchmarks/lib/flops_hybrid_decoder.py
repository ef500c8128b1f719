"""Operations and least bytes of the hybrid state-space / attention /
latent-expert decoder, from sizes alone: what one chip's share of the model
needs for a trained token, and what each of its kernels needs for a step.
Beside ``flops.py`` and ``flops_routed_decoder.py``, which stay as they are.

A trained token needs, forward plus backward, 6 operations for every matrix
parameter it touches, 12 x head_dim for every (query, key) pair its position
can see in every query head held here, and 6 for every multiply-add of the
state-space scan's interior. It touches, by the letter of its layer in
``pattern``: ``M`` the input projection (hidden x (2 inner + 2 groups x state
+ heads)) and the output projection (inner x hidden); ``*`` the four
attention projections; ``E`` the router, the two latent projections, the
shared expert's columns held here, and of the experts held here the expected
number it is routed to, ``experts_per_token x held / total``; then the output
head over this chip's slice of the vocabulary. Embedding rows are lookups and
the convolution's four taps a channel are no matrix. Recomputed operations
(rematerialisation, the backward kernels' second pass over the scores) do
not count. **A share's router** (fewer experts held than the router has
outputs) is not trained by the program, so its matrix counts 2 operations a
parameter, the forward product, and not 6.

**The scan's interior** is counted as its chunked form needs it once: per
token and layer, with ``L`` the chunk, the scores ``C B^T`` of a group over
the causal half of a chunk ((L + 1) / 2 x state), per head the masked
product with X ((L + 1) / 2 x head_dim), the chunk's state (head_dim x
state) and its readout (head_dim x state). The program multiplies whole
L x L blocks and masks them; the upper halves are no work the algorithm
needs, so a share of a roofline counted on this basis cannot be flattered
by them.
"""
from __future__ import annotations

MFU_BASIS = ("analytic_model_flops: 6*N_matmul_touched (2 for a share's "
             "router) + 12*head_dim*heads*visible_pairs + "
             "6*scan_multiply_adds per token")
_BF16, _F32 = 2, 4


def _count(sizes: dict, kind: str) -> int:
    return sizes["pattern"][:sizes["layers"]].count(kind)


def _inner(sizes: dict) -> int:
    return sizes["mamba_heads"] * sizes["mamba_head_dim"]


def expected_experts_per_token(sizes: dict) -> float:
    """Of a token's choices, how many land on an expert held here."""
    return (sizes["experts_per_token"] * sizes["experts_count"]
            / sizes["experts_total"])


def expert_params(sizes: dict) -> int:
    return 2 * sizes["latent_dim"] * sizes["expert_dim"]


def mamba_params(sizes: dict) -> int:
    """Matrix parameters of one state-space layer's two projections."""
    h, inner = sizes["hidden"], _inner(sizes)
    into = 2 * inner + 2 * sizes["mamba_groups"] * sizes["state_dim"] \
        + sizes["mamba_heads"]
    return h * into + inner * h


def router_params(sizes: dict) -> int:
    """The routers' matrices, over the expert layers."""
    return _count(sizes, "E") * sizes["hidden"] * sizes["experts_total"]


def matmul_params_touched(sizes: dict) -> float:
    h = sizes["hidden"]
    attention = 2 * h * sizes["head_dim"] * (sizes["heads"]
                                             + sizes["kv_heads"])
    shared = 2 * h * (sizes["shared_dim"] // sizes.get("model_share", 1))
    experts = (h * sizes["experts_total"] + 2 * h * sizes["latent_dim"]
               + shared
               + expected_experts_per_token(sizes) * expert_params(sizes))
    return (_count(sizes, "M") * mamba_params(sizes)
            + _count(sizes, "*") * attention + _count(sizes, "E") * experts
            + h * sizes["vocab_size"])


def attention_flops_per_sequence(sizes: dict, seq_len: int) -> float:
    """Every causal pair, in every query head, of every attention layer."""
    pairs = seq_len * (seq_len + 1) // 2
    return (12.0 * sizes["head_dim"] * sizes["heads"] * pairs
            * _count(sizes, "*"))


def scan_multiply_adds_per_token(sizes: dict) -> float:
    """The chunked scan's interior for one token in one state-space layer."""
    half = (sizes["chunk"] + 1) / 2.0
    n, p = sizes["state_dim"], sizes["mamba_head_dim"]
    return (sizes["mamba_groups"] * half * n
            + sizes["mamba_heads"] * (half * p + 2 * p * n))


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    untrained = router_params(sizes) \
        if sizes["experts_count"] < sizes["experts_total"] else 0
    return (6.0 * matmul_params_touched(sizes) - 4.0 * untrained
            + attention_flops_per_sequence(sizes, seq_len) / seq_len
            + 6.0 * _count(sizes, "M") * scan_multiply_adds_per_token(sizes))


def kernels_per_step(sizes: dict, batch: int, seq_len: int,
                     routed_rows=None) -> dict:
    """For each kernel of this block, the operations and the least bytes of
    one training step (forward and backward, every layer), as facts:
    ``<kernel>_flops_per_step`` and ``<kernel>_bytes_per_step``.

    ``experts_ffn``: the two grouped products of the held experts over the
    latent rows routed to them, summed over the expert layers:
    ``routed_rows`` where the program counted them in the step that is
    read, else the expected number; at least, each pass reads its rows and
    the held experts' weights once and writes its result (forward, the
    rows' gradient, the weights' gradient), all in bfloat16.

    ``attn_stream``: the streamed attention kernels over the causal pairs;
    at least, forward reads q, k, v and writes o, backward reads q, k, v, o
    and do and writes dq, dk, dv.

    ``ssm_scan``: the scan's interior as the module's docstring counts it;
    at least, forward reads X, B, C (bfloat16) and delta (float32) and
    writes y (bfloat16), backward reads them and dy and writes the four
    gradients.
    """
    tokens = batch * seq_len
    z, f, d = sizes["latent_dim"], sizes["expert_dim"], sizes["head_dim"]
    e_layers, held = _count(sizes, "E"), sizes["experts_count"]
    rows = routed_rows if routed_rows is not None \
        else e_layers * tokens * expected_experts_per_token(sizes)
    weights = e_layers * held * expert_params(sizes)
    row_bytes = rows * (2 * z + 2 * f)      # in, inner, squared, out
    experts_bytes = _BF16 * (3 * weights + 3 * row_bytes)
    q_rows = tokens * sizes["heads"] * d
    kv_rows = tokens * sizes["kv_heads"] * d
    attn_bytes = _count(sizes, "*") * _BF16 * (
        (2 * q_rows + 2 * kv_rows) + (4 * q_rows + 4 * kv_rows))
    m_layers = _count(sizes, "M")
    x_row = _BF16 * _inner(sizes)
    bc_row = _BF16 * 2 * sizes["mamba_groups"] * sizes["state_dim"]
    dt_row = _F32 * sizes["mamba_heads"]
    scan_bytes = m_layers * tokens * (
        (2 * x_row + bc_row + dt_row)                   # forward
        + (4 * x_row + 2 * bc_row + 2 * dt_row))        # backward
    return {
        "experts_ffn_flops_per_step": 6.0 * rows * expert_params(sizes),
        "experts_ffn_bytes_per_step": float(experts_bytes),
        "attn_stream_flops_per_step":
            batch * attention_flops_per_sequence(sizes, seq_len),
        "attn_stream_bytes_per_step": float(attn_bytes),
        "ssm_scan_flops_per_step":
            6.0 * m_layers * tokens * scan_multiply_adds_per_token(sizes),
        "ssm_scan_bytes_per_step": float(scan_bytes),
    }
