"""Operations the algorithm needs, computed from sizes. Copied from
``deeplearning4j_tpu/profiler/profiler.py`` (``MFU_BASIS``,
``transformer_flops_per_token``, ``non_embedding_params``) so that a later
change to the program cannot move the yardstick."""
from __future__ import annotations

MFU_BASIS = "analytic_model_flops: 6*N_nonemb + 12*L*H*T per token"


def non_embedding_params(sizes: dict) -> int:
    """Parameters that do matrix work, from the sizes alone: the blocks, the
    final LayerNorm and the untied output head; token and position tables
    are lookups and are left out."""
    h, m, v = sizes["hidden"], sizes["mlp_dim"], sizes["vocab_size"]
    block = (2 * h + 2 * h                    # two LayerNorms
             + h * 3 * h + 3 * h              # qkv
             + h * h + h                      # attention output
             + h * m + m + m * h + h)         # mlp
    return sizes["layers"] * block + 2 * h + h * v


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward plus backward per trained token: 6*N on the matrix
    parameters plus the attention interior 12*L*H*T. Recomputed operations
    (remat) do not count."""
    return (6 * non_embedding_params(sizes)
            + 12 * sizes["layers"] * sizes["hidden"] * seq_len)

