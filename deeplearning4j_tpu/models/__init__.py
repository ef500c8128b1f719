"""Flagship model family — TPU-native transformer (BERT-class encoder / causal LM).

Reference parity target: the SameDiff BERT-base fine-tune path
(dl4j-examples + samediff-import, BASELINE configs #4/#5). The reference
executes BERT op-by-op through a JVM interpreter; here the whole train step
(fwd + loss + bwd + optimizer) is ONE pjit-compiled XLA program sharded over a
data/model/context device mesh.
"""
from deeplearning4j_tpu.models.bert import (
    TransformerConfig,
    family_of,
    make_train_step,
    BERT_BASE,
    init_kv_cache,
    kv_cache_pspecs,
    paged_kv_cache_pspecs,
    place_kv_cache,
    make_prefill,
    make_decode_step,
    make_paged_prefill,
    grow_block_table,
    make_paged_decode_step,
    sample_token,
    validate_block_size,
    validate_kv_dtype,
    quantize_kv,
    KV_DTYPES,
)

from deeplearning4j_tpu.models.moe_decoder import MoEDecoderConfig
from deeplearning4j_tpu.models.hybrid_decoder import HybridDecoderConfig
from deeplearning4j_tpu.models.conv_decoder import ConvDecoderConfig
from deeplearning4j_tpu.models.delta_decoder import DeltaDecoderConfig


# One entry point per function, whichever family the configuration is of
# (``bert.register_family``): a ``TransformerConfig`` reaches ``bert.py``'s
# functions, a ``MoEDecoderConfig`` ``moe_decoder.py``'s, a
# ``HybridDecoderConfig`` ``hybrid_decoder.py``'s, a ``ConvDecoderConfig``
# ``conv_decoder.py``'s, a ``DeltaDecoderConfig`` ``delta_decoder.py``'s: five
# families.
def init_params(key, cfg):
    return family_of(cfg).init_params(key, cfg)


def param_pspecs(cfg):
    return family_of(cfg).param_pspecs(cfg)


def forward(params, token_ids, cfg, mesh=None):
    """token_ids (B, T) int32 -> logits (B, T, vocab) fp32."""
    return family_of(cfg).forward(params, token_ids, cfg, mesh)


def lm_loss(params, batch, cfg, mesh=None):
    """Weighted LM cross-entropy of batch = {tokens, targets, weights}."""
    return family_of(cfg).lm_loss(params, batch, cfg, mesh)


__all__ = [
    "TransformerConfig", "MoEDecoderConfig", "HybridDecoderConfig",
    "ConvDecoderConfig", "DeltaDecoderConfig",
    "init_params", "forward", "lm_loss",
    "make_train_step", "param_pspecs", "BERT_BASE",
    "init_kv_cache", "kv_cache_pspecs", "paged_kv_cache_pspecs",
    "place_kv_cache", "make_prefill", "make_decode_step",
    "make_paged_prefill", "make_paged_decode_step", "sample_token",
    "grow_block_table",
    "validate_block_size", "validate_kv_dtype", "quantize_kv",
    "KV_DTYPES",
]
