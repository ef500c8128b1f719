"""Compile rehearsals: the Pallas kernels of the two main paths, compiled
for a *described* TPU v5e at the shapes ``chip_smoke.py`` runs. Nothing
executes and no chip is attached — the installed TPU compiler refuses here
what it would refuse on the chip (a contraction Mosaic cannot express, a
block off the tiling, too much VMEM), which interpret mode never shows.
A compile that passes is not a chip run.

All of these live in ONE file and describe the topology inside a fixture:
one process at a time may load the TPU library, and under pytest-xdist only
the worker that is handed this file does (on-chip-measurement guide, §2).

Two settings of the test suite are scoped off around every compile, because
neither is how the chip runs and Mosaic refuses both: ``jax_enable_x64``
(tests/conftest.py) and the ``"highest"`` matmul precision the package pins
when the platform is cpu.
"""
import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops.pallas_kernels import (
    flash_attention, mha_attention_packed, paged_decode_attention)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def as_on_the_chip():
    with jax.enable_x64(False), jax.default_matmul_precision("default"):
        yield


def compile_for(one_chip, fn, *shapes_and_dtypes):
    """Lower + compile ``fn`` for the described chip; returns the program
    text. Raises what the chip's compiler would raise."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_and_dtypes]
    with as_on_the_chip():
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_packed_attention_at_flagship_shape(one_chip, causal, backward):
    """mha_attention_packed at the flagship train shape: B=96, T=512,
    12 heads of 64, bf16 (what make_train_step runs per layer)."""
    B, T, heads, hd = 96, 512, 12, 768

    def fwd(q, k, v):
        return mha_attention_packed(q, k, v, heads, causal, None, False)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(fwd, q, k, v)
        return out, vjp(out)

    compile_for(one_chip, fwd_bwd if backward else fwd,
                *[((B, T, hd), jnp.bfloat16)] * 3)


def _streamed_fwd_bwd(window=None):
    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                            False, window), q, k, v)
        return out, vjp(out)
    return fwd_bwd


def test_streamed_flash_attention_at_long_context(one_chip):
    """flash_attention forward and the fused backward at T=8192, D=64."""
    text = compile_for(one_chip, _streamed_fwd_bwd(),
                       *[((2, 12, 8192, 64), jnp.bfloat16)] * 3)
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" not in text


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "window"])
def test_streamed_flash_attention_with_kv_groups_and_window(one_chip, window):
    """The routed-expert decoder's attention at its published widths: B=2,
    T=8192, 28 query heads on 4 kv heads of 128, forward and the fused
    backward (a head's dq and a kv head's dk/dv add up in (T, D) float32
    scratch: 36 MB of VMEM as Mosaic allocates it)."""
    text = compile_for(one_chip, _streamed_fwd_bwd(window),
                       ((2, 28, 8192, 128), jnp.bfloat16),
                       *[((2, 4, 8192, 128), jnp.bfloat16)] * 2)
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" not in text


def test_streamed_flash_attention_past_the_fused_envelope(one_chip):
    """At T=16384, D=128 the fused backward's buffers pass the VMEM limit
    and the backward is the two passes, which the ring backward launches a
    shard pair at a time: both kernels at a kv group of 4."""
    text = compile_for(one_chip, _streamed_fwd_bwd(),
                       ((1, 4, 16384, 128), jnp.bfloat16),
                       *[((1, 1, 16384, 128), jnp.bfloat16)] * 2)
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" in text


def test_grouped_expert_products_at_published_widths(one_chip, monkeypatch):
    """The expert layer's grouped matmuls (megablox gmm, its transposed
    form and tgmm) over a 98,304-row buffer of 16 held experts of 2560 x
    768: the tilings ``moe_decoder._tiling`` picks must fit VMEM."""
    from deeplearning4j_tpu.models import moe_decoder

    def fwd_bwd(xs, gate, up, down, sizes):
        experts = {"gate": gate, "up": up, "down": down}
        out, vjp = jax.vjp(
            lambda xs, e: moe_decoder._grouped_ffn(xs, e, sizes), xs, experts)
        return out, vjp(out)

    # the program asks the backend whether to interpret: steered here only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compile_for(one_chip, fwd_bwd, ((98304, 2560), jnp.bfloat16),
                ((16, 2560, 768), jnp.float32),
                ((16, 2560, 768), jnp.float32),
                ((16, 768, 2560), jnp.float32), ((17,), jnp.int32))


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
def test_paged_decode_attention_at_engine_shape(one_chip, pool):
    """paged_decode_attention as the engine calls it: 16 slots, 12 heads of
    64, the default 16-token blocks over max_len 512; int8 pools bring their
    (1, B, H) scale blocks."""
    S, H, D, B = 16, 12, 64, 16
    nb = 512 // B
    NB = S * nb + 1
    quantized = pool == "int8"
    q_dtype = jnp.bfloat16 if quantized else jnp.dtype(pool)
    shapes = [((S, H, D), q_dtype), ((NB, B, H, D), jnp.dtype(pool)),
              ((NB, B, H, D), jnp.dtype(pool)), ((S, nb), jnp.int32),
              ((S,), jnp.int32)]
    if quantized:
        shapes += [((NB, B, H), jnp.float32)] * 2

    def attend(q, k, v, tables, pos, *scales):
        ks, vs = scales if quantized else (None, None)
        return paged_decode_attention(q, k, v, tables, pos, block_size=B,
                                      k_scale=ks, v_scale=vs,
                                      interpret=False)

    compile_for(one_chip, attend, *shapes)
