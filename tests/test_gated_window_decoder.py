"""The Laguna block of the routed-expert decoder (``models/moe_decoder.py``
with per-layer query heads, a rotary by layer kind with partial YaRN, a
per-head output gate, a router on the normed feed-forward input with a
routed scale, SwiGLU experts beside a shared expert after a dense layer)
against its plain reference (``benchmarks/references/
gated_window_expert_decoder.py``) at tiny sizes on seeded weights: logits,
loss and gradients, uncut and on a share; one test a term; the shares of a
layer, by heads, by columns and by experts, adding up to the uncut layer;
and SmallThinker's program, which must not change."""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmarks.references import gated_window_expert_decoder as ref
from deeplearning4j_tpu import models
from deeplearning4j_tpu.models import (
    MoEDecoderConfig, forward, init_params, lm_loss, make_train_step,
    moe_decoder, param_pspecs)
from deeplearning4j_tpu.ops import pallas_kernels

B, T, V = 2, 64, 256
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_x64():
    """The suite turns x64 on (tests/conftest.py); the interpreter of the
    grouped-matmul kernel (megablox, a JAX library) needs it off, as on the
    chip."""
    with jax.enable_x64(False):
        yield


def _cfg(**kw):
    """A share like the benchmark's, small: rank 1 of 2 by heads (2, 3, 2
    query heads on 1 of 2 kv heads, half the shared expert's and the dense
    MLP's columns), 4 of 16 experts at 6 a token (more than are held), a
    dense full layer, a window layer and a full expert layer; the window
    (16) is narrower than the streamed kernels' block at T = 64 (64); the
    full layers turn half of a head of 16 at YaRN's frequencies."""
    base = dict(vocab_size=V, hidden=32, layers=3, heads=2,
                heads_per_layer=(2, 3, 2), kv_heads=1, kv_heads_total=2,
                head_dim=16, expert_dim=24, experts_total=16,
                experts_count=4, experts_offset=4, experts_per_token=6,
                routed_scale=2.5, router_input="ffn",
                freeze_share_router=True, expert_act="silu", shared_dim=32,
                dense_layers=1, mlp_dim=64, head_gate=True, model_share=2,
                model_rank=1, window=16, window_layout=(0, 1, 0),
                rope_layout=(1, 1, 1), rope_theta=1e4,
                global_rope_theta=5e5, global_rotary_fraction=0.5,
                yarn_factor=128.0, yarn_original_max=16,
                yarn_attention_factor=1.4852030263919618, max_seq=64,
                attention_impl="flash", dtype=jnp.float32, remat=False)
    return MoEDecoderConfig(**dict(base, **kw))


def _sizes(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0, scale=3.0):
    """Seeded weights, the matrices scaled up so that every term is far
    from rounding."""
    p = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(lambda a: a * scale if a.ndim > 1 else a, p)


def _batch(seed=1, t=T):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, t), 0, V)
    return {"tokens": tok, "targets": jnp.roll(tok, -1, 1),
            "weights": jnp.ones((B, t)).at[:, -1].set(0.0)}


def _all(t=T):
    return jnp.broadcast_to(jnp.arange(t)[None], (B, t))


def _close(got, want, rtol=2e-4, atol=2e-5):
    return jnp.allclose(got, want, rtol=rtol,
                        atol=atol * float(jnp.abs(want).max()))


def _layer_ref(bp, x, index, cfg):
    """The reference's layer ``index`` on every sequence of x (B, T, H)."""
    return jnp.stack([ref.layer(bp, xb, jnp.arange(x.shape[1]), index,
                                _sizes(cfg))[0] for xb in x])


# ------------------------------------------- program against reference
@pytest.mark.parametrize("share,remat", [("share", False), ("share", True),
                                         ("uncut", False)])
def test_float32_logits_loss_and_gradients_match_the_reference(share, remat):
    cfg = _cfg(remat=remat)
    if share == "uncut":
        cfg = dataclasses.replace(cfg.whole, vocab_size=V)
        assert cfg.heads_per_layer == (4, 6, 4) and cfg.kv_heads == 2
        assert cfg.experts_held == (0, 16) and cfg.shared_columns == 32
    params, batch = _params(cfg), _batch()
    run = jax.jit(lambda p, b: (forward(p, b["tokens"], cfg),
                                jax.value_and_grad(lm_loss)(p, b, cfg)))
    with jax.default_matmul_precision("highest"):
        got_logits, (got_loss, got_grads) = run(params, batch)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    want_grads = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    assert jnp.allclose(got_logits, want["logits"], atol=3e-5, rtol=3e-5)
    assert jnp.allclose(got_loss, want["loss"], rtol=2e-6)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for got, wanted in zip(jax.tree.leaves(got_grads),
                           jax.tree.leaves(want_grads)):
        assert _close(got, wanted)
    blocks = got_grads["blocks"]
    assert "mlp" in blocks[0] and "router" not in blocks[0]
    for g in blocks[1:]:
        # a share does not train its router, the whole model does
        assert bool(g["router"].any()) == (share == "uncut")
        assert g["experts"]["down"].any() and g["shared"]["down"].any()
    assert all(g["gate"].any() for g in blocks)


def test_bfloat16_compute_stays_within_the_stated_tolerance():
    """What the benchmark's ``correct`` compares, at tiny size: loss over
    all positions, logits where no held choice differs."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params, batch = _params(cfg, scale=1.0), _batch()
    got = jax.jit(forward, static_argnums=2)(params, batch["tokens"], cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    loss, counters = jax.jit(moe_decoder.lm_loss_and_counters,
                             static_argnums=2)(params, batch, cfg)
    chosen = np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
    # the expert layers only, each token's 6 choices
    assert chosen.shape == (2, B, T, 6)
    flipped = (chosen != np.asarray(want["chosen"])).any((0, 3))
    gap = np.asarray(jnp.abs(got - want["logits"]).max(-1))
    assert flipped.mean() < 0.2 and gap[~flipped].max() < 0.05
    assert not flipped.any() or want["margin"][flipped].max() < 0.05
    assert abs(float(loss) - float(want["loss"])) < 1e-3 * float(want["loss"])


# ----------------------------------------------------- one test a term
def test_each_layer_holds_its_own_query_heads():
    """Query heads by layer over the same key/value heads: the projections'
    shapes follow ``heads_per_layer``, and a layer's attention half with 3
    heads on 1 kv head is the reference's."""
    cfg = _cfg()
    params = _params(cfg)
    for i, heads in enumerate(cfg.heads_per_layer):
        bp = params["blocks"][i]
        assert bp["q"].shape == (32, heads * 16) and bp["o"].shape[0] \
            == heads * 16 and bp["gate"].shape == (32, heads)
        assert bp["k"].shape == bp["v"].shape == (32, 16)
    with pytest.raises(AssertionError):
        _cfg(heads_per_layer=(2, 3))            # one count a layer
    bp = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(moe_decoder._block, static_argnums=(3, 4))(
            bp, x, jnp.arange(T), 1, cfg)
    assert jnp.allclose(got, _layer_ref(bp, x, 1, cfg), atol=3e-5, rtol=3e-5)


def _yarn_by_the_formula(x, positions, theta, fraction, factor, original,
                         beta_fast, beta_slow, attention_factor):
    """The published ``rope_type: yarn`` with a ``partial_rotary_factor``
    (``transformers``' ``_compute_yarn_parameters`` and the rotate-half
    ``apply_rotary_pos_emb`` on the first ``dim`` channels), in NumPy."""
    dim = int(x.shape[-1] * fraction)
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp                       # the extrapolation factor
    inv_freq = interpolation * (1 - keep) + extrapolation * keep
    # the angles in float32, as the published code takes them: at a
    # position of 9,000 a float64 angle differs by a float32 ulp of it
    emb = np.concatenate([np.outer(positions.astype(np.float32),
                                   inv_freq.astype(np.float32))] * 2, -1)
    cos, sin = (f(emb)[:, None, :] * attention_factor
                for f in (np.cos, np.sin))
    rot, rest = x[..., :dim], x[..., dim:]
    rotated = np.concatenate([-rot[..., dim // 2:], rot[..., :dim // 2]], -1)
    return np.concatenate([rot * cos + rotated * sin, rest], -1), inv_freq


def test_the_full_layers_turn_half_a_head_at_yarns_frequencies():
    """At the published numbers (head 128, half turned, theta 5e5, factor
    128, original 8,192, betas 32 and 1, attention factor 1.4852) the
    program's rotary of a full layer is the formula's; the 64 channels it
    does not turn pass as they are; the fastest pairs keep theta's
    frequency and the slowest are divided by the factor; the window
    layers' rotary turns the whole head at their own theta."""
    cfg = MoEDecoderConfig(
        layers=2, heads=1, kv_heads=1, head_dim=128, window_layout=(0, 1),
        rope_layout=(1, 1), rope_theta=1e4, global_rope_theta=5e5,
        global_rotary_fraction=0.5, yarn_factor=128.0,
        yarn_original_max=8192, yarn_attention_factor=1.4852030263919618)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1, 300, 2, 128)))
    positions = np.arange(0, 9000, 30)
    got = np.asarray(moe_decoder._rope(
        jnp.asarray(x), jnp.asarray(positions), *cfg.rope_at(0)))
    want, inv_freq = _yarn_by_the_formula(
        x[0], positions, 5e5, 0.5, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    base = 5e5 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(inv_freq[:9], base[:9]) \
        and np.allclose(inv_freq[18:], base[18:] / 128)
    theta, turned, yarn = cfg.rope_at(1)
    assert (theta, turned, yarn) == (1e4, None, None)
    plain = np.asarray(moe_decoder._rope(jnp.asarray(x),
                                         jnp.asarray(positions), 1e4))
    np.testing.assert_array_equal(plain, np.asarray(moe_decoder._rope(
        jnp.asarray(x), jnp.asarray(positions), theta, turned, yarn)))
    # the reference computes the same frequencies by its own transcription
    assert np.allclose(ref.yarn_frequencies(64, 5e5, 128.0, 8192, 32, 1),
                       inv_freq)


def test_the_gate_scales_each_heads_output_before_w_o():
    """``sigmoid(a W_gate)`` is one number a head and position: with
    ``W_gate`` zero every head's output is halved; with a large positive
    column for every head but the first and a large negative one for the
    first, the layer is the ungated layer without the first head's rows
    of ``W_o``."""
    cfg = _cfg()
    bp = _params(cfg)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, 32))
    at = jnp.arange(T)
    ungated = dataclasses.replace(cfg, head_gate=False)
    attend = jax.jit(moe_decoder._attend, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        plain = attend(bp, x, at, 1, ungated) - x
        half = attend(dict(bp, gate=jnp.zeros_like(bp["gate"])), x, at, 1,
                      cfg) - x
        # on a positive stream every entry of a = rmsnorm(x) is positive,
        # so a column of +-1e3 saturates its head's gate at 1 or 0
        sharp = jnp.full_like(bp["gate"], 1e3).at[:, 0].set(-1e3)
        gated = attend(dict(bp, gate=sharp), jnp.abs(x), at, 1, cfg)
        without_first = attend(dict(bp, o=bp["o"].at[:16].set(0.0)),
                               jnp.abs(x), at, 1, ungated)
    assert jnp.allclose(half, 0.5 * plain, atol=1e-5)
    assert jnp.allclose(gated, without_first, atol=1e-4)


@pytest.mark.parametrize("group", [3, 2])
def test_a_window_narrower_than_a_kernel_block(group):
    """The streamed kernels (interpret mode) at a band of 8 inside blocks of
    32: a query block visits its own block and the one before, the band's
    edge is masked; values and all three gradients are the dense masked
    reference's, at kv groups of 3 and 2."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, group, T, 16))
    k, v = (jax.random.normal(kk, (1, 1, T, 16)) for kk in ks[1:3])
    do = jax.random.normal(ks[3], q.shape)

    def run(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out, *pull(do))

    with jax.default_matmul_precision("highest"):
        got = run(lambda a, b, c: pallas_kernels.flash_attention(
            a, b, c, True, 32, 32, None, True, 8))
        want = run(lambda a, b, c: pallas_kernels._attention_reference(
            a, b, c, True, None, 8))
    for g, w in zip(got, want):
        assert jnp.allclose(g, w, atol=2e-5, rtol=2e-5)
    # and the band is 8 wide: a key 8 positions back moves nothing
    far = k.at[:, :, 10].add(5.0)
    moved = pallas_kernels.flash_attention(q, far, v, True, 32, 32, None,
                                           True, 8)
    assert jnp.allclose(moved[:, :, 18:], got[0][:, :, 18:], atol=1e-6)
    assert not jnp.allclose(moved[:, :, 10:18], got[0][:, :, 10:18])


def test_more_choices_a_token_than_experts_held():
    """k = 6 of 16 with 4 held: the layer has 4 slots a token, one a held
    expert; every held choice lands once and the routed rows add up."""
    cfg = _cfg()
    assert cfg.experts_per_token > cfg.experts_count
    bp = _params(cfg)["blocks"][2]
    m = jax.random.normal(jax.random.PRNGKey(8), (B * T, 32))
    r = m @ bp["router"]
    with jax.default_matmul_precision("highest"):
        routed, counters = jax.jit(moe_decoder._experts,
                                   static_argnums=3)(bp, m, r, cfg)
    top = np.asarray(jax.lax.top_k(r, 6)[1])
    held = (top >= 4) & (top < 8)
    assert int(counters["choices_here"]) == held.sum()
    assert int(counters["buffer_rows"]) == B * T * 4     # one slot a held
    #                                                      expert, no rung
    chosen = np.asarray(counters["chosen"])
    assert chosen.shape == (B * T, 6)
    assert ((chosen >= 4) | (chosen == -1)).all()
    want, _, _ = ref._experts(
        dict(bp, shared={n: jnp.zeros_like(w)
                         for n, w in bp["shared"].items()}),
        m, 6, 4, True, 2.5, True)
    assert jnp.allclose(routed, want, atol=3e-5, rtol=3e-5)


def test_the_router_reads_the_normed_ffn_input_and_scales_its_weights():
    """The router's logits are ``rmsnorm(y) W_r``, not the layer's input;
    the routed weights carry ``routed_scale`` after the norm, so the routed
    part at 2.5 is 2.5 times the part at 1 and the choices are the same."""
    cfg = _cfg()
    bp = _params(cfg)["blocks"][2]
    x = jax.random.normal(jax.random.PRNGKey(9), (B, T, 32))
    block = jax.jit(moe_decoder._block, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        got, _ = block(bp, x, jnp.arange(T), 2, cfg)
        early, _ = block(bp, x, jnp.arange(T), 2,
                         dataclasses.replace(cfg, router_input="layer"))
        m = jax.random.normal(jax.random.PRNGKey(10), (B * T, 32))
        r = m @ bp["router"]
        parts = jax.jit(moe_decoder._expert_parts, static_argnums=3)
        scaled, _, c1 = parts(bp, m, r, cfg)
        one, _, c2 = parts(bp, m, r,
                           dataclasses.replace(cfg, routed_scale=1.0))
    assert jnp.allclose(got, _layer_ref(bp, x, 2, cfg), atol=3e-5, rtol=3e-5)
    assert not jnp.allclose(got, early, atol=1e-3)
    assert jnp.allclose(scaled, 2.5 * one, atol=1e-5, rtol=1e-5)
    assert (np.asarray(c1["chosen"]) == np.asarray(c2["chosen"])).all()


# ------------------------------------------ the shares add up (section 4)
def _whole_cfg():
    return _cfg(heads=4, heads_per_layer=(4, 6, 4), kv_heads=2,
                kv_heads_total=None,
                experts_count=16, experts_offset=0, model_share=1,
                model_rank=0)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(layer):
    """2 chips by heads (2 or 3 of 4 or 6 query heads on 1 of 2 kv heads,
    with their gate columns) and by columns (16 of the shared expert's 32,
    32 of the dense MLP's 64), and the 16 experts in 4 shares of 4: each
    half's partial results over all the shares, with what every chip
    computes alike (the norms, the router, the residual) counted once, add
    up to what the uncut reference gives for the whole layer; one share's
    layer is the reference given the same share."""
    whole = _whole_cfg()
    params = _params(whole)
    bp = params["blocks"][layer]
    x = jax.random.normal(jax.random.PRNGKey(11), (B, T, 32))
    want = _layer_ref(bp, x, layer, whole)
    attend = jax.jit(moe_decoder._attend, static_argnums=(3, 4))
    shares = []
    with jax.default_matmul_precision("highest"):
        for rank in range(2):
            cfg = dataclasses.replace(
                whole, model_share=2, model_rank=rank, heads=2,
                heads_per_layer=(2, 3, 2), kv_heads=1, kv_heads_total=2,
                vocab_size=V // 2, experts_count=4, experts_offset=0)
            assert cfg.whole == dataclasses.replace(
                whole, kv_heads_total=2, vocab_size=V)
            shares.append((cfg, moe_decoder.share_of(params, cfg)["blocks"]))
        h = x + sum(attend(mine[layer], x, jnp.arange(T), layer, cfg) - x
                    for cfg, mine in shares)
        m = moe_decoder._rmsnorm(h, bp["ln2"], whole.rms_eps).reshape(
            B * T, -1)
        if layer == 0:
            parts = [moe_decoder._swiglu(mine[0]["mlp"], m, jnp.float32)
                     for _, mine in shares]
        else:
            r = m @ bp["router"]
            experts = jax.jit(moe_decoder._experts, static_argnums=3)
            parts = [moe_decoder._swiglu(mine[1]["shared"], m, jnp.float32)
                     for _, mine in shares]
            for offset in range(0, 16, 4):
                cfg = dataclasses.replace(shares[0][0],
                                          experts_offset=offset)
                mine = moe_decoder.share_of(params, cfg)["blocks"][1]
                parts.append(experts(mine, m, r, cfg)[0])
        got = h + sum(parts).reshape(h.shape)
        cfg, mine = shares[1]
        alone, _ = jax.jit(moe_decoder._block, static_argnums=(3, 4))(
            mine[layer], x, jnp.arange(T), layer, cfg)
    assert jnp.allclose(got, want, atol=5e-5, rtol=5e-5)
    assert jnp.allclose(alone, _layer_ref(mine[layer], x, layer, cfg),
                        atol=3e-5, rtol=3e-5)
    # one share alone is not the layer
    assert not jnp.allclose(h + parts[0].reshape(h.shape), want, atol=1e-2)


def test_a_share_holds_what_param_pspecs_shards():
    """Cutting exactly the axes ``param_pspecs`` names gives the shapes
    ``init_params`` makes for the share."""
    cfg = _cfg(vocab_size=V // 2)
    whole = cfg.whole
    mine = moe_decoder.share_of(_params(whole), cfg)
    made = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, made)
    specs = param_pspecs(cfg)
    full = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), whole))
    is_spec = lambda s: isinstance(s, PartitionSpec)     # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_spec) \
        == jax.tree.structure(made)
    for spec, a, b in zip(jax.tree.leaves(specs, is_leaf=is_spec),
                          jax.tree.leaves(made), jax.tree.leaves(full)):
        cut = [i for i, (p, q) in enumerate(zip(a.shape, b.shape)) if p != q]
        named = [i for i, axis in enumerate(spec) if axis is not None]
        assert cut == named, (spec, a.shape, b.shape)


def test_the_benchmarks_share_has_the_stated_parameter_count():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1-tp8ep32.json")) as f:
        config = json.load(f)
    from benchmarks.lib import model

    cfg = MoEDecoderConfig(**model.sizes(config, False))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 435_836_928 == config["deployment"]["parameters"]
    assert cfg.heads_per_layer == (6, 9, 9, 9, 6) and cfg.kv_heads == 1
    assert cfg.experts_held == (0, 8) and cfg.shared_columns == 128
    assert cfg.mlp_columns == 1536
    # the program's layouts are the source's names, as numbers
    assert cfg.window_layout == tuple(
        int(t == "sliding_attention") for t in config["layer_types"])
    assert cfg.dense_layers == config["mlp_layer_types"].count("dense")
    # every width is the published one
    published = config["published"]
    assert (cfg.hidden, cfg.head_dim, cfg.expert_dim, cfg.shared_dim,
            cfg.mlp_dim, cfg.window, cfg.experts_per_token,
            cfg.experts_total) == (3072, 128, 1024, 1024, 12288, 512, 10, 256)
    assert [h * 8 for h in cfg.heads_per_layer] \
        == published["num_attention_heads_per_layer"][:5]
    full, window = (config["rope_parameters"][k]
                    for k in ("full_attention", "sliding_attention"))
    assert cfg.rope_at(0) == (full["rope_theta"], 64, (
        full["factor"], full["original_max_position_embeddings"],
        full["beta_fast"], full["beta_slow"], full["attention_factor"]))
    assert cfg.rope_at(1) == (window["rope_theta"], None, None)
    # a token's 10 choices meet 8 held experts: a rung of 16,384 rows under
    # the worst case's 131,072 at 16,384 tokens
    assert moe_decoder._rung(16384, 10, 8, 256) == 16384


def test_the_family_trains_through_the_one_entry_point_with_its_counters():
    cfg = _cfg(remat=True, dtype=jnp.bfloat16)
    assert models.family_of(cfg) is models.family_of(MoEDecoderConfig())
    params, batch = _params(cfg, scale=1.0), _batch()
    init, step = make_train_step(cfg)
    _, _, loss, counters = step(params, init(params), batch)
    assert np.isfinite(float(loss))
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (2, 4)            # the two expert layers
    assert (rows.sum(1) == np.asarray(counters["choices_here"])).all()
    assert np.asarray(counters["chosen"]).shape == (2, B * T, 6)


# ------------------------------------ SmallThinker's program stays as it was
def _rope_of_the_parent(x, positions, theta: float):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _route_of_the_parent(r, cfg):
    p = jax.nn.softmax(r, axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e, top_p


def _experts_of_the_parent(bp, m, r, cfg):
    with jax.named_scope("router"):
        top_e, top_w = _route_of_the_parent(r, cfg)
    return moe_decoder.routed_experts(
        m, top_e, top_w, cfg.experts_held, cfg.experts_total, cfg.dtype,
        moe_decoder._grouped_ffn, bp["experts"])


def _block_of_the_parent(bp, x, positions, layer, cfg):
    """``moe_decoder._block`` before the Laguna terms, line for line."""
    from jax.ad_checkpoint import checkpoint_name
    B_, T_, H = x.shape
    with jax.named_scope("router"):
        r = jnp.dot(x.reshape(B_ * T_, H), bp["router"],
                    precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("attn_qkv"):
        a = moe_decoder._rmsnorm(x, bp["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = (
            (a @ bp[n].astype(cfg.dtype)).reshape(B_, T_, -1, cfg.head_dim)
            for n in ("q", "k", "v"))
    if cfg.rope_layout[layer]:
        with jax.named_scope("rope"):
            q, k = (_rope_of_the_parent(t, positions, cfg.rope_theta)
                    for t in (q, k))
    with jax.named_scope("attention"):
        o = moe_decoder._attention(
            *(checkpoint_name(t.transpose(0, 2, 1, 3), n)
              for t, n in zip((q, k, v), moe_decoder._QKV_NAMES)),
            cfg.window if cfg.window_layout[layer] else None, cfg)
        o = o.transpose(0, 2, 1, 3).reshape(B_, T_, -1)
    with jax.named_scope("attn_out"):
        y = x + jnp.dot(o, bp["o"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    with jax.named_scope("moe_dispatch"):
        m = moe_decoder._rmsnorm(y, bp["ln2"], cfg.rms_eps)
    out, counters = _experts_of_the_parent(bp, m.reshape(B_ * T_, H), r, cfg)
    with jax.named_scope("moe_combine"):
        return y + out.reshape(B_, T_, H), counters


def test_smallthinkers_step_lowers_to_the_same_text(monkeypatch):
    """With the parent's ``_block`` (its rotary, router and expert layer
    with it) in place of today's, SmallThinker's train step at tiny widths
    lowers to the same text: the Laguna terms add nothing to its program."""
    cfg = MoEDecoderConfig(
        vocab_size=V, hidden=32, layers=4, heads=4, kv_heads=2, head_dim=8,
        expert_dim=16, experts_total=8, experts_per_token=2, experts_count=4,
        window=16, max_seq=64)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    batch = _batch(t=32)

    def text():
        init, step = make_train_step(cfg)
        return step.lower(shapes, jax.eval_shape(init, shapes),
                          batch).as_text()

    today = text()
    monkeypatch.setattr(moe_decoder, "_block", _block_of_the_parent)
    assert today == text()
    assert "stablehlo" in today and len(today) > 50_000
