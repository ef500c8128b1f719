"""The gated short-convolution / attention decoder with a leading dense layer
and routed SwiGLU experts (``models/conv_decoder.py``) against its plain
reference (``benchmarks/references/conv_expert_decoder.py``) at the
configuration's tiny sizes on seeded weights: logits, loss and gradients; the
convolution mixer against a loop over positions; q/k norm before the
rotation; the sigmoid/bias router with its 1e-6; a share that does not train
its router; the share test; the shared path of ``moe_decoder.routed_experts``
that only this family runs (a slot is a choice *and* the layer has a rung);
what a block's checkpoint keeps; and that the code this family shares with
the two routed decoders left their steps the programs they were."""
import collections
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec

from benchmarks.references import conv_expert_decoder as ref
from deeplearning4j_tpu.models import (
    ConvDecoderConfig, conv_decoder, forward, hybrid_decoder, init_params,
    lm_loss, make_train_step, moe_decoder, param_pspecs)
from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
from tests import test_hybrid_decoder as hybrid_tests
from tests.test_moe_decoder import _choices
from tests.test_trace_names import _pallas_names

B, T, V = 2, 32, 256
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXERS = ("conv", "full_attention", "conv", "conv", "conv")


@pytest.fixture(autouse=True)
def _no_x64():
    """The suite turns x64 on (tests/conftest.py); the interpreter of the
    grouped-matmul kernel (megablox, a JAX library) needs it off, as on the
    chip."""
    with jax.enable_x64(False):
        yield


def _cfg(**kw):
    """The configuration file's ``tiny`` sizes: 2 of 16 experts held at 2 a
    token, so that 64 tokens have a rung of 32 rows under the buffer's 128
    and a slot is one of the token's choices, as at the benchmark's 8 of 64
    at 4."""
    base = dict(vocab_size=V, hidden=32, layers=5, mixers=MIXERS,
                dense_layers=1, heads=4, kv_heads=2, head_dim=8, mlp_dim=96,
                expert_dim=24, experts_total=16, experts_count=2,
                experts_offset=4, experts_per_token=2, max_seq=64,
                attention_impl="flash", dtype=jnp.float32, remat=False)
    return ConvDecoderConfig(**dict(base, **kw))


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


def _share_of(params, cfg):
    """The share ``cfg`` of the uncut model's parameters: what
    ``param_pspecs`` shards, cut at ``experts_offset`` and, for the
    vocabulary, at this share's turn among the shares."""
    off, held = cfg.experts_held
    first = off // held * cfg.vocab_size
    blocks = [dict(bp, experts={n: lax.slice_in_dim(w, off, off + held)
                                for n, w in bp["experts"].items()})
              if "experts" in bp else bp for bp in params["blocks"]]
    return dict(params, blocks=blocks,
                tok_emb=lax.slice_in_dim(params["tok_emb"], first,
                                         first + cfg.vocab_size),
                lm_head=lax.slice_in_dim(params["lm_head"], first,
                                         first + cfg.vocab_size, axis=1))


def _close(got, want, rtol=2e-4, atol=2e-5):
    return jnp.allclose(got, want, rtol=rtol,
                        atol=atol * float(jnp.abs(want).max()))


# ------------------------------------------------- program against reference
def test_the_layers_kinds_follow_mixers_and_dense_layers():
    cfg = _cfg()
    assert cfg.kinds == ("cd", "ae", "ce", "ce", "ce")
    assert _cfg(mixers="caccc").kinds == cfg.kinds      # one letter a layer
    assert cfg.head_dim == 8 and _cfg(head_dim=None).head_dim == 8
    whole = ConvDecoderConfig()
    assert [k[0] for k in whole.kinds].count("a") == 10 \
        and [k[1] for k in whole.kinds].count("d") == 2 \
        and whole.head_dim == 64 and whole.experts_held == (0, 64)
    with pytest.raises(AssertionError):
        _cfg(mixers=("conv", "mamba", "conv", "conv", "conv"))
    with pytest.raises(AssertionError):
        _cfg(mixers=("conv",) * 4)
    with pytest.raises(AssertionError):
        _cfg(dense_layers=6)


@pytest.mark.parametrize("impl", ["flash", "full"])
@pytest.mark.parametrize("remat", [False, True])
def test_float32_logits_loss_and_gradients_match_the_reference(impl, remat):
    cfg = _cfg(attention_impl=impl, remat=remat)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        got_logits = forward(params, batch["tokens"], cfg)
        got_loss, got_grads = jax.value_and_grad(lm_loss)(params, batch, cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    want_grads = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    assert jnp.allclose(got_logits, want["logits"], atol=2e-5, rtol=2e-5)
    assert jnp.allclose(got_loss, want["loss"], rtol=2e-6)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for got, wanted in zip(jax.tree.leaves(got_grads),
                           jax.tree.leaves(want_grads)):
        assert _close(got, wanted)


def test_bfloat16_compute_stays_within_the_stated_tolerance():
    """What the benchmark's ``correct`` compares, at tiny size: loss over
    all positions, logits where no held choice differs."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params, batch = _params(cfg, scale=1.0), _batch()
    got = forward(params, batch["tokens"], cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    loss, counters = conv_decoder.lm_loss_and_counters(params, batch, cfg)
    chosen = np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
    assert chosen.shape == (4, B, T, cfg.experts_per_token)
    flipped = (chosen != np.asarray(want["chosen"])).any((0, 3))
    gap = np.asarray(jnp.abs(got - want["logits"]).max(-1))
    assert flipped.mean() < 0.2 and gap[~flipped].max() < 0.03
    # a choice differs only where the reference says it was close
    assert not flipped.any() or want["margin"][flipped].max() < 0.05
    assert abs(float(loss) - float(want["loss"])) < 1e-3 * float(want["loss"])


# ------------------------------------------------- the convolution mixer
def _gate_by_positions(bcx, taps):
    """``C_t * sum_j w_(K-1-j) (B * X)_(t-j)``, one position at a time, the
    positions before the first reading zeros."""
    K, H = taps.shape
    b, c, x = (bcx[..., i * H:(i + 1) * H] for i in range(3))
    z = b * x
    rows = []
    for t in range(bcx.shape[1]):
        v = sum(taps[K - 1 - j] * z[:, t - j] for j in range(K) if t - j >= 0)
        rows.append(c[:, t] * v)
    return jnp.stack(rows, axis=1)


@pytest.mark.parametrize("t", [1, 2, 3, 9])
def test_the_gated_convolution_is_the_loop_over_positions(t):
    """Values and gradients; at T = 1 and 2 some taps read only zeros."""
    H, K = 16, 3
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    bcx = jax.random.normal(ks[0], (B, t, 3 * H))
    taps = jax.random.uniform(ks[1], (K, H), minval=-1.0, maxval=1.0)
    cot = jax.random.normal(ks[2], (B, t, H))
    got, got_vjp = jax.vjp(conv_decoder._conv_gate, bcx, taps)
    want, want_vjp = jax.vjp(_gate_by_positions, bcx, taps)
    assert got.shape == (B, t, H) and got.dtype == bcx.dtype
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-6)
    for g, w in zip(got_vjp(cot), want_vjp(cot)):
        assert jnp.allclose(g, w, rtol=1e-5, atol=1e-5)
    # the first position reads the last tap alone
    b, c, x = jnp.split(bcx, 3, axis=-1)
    assert jnp.allclose(got[:, 0], c[:, 0] * taps[-1] * b[:, 0] * x[:, 0],
                        rtol=1e-5, atol=1e-6)


def test_the_convolution_is_shared_and_its_bias_is_optional():
    """``hybrid_decoder._causal_conv`` with no bias is the same sum less the
    bias; the Mamba-2 mixer passes one as before."""
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 7, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 5))
    b = jax.random.normal(jax.random.PRNGKey(2), (5,))
    assert conv_decoder._causal_conv is hybrid_decoder._causal_conv
    assert jnp.allclose(hybrid_decoder._causal_conv(x, w, b),
                        hybrid_decoder._causal_conv(x, w) + b, atol=1e-6)
    assert jnp.allclose(hybrid_decoder._causal_conv(x, w)[:, 0],
                        x[:, 0] * w[-1])


def test_a_sequence_reads_nothing_that_comes_after_it():
    """Causal through both mixers: the first 20 positions of 32 read as the
    same 20 alone."""
    cfg = _cfg()
    params, tok = _params(cfg), _batch()["tokens"]
    with jax.default_matmul_precision("highest"):
        short = forward(params, tok[:, :20], cfg)
        whole = forward(params, tok, cfg)
    assert jnp.allclose(short, whole[:, :20], atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------- attention
def test_queries_and_keys_are_normed_before_the_rotation_and_not_after():
    """With a scale that differs by dimension the norm and the rotation do
    not commute (with a scale of ones they would: a rotation keeps a
    head's length)."""
    cfg = _cfg(layers=1, mixers=("full_attention",), dense_layers=1)
    bp = _params(cfg)["blocks"][0]
    for i, n in enumerate(("q_norm", "k_norm")):
        bp[n] = {"scale": jax.random.uniform(
            jax.random.PRNGKey(i), (cfg.head_dim,), minval=0.5, maxval=2.0)}
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    eps, theta = cfg.rms_eps, cfg.rope_theta
    with jax.default_matmul_precision("highest"):
        got = conv_decoder._attend(bp, x, jnp.arange(T), cfg)

        def by_hand(xb, norm_first):
            u = ref._rmsnorm(xb, bp["ln_op"]["scale"], eps)
            q, k, v = ((u @ bp[n]).reshape(T, -1, cfg.head_dim)
                       for n in ("q", "k", "v"))
            scales = bp["q_norm"]["scale"], bp["k_norm"]["scale"]
            if norm_first:
                q, k = (ref._rope(ref._rmsnorm(t, g, eps), theta)
                        for t, g in zip((q, k), scales))
            else:
                q, k = (ref._rmsnorm(ref._rope(t, theta), g, eps)
                        for t, g in zip((q, k), scales))
            k, v = (jnp.repeat(t, cfg.heads // cfg.kv_heads, axis=1)
                    for t in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(cfg.head_dim)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s,
                          -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            return xb + o.reshape(T, -1) @ bp["o"]

        before = jnp.stack([by_hand(xb, True) for xb in x])
        after = jnp.stack([by_hand(xb, False) for xb in x])
    assert jnp.allclose(got, before, atol=2e-5, rtol=2e-5)
    assert not jnp.allclose(got, after, atol=1e-3)


# ------------------------------------------------------------------ router
def test_the_bias_chooses_and_does_not_weigh_and_the_sum_carries_1e_6():
    cfg = _cfg()
    n, total = 64, cfg.experts_total
    # small scores, so that 1e-6 shows in the normalisation
    s = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (n, total))
                       - 9.0)
    none = jnp.zeros((total,))
    bias = jnp.zeros((total,)).at[3].set(10.0).at[0].set(-10.0)
    route = functools.partial(hybrid_decoder._route, cfg=cfg, eps=1e-6)
    plain_e, plain_w = route(s, none)
    e, w = route(s, bias)
    assert e.shape == (n, cfg.experts_per_token)
    # expert 3 is chosen by every token and expert 0 by none
    assert (e == 3).any(-1).all() and not (e == 0).any()
    assert (plain_e == 0).any() and not (plain_e == 3).any(-1).all()
    # the weights are the scores of the chosen over their sum plus 1e-6
    top_s = jnp.take_along_axis(s, e, -1)
    total_s = top_s.sum(-1, keepdims=True)
    assert jnp.allclose(w, top_s / (total_s + 1e-6), rtol=1e-6)
    assert (w.sum(-1) < 1.0 - 1e-4).all()           # sums of 1e-4..1e-3
    exact = hybrid_decoder._route(s, bias, cfg)[1]  # the hybrid's: no 1e-6
    assert jnp.allclose(exact.sum(-1), 1.0, rtol=1e-6)
    assert not jnp.allclose(w, exact, rtol=1e-4)
    # a token that keeps its choice keeps its weights
    same = (jnp.sort(e, -1) == jnp.sort(plain_e, -1)).all(-1)
    order, plain_order = jnp.argsort(e, -1), jnp.argsort(plain_e, -1)
    assert same.any() and jnp.allclose(
        jnp.take_along_axis(w, order, -1)[same],
        jnp.take_along_axis(plain_w, plain_order, -1)[same])
    scaled = dataclasses.replace(cfg, routed_scale=2.5, norm_topk_prob=False)
    assert jnp.allclose(hybrid_decoder._route(s, bias, scaled, eps=1e-6)[1],
                        2.5 * top_s)


@pytest.mark.parametrize("held", [2, 16])
def test_the_whole_model_trains_its_router_and_a_share_does_not(held):
    cfg = _cfg(experts_count=held, experts_offset=0 if held == 16 else 4)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lm_loss)(params, batch, cfg)
    want = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    for kind, g, w in zip(cfg.kinds, got["blocks"], want["blocks"]):
        if kind[1] != "e":
            assert "router" not in g and g["mlp"]["down"].any()
            continue
        assert not g["router_bias"].any() and not w["router_bias"].any()
        if held < cfg.experts_total:
            assert not g["router"].any() and not w["router"].any()
        else:
            assert float(jnp.abs(w["router"]).max()) > 0
            assert _close(g["router"], w["router"])
        # what the experts held here learn does not wait for the router
        assert g["experts"]["down"].any() and g["ln_ffn"]["scale"].any()


# ------------------------------------------------------------ expert layer
def test_routing_drops_nothing_when_every_token_picks_the_same_experts():
    """The worst case the buffer is sized for: every token takes both held
    experts, ``tokens x 2`` rows, which is over the rung: the whole buffer's
    route. With the router's matrix zero every score is 0.5 and the bias
    alone chooses."""
    cfg = _cfg(layers=1, mixers=("conv",), dense_layers=0)   # experts 4, 5
    bp = _params(cfg)["blocks"][0]
    n = B * T
    m = jax.random.normal(jax.random.PRNGKey(6), (n, cfg.hidden))
    bp = dict(bp, router=jnp.zeros_like(bp["router"]),
              router_bias=jnp.zeros((16,)).at[jnp.asarray([4, 5])].set(1.0))
    with jax.default_matmul_precision("highest"):
        got, counters = conv_decoder._expert_part(bp, m, cfg)
        want, _, chosen = ref._experts(bp, m, 2, 4, True, 1.0)
    assert counters["rows_per_expert"].tolist() == [n, n]
    assert int(counters["choices_here"]) == 2 * n == \
        int(counters["buffer_rows"])
    assert int(counters["tokens_without_expert"]) == 0
    assert (counters["chosen"] == chosen).all() \
        and chosen[0].tolist() == [4, 5]
    assert jnp.allclose(got, want, atol=1e-4, rtol=1e-4)
    # and the other extreme: nobody picks a held expert, on the rung
    bp = dict(bp, router_bias=jnp.zeros((16,)).at[:2].set(1.0))
    got, counters = conv_decoder._expert_part(bp, m, cfg)
    assert int(counters["choices_here"]) == 0 and not got.any()
    assert int(counters["tokens_without_expert"]) == n
    assert int(counters["buffer_rows"]) == 32


def test_the_step_returns_counters_stacked_over_the_four_expert_layers():
    cfg = _cfg(remat=True)
    params, batch = _params(cfg), _batch()
    init, step = make_train_step(cfg)
    _, _, loss, counters = step(params, init(params), batch)
    assert np.isfinite(float(loss))
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (4, cfg.experts_count)
    assert (rows.sum(1) == np.asarray(counters["choices_here"])).all()
    assert (rows <= B * T).all()
    assert np.asarray(counters["chosen"]).shape == (4, B * T, 2)
    assert np.asarray(counters["tokens_without_expert"]).shape == (4,)
    assert counters["buffer_rows"].tolist() == [32] * 4        # the rung


# ------------------------- the shared path: a slot is a choice, with a rung
def _swiglu_plain(m, top_e, top_w, held, experts):
    """The layer's formula with no buffer: every held expert's SwiGLU on
    every row, weighted by the token's weight for it, or zero."""
    out = 0.0
    for e in range(held[1]):
        w = jnp.where(top_e == held[0] + e, top_w, 0.0).sum(-1)
        h = jax.nn.silu(m @ experts["gate"][e]) * (m @ experts["up"][e])
        out = out + w[:, None] * (h @ experts["down"][e])
    return out


@pytest.mark.parametrize("routed,buffer_rows", [
    (0, 32), (12, 32), (31, 32),      # fewer than the rung: the small route
    (32, 128), (100, 128), (128, 128)])   # the rung or more: the whole buffer
def test_choices_as_slots_on_either_route_are_the_plain_formula(
        monkeypatch, routed, buffer_rows):
    """64 tokens take 2 of 16 experts, 2 held: ``count >= k``, so a slot is
    one of the token's choices (the routed-expert decoder's layout), *and*
    ``_rung`` gives 32 rows under the buffer's 128 (the hybrid decoder's
    rung with the other layout). On the small route ``back`` is clamped to
    the rung's last row, which is of the "none" group because the count is
    strictly under the rung: a slot whose choice is held elsewhere reads a
    row the grouped products leave zero, and its cotangent falls on that
    row. Output, counters and gradients from the route the count picks,
    from the whole buffer and from the plain formula."""
    tokens, k, total, held, H, F = 64, 2, 16, (4, 2), 16, 24
    assert moe_decoder._rung(tokens, k, held[1], total) == 32
    assert moe_decoder._rung(32_768, 4, 8, 64) == 32_768      # the benchmark's
    top_e, top_w = _choices(tokens, k, total, held, routed)
    ks = jax.random.split(jax.random.PRNGKey(routed), 5)
    m, d_out = (jax.random.normal(k_, (tokens, H)) for k_ in ks[:2])
    experts = {n: jax.random.normal(k_, shape) * 0.3 for n, k_, shape in zip(
        ("gate", "up", "down"), ks[2:],
        [(held[1], H, F), (held[1], H, F), (held[1], F, H)])}

    def layer(m_, top_w_, experts_):
        return moe_decoder.routed_experts(
            m_, top_e, top_w_, held, total, jnp.float32,
            conv_decoder._swiglu_ffn, experts_)

    def run():
        with jax.default_matmul_precision("highest"):
            out, pull, counters = jax.vjp(layer, m, top_w, experts,
                                          has_aux=True)
            return out, counters, pull(d_out)

    got_out, got_counters, got_grads = run()
    assert int(got_counters["buffer_rows"]) == buffer_rows
    assert int(got_counters["choices_here"]) == routed
    assert got_counters["chosen"].shape == (tokens, k)
    monkeypatch.setattr(moe_decoder, "_rung", lambda *a: None)
    full_out, full_counters, full_grads = run()
    assert int(full_counters.pop("buffer_rows")) == 128
    assert jnp.allclose(got_out, full_out, rtol=1e-6, atol=1e-6)
    for name, value in full_counters.items():
        assert (np.asarray(got_counters[name]) == np.asarray(value)).all()
    with jax.default_matmul_precision("highest"):
        want_out, pull = jax.vjp(
            lambda *a: _swiglu_plain(a[0], top_e, a[1], held, a[2]),
            m, top_w, experts)
        want_grads = pull(d_out)
    assert jnp.allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    for got, full, want in zip(*map(jax.tree.leaves,
                                    (got_grads, full_grads, want_grads))):
        assert jnp.isfinite(got).all()
        assert jnp.allclose(got, full, rtol=1e-5, atol=1e-5)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)


def _held_bias(cfg, forced: bool):
    off, count = cfg.experts_held
    bias = jnp.zeros((cfg.experts_total,))
    return bias.at[off:off + count].set(1.0 if forced else -1.0)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("router,buffer_rows", [
    ("seeded", 32), ("all_held", 128), ("none_held", 32)])
def test_a_share_with_a_rung_is_the_reference_on_either_route(
        monkeypatch, remat, router, buffer_rows):
    """``tests/test_hybrid_decoder.py``'s test of the same name, with this
    family's layout: 2 of 16 experts held at 2 a token, a slot a choice.
    Loss, counters and gradients against the plain reference and against
    the program without a rung: with the seeded router (rows under the
    rung), with a bias that sends every token to the 2 held experts (128
    rows, over it: the whole buffer) and with one that sends none."""
    cfg = _cfg(remat=remat)
    params, batch = _params(cfg), _batch()
    if router != "seeded":
        for bp in params["blocks"]:
            if "router_bias" in bp:
                bp["router_bias"] = _held_bias(cfg, router == "all_held")
    both = jax.value_and_grad(conv_decoder.lm_loss_and_counters,
                              has_aux=True)
    with jax.default_matmul_precision("highest"):
        (got_loss, counters), got_grads = both(params, batch, cfg)
        monkeypatch.setattr(moe_decoder, "_rung", lambda *a: None)
        (full_loss, full_counters), full_grads = both(params, batch, cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    want_grads = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    assert counters["buffer_rows"].tolist() == [buffer_rows] * 4
    assert full_counters.pop("buffer_rows").tolist() == [128] * 4
    routed = np.asarray(counters["choices_here"])
    assert ((routed < 32) == (buffer_rows == 32)).all()
    for name, value in full_counters.items():
        assert (np.asarray(counters[name]) == np.asarray(value)).all()
    assert (np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
            == np.asarray(want["chosen"])).all()
    assert jnp.allclose(got_loss, full_loss, rtol=1e-6)
    assert jnp.allclose(got_loss, want["loss"], rtol=2e-6)
    for got, full, wanted in zip(*map(
            jax.tree.leaves, (got_grads, full_grads, want_grads))):
        scale = float(jnp.abs(wanted).max())
        assert jnp.allclose(got, full, rtol=1e-5, atol=1e-6 * scale)
        assert jnp.allclose(got, wanted, rtol=2e-4, atol=2e-5 * scale)


@pytest.mark.parametrize("remat", [True, False])
def test_the_streamed_kernels_run_once_an_attention_layer(remat):
    """The gradient holds, for each attention layer, one ``flash_fwd`` (the
    block's checkpoint keeps its results) and one fused ``flash_bwd_dkv``,
    which makes dq too: no ``flash_bwd_dq``."""
    cfg = _cfg(remat=remat)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: lm_loss(p, b, cfg)))(
        _params(cfg), _batch())
    calls = collections.Counter(_pallas_names(jaxpr.jaxpr))
    attention_layers = [k[0] for k in cfg.kinds].count("a")
    assert attention_layers == 1
    assert calls["flash_fwd"] == calls["flash_bwd_dkv"] == attention_layers
    assert calls["flash_bwd_dq"] == 0


# ------------------------------------- what a block's checkpoint keeps
@pytest.mark.parametrize("kind,kept", [
    ("cd", []), ("ce", ["router_logits", "router_choice"]),
    ("ae", ["attn", "attn", "attn", "flash", "flash", "router_logits",
            "router_choice"])])
def test_a_block_keeps_its_input_and_what_is_named(capsys, kind, kept):
    """``print_saved_residuals`` of one block under ``encode``'s policy: the
    block's arguments and, by kind, nothing of a convolution mixer and of
    the dense MLP; the router's logits and choice; q, k, v and the kernel's
    output and logsumexp. Nothing else, and nothing with the expert
    buffer's rows or the rung's (its conditional keeps the layer's inputs,
    which the replay makes again)."""
    cfg = _cfg(remat=True, layers=1, dense_layers=int(kind[1] == "d"),
               mixers=("conv" if kind[0] == "c" else "full_attention",))
    assert cfg.kinds == (kind,)
    bp = _params(cfg)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    ck = jax.checkpoint(
        functools.partial(conv_decoder._block, kind=kind, cfg=cfg),
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_SAVED_NAMES, *moe_decoder._QKV_NAMES,
            *conv_decoder._KEPT_NAMES))
    jax.ad_checkpoint.print_saved_residuals(
        lambda bp_, x_, pos_: ck(bp_, x_, pos_)[0].sum(), bp, x,
        jnp.arange(T))
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "from the argument" not in ln]
    assert len(lines) == len(kept), lines
    if kind[1] == "e":
        shapes = sorted(ln.split()[0] for ln in lines)
        assert f"f32[{B * T},16]" in shapes and f"i32[{B * T},2]" in shapes
        assert sum("'router_choice'" in ln for ln in lines) == 1
        assert not any(f"[{n}," in ln for ln in lines
                       for n in (32, B * T * 2))
    if kind[0] == "a":
        assert sum("pallas_kernels.py" in ln for ln in lines) == 2


def test_rematerialisation_changes_no_gradient():
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(lm_loss)(params, batch, cfg)
        got = jax.value_and_grad(lm_loss)(
            params, batch, dataclasses.replace(cfg, remat=True))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5,
                            atol=1e-6 * float(jnp.abs(b).max()))


# ----------------------------------------------------------- the share
@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(mixer):
    """16 experts in 8 shares of 2: what each share's experts give, with
    the mixer's step and the residual (which every chip computes alike)
    counted once, adds up to what the uncut reference gives for the whole
    layer."""
    whole = _cfg(layers=1, mixers=(mixer,), dense_layers=0,
                 experts_count=16, experts_offset=0)
    params = _params(whole)
    bp = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, whole.hidden))
    want = jnp.stack([ref.layer(bp, xb, _sizes(whole))[0] for xb in x])
    parts, rows = [], []
    with jax.default_matmul_precision("highest"):
        mix = conv_decoder._conv_mixer if mixer == "conv" \
            else conv_decoder._attend
        h = mix(bp, x, jnp.arange(T), whole)
        for offset in range(0, 16, 2):
            cfg = dataclasses.replace(whole, experts_count=2,
                                      experts_offset=offset,
                                      vocab_size=V // 8)
            mine = _share_of(params, cfg)
            assert mine["blocks"][0]["experts"]["gate"].shape[0] == 2
            assert mine["tok_emb"].shape == (V // 8, whole.hidden)
            out, counters = conv_decoder._block(
                mine["blocks"][0], x, jnp.arange(T), cfg.kinds[0], cfg)
            # each share against the reference given the same share
            alone = jnp.stack([ref.layer(mine["blocks"][0], xb,
                                         _sizes(cfg))[0] for xb in x])
            assert jnp.allclose(out, alone, atol=2e-5, rtol=2e-5)
            parts.append(out - h)
            rows.append(int(counters["choices_here"]))
    assert sum(rows) == B * T * whole.experts_per_token
    assert jnp.allclose(h + sum(parts), want, atol=5e-5, rtol=5e-5)
    # one share alone is not the layer
    assert not jnp.allclose(h + parts[0], want, atol=1e-2)


def test_a_share_holds_what_param_pspecs_shards():
    """Cutting exactly the axes ``param_pspecs`` names gives the shapes
    ``init_params`` makes for the share; a mesh is refused by name."""
    cfg = _cfg(vocab_size=V // 8, experts_offset=6)
    whole = dataclasses.replace(cfg, vocab_size=V, experts_count=16,
                                experts_offset=0)
    mine = _share_of(_params(whole), cfg)
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
        cut = [i for i, (m, n) in enumerate(zip(a.shape, b.shape)) if m != n]
        named = [i for i, axis in enumerate(spec) if axis is not None]
        assert cut == named, (spec, a.shape, b.shape)
    with pytest.raises(NotImplementedError, match="all-to-all"):
        lm_loss(mine, _batch(), cfg, mesh=object())


def test_the_benchmarks_share_has_the_stated_parameter_count():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        config = json.load(f)
    from benchmarks.lib import model

    cfg = ConvDecoderConfig(**model.sizes(config, False))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 486_062_464 == config["deployment"]["parameters"]
    assert cfg.kinds == ("cd", "ae", "ce", "ce", "ce")
    assert cfg.experts_held == (0, 8) and cfg.experts_total == 64
    assert cfg.head_dim == 64 == cfg.hidden // cfg.heads
    assert moe_decoder._rung(4 * 8192, cfg.experts_per_token, 8, 64) \
        == 4 * 8192
    # every width is the source's
    published = ConvDecoderConfig()
    for field in ("hidden", "heads", "kv_heads", "head_dim", "mlp_dim",
                  "expert_dim", "experts_per_token", "conv_kernel",
                  "experts_total", "rope_theta", "rms_eps"):
        assert getattr(cfg, field) == getattr(published, field), field


# ------------------ the two routed decoders' programs stay what they were
def _grouped_ffn_of_the_parent(xs, experts, sizes):
    """``moe_decoder._grouped_ffn`` before the activation became an
    argument (PR 33), line for line."""
    gate, up, down = (experts[n].astype(xs.dtype)
                      for n in ("gate", "up", "down"))
    h = jax.nn.relu(moe_decoder._grouped_matmul(xs, gate, sizes)) \
        * moe_decoder._grouped_matmul(xs, up, sizes)
    return moe_decoder._grouped_matmul(h, down, sizes)


def _causal_conv_of_the_parent(x, w, b):
    K, T_ = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(padded[:, j:j + T_] * w[j] for j in range(K))


def _route_of_the_parent(s, bias, cfg):
    _, top_e = jax.lax.top_k(s + bias, cfg.experts_per_token)
    top_e = jax.ad_checkpoint.checkpoint_name(top_e, "router_choice")
    top_s = jnp.where(top_e[:, :, None] == jnp.arange(s.shape[-1]),
                      s[:, None, :], 0.0).sum(-1)
    if cfg.norm_topk_prob:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_e, top_s * cfg.routed_scale


@pytest.mark.parametrize("family", ["routed", "hybrid", "hybrid_rung"])
def test_the_routed_decoders_steps_lower_to_the_same_text(
        monkeypatch, family):
    """What this family shares and changed to share: the experts' body
    takes its activation as an argument, the convolution an optional bias,
    the sigmoid/bias router an optional 1e-6. With the parent's functions
    in their place the routed-expert decoder's and the hybrid decoder's
    train steps (with and without a rung) lower to the same text."""
    if family == "routed":
        cfg, batch = hybrid_tests._routed_cfg(), hybrid_tests._batch()
    else:
        cfg = hybrid_tests._cfg(
            remat=True, experts_total=64 if family == "hybrid_rung" else 16)
        batch = hybrid_tests._batch()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))

    def text():
        init, step = make_train_step(cfg)
        return step.lower(shapes, jax.eval_shape(init, shapes),
                          batch).as_text()

    shared = text()
    monkeypatch.setattr(moe_decoder, "_grouped_ffn",
                        _grouped_ffn_of_the_parent)
    monkeypatch.setattr(hybrid_decoder, "_causal_conv",
                        _causal_conv_of_the_parent)
    monkeypatch.setattr(hybrid_decoder, "_route", _route_of_the_parent)
    assert shared == text()
    assert "stablehlo" in shared and len(shared) > 100_000
