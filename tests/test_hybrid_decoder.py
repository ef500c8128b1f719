"""The hybrid state-space / attention / latent-expert decoder
(``models/hybrid_decoder.py``) against its plain reference
(``benchmarks/references/hybrid_ssm_expert_decoder.py``) at tiny sizes on
seeded weights: logits, loss and gradients; the chunked scan against the
recurrence one position at a time; the sigmoid/bias router; routing that
drops nothing in a buffer of ``tokens x min(k, held)`` rows; what a block's
checkpoint keeps; the share test — the parts that all the shares of a layer
give, by heads and by experts, add up to the uncut layer, for a layer of
each kind; and that factoring ``moe_decoder._experts`` left the routed-expert
decoder's step the program it was."""
import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec

from benchmarks.references import hybrid_ssm_expert_decoder as ref
from deeplearning4j_tpu import models
from deeplearning4j_tpu.models import (
    ConvDecoderConfig, HybridDecoderConfig, MoEDecoderConfig,
    TransformerConfig, forward,
    hybrid_decoder, init_params, lm_loss, make_train_step, moe_decoder,
    param_pspecs)
from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
from tests.test_trace_names import _eqns, _pallas_names

B, T, V = 2, 32, 128


@pytest.fixture(autouse=True)
def _no_x64():
    """The suite turns x64 on (tests/conftest.py); the interpreter of the
    grouped-matmul kernel (megablox, a JAX library) needs it off, as on the
    chip."""
    with jax.enable_x64(False):
        yield


def _cfg(**kw):
    base = dict(vocab_size=V, hidden=32, layers=5, pattern="MEM*E",
                mamba_heads=4, mamba_head_dim=8, mamba_groups=2,
                state_dim=16, chunk=8, heads=4, kv_heads=2, head_dim=8,
                latent_dim=16, expert_dim=24, shared_dim=32,
                experts_total=16, experts_per_token=6, experts_count=4,
                experts_offset=4, max_seq=64, attention_impl="flash",
                dtype=jnp.float32, remat=False)
    return HybridDecoderConfig(**dict(base, **kw))


def _routed_cfg(**kw):
    """The routed-expert decoder with more experts held than a token takes
    (4 of 8 at 2): no rung, a slot is a choice."""
    base = dict(vocab_size=V, hidden=32, layers=4, heads=4, kv_heads=2,
                head_dim=8, expert_dim=16, experts_total=8,
                experts_per_token=2, experts_count=4, experts_offset=2,
                window=16, max_seq=64)
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


# ------------------------------------------------- program against reference
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
        assert jnp.allclose(got, wanted, rtol=2e-4,
                            atol=2e-5 * float(jnp.abs(wanted).max()))


def test_bfloat16_compute_stays_within_the_stated_tolerance():
    """What the benchmark's ``correct`` compares, at tiny size: loss over
    all positions, logits where no held choice differs."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params, batch = _params(cfg, scale=1.0), _batch()
    got = forward(params, batch["tokens"], cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    loss, counters = hybrid_decoder.lm_loss_and_counters(params, batch, cfg)
    chosen = np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
    assert chosen.shape == (2, B, T, cfg.experts_per_token)
    flipped = (chosen != np.asarray(want["chosen"])).any((0, 3))
    gap = np.asarray(jnp.abs(got - want["logits"]).max(-1))
    assert flipped.mean() < 0.2 and gap[~flipped].max() < 0.03
    # a choice differs only where the reference says it was close
    assert not flipped.any() or want["margin"][flipped].max() < 0.05
    assert abs(float(loss) - float(want["loss"])) < 1e-3 * float(want["loss"])


# ------------------------------------------------------- the chunked scan
def _recurrence(X, delta, A, Bm, Cm):
    """``h_t = exp(delta_t A) h_(t-1) + delta_t X_t (x) B_t``,
    ``y_t = h_t C_t``, one position at a time, for one sequence:
    X (T, heads, P), delta (T, heads), Bm and Cm (T, groups, N)."""
    heads = X.shape[1]
    Bh, Ch = (jnp.repeat(t, heads // t.shape[1], axis=1) for t in (Bm, Cm))

    def position(h, step):
        x, d, b, c = step
        h = jnp.exp(d * A)[:, None, None] * h \
            + (d[:, None] * x)[:, :, None] * b[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c)

    start = jnp.zeros((heads, X.shape[2], Bm.shape[2]), jnp.float32)
    return lax.scan(position, start, (X, delta, Bh, Ch))[1]


def _scan_inputs(t, strength, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads, P, G, N = 4, 8, 2, 16
    X = jax.random.normal(ks[0], (B, t, heads, P))
    Bm, Cm = (jax.random.normal(k, (B, t, G, N)) for k in ks[1:3])
    delta = jax.nn.softplus(jax.random.normal(ks[3], (B, t, heads)))
    A = -strength * jax.random.uniform(ks[4], (heads,), minval=0.5, maxval=2)
    cot = jax.random.normal(ks[5], (B, t, heads, P))
    return (X, delta, A, Bm, Cm), cot


@pytest.mark.parametrize("t,chunk,strength", [
    (8, 8, 0.3), (32, 8, 0.3), (32, 8, 30.0), (27, 8, 0.3)],
    ids=["one_chunk", "four_chunks", "strong_decay", "padded_T"])
def test_the_chunked_scan_is_the_recurrence_values_and_gradients(
        t, chunk, strength):
    """At one chunk the carried state is never read and at several it is.
    With a decay of up to exp(-60) a position the mask has to come from
    differences of a float32 cumulative sum: the factored form
    exp(cum_l) / exp(cum_s) underflows inside one chunk. A T that is no
    multiple of the chunk is **padded**, with delta = 0, and cut back: the
    positions that exist read as without padding."""
    args, cot = _scan_inputs(t, strength)

    def got_fn(*a):
        return hybrid_decoder._ssd(*a, chunk)

    def want_fn(X, delta, A, Bm, Cm):
        return jax.vmap(_recurrence, (0, 0, None, 0, 0))(X, delta, A, Bm, Cm)

    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(got_fn, *args)
        want, want_vjp = jax.vjp(want_fn, *args)
        assert got.shape == want.shape == (B, t, 4, 8)
        assert np.isfinite(np.asarray(got)).all()
        assert jnp.allclose(got, want, rtol=1e-4,
                            atol=1e-5 * float(jnp.abs(want).max()))
        for g, w in zip(got_vjp(cot), want_vjp(cot)):
            assert np.isfinite(np.asarray(g)).all()
            assert jnp.allclose(g, w, rtol=1e-4,
                                atol=2e-5 * float(jnp.abs(w).max()))


def test_a_sequence_that_is_no_multiple_of_the_chunk_runs_whole():
    """Through the model: T = 27 on chunks of 8 gives the first 27
    positions of the same tokens at T = 32 (the model is causal)."""
    cfg = _cfg()
    params, tok = _params(cfg), _batch()["tokens"]
    with jax.default_matmul_precision("highest"):
        short = forward(params, tok[:, :27], cfg)
        whole = forward(params, tok, cfg)
    assert jnp.allclose(short, whole[:, :27], atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ router
def test_the_bias_changes_the_choice_and_not_the_weights():
    cfg = _cfg()
    n, k = 64, cfg.experts_per_token
    s = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2),
                                         (n, cfg.experts_total)))
    none = jnp.zeros((cfg.experts_total,))
    bias = jnp.zeros((cfg.experts_total,)).at[3].set(10.0).at[0].set(-10.0)
    plain_e, plain_w = hybrid_decoder._route(s, none, cfg)
    e, w = hybrid_decoder._route(s, bias, cfg)
    # expert 3 is chosen by every token and expert 0 by none
    assert (e == 3).any(-1).all() and not (e == 0).any()
    assert (plain_e == 0).any() and not (plain_e == 3).any(-1).all()
    # the weights are the scores of the chosen, normalised, times the scale
    top_s = jnp.take_along_axis(s, e, -1)
    assert jnp.allclose(w, cfg.routed_scale * top_s
                        / top_s.sum(-1, keepdims=True), rtol=1e-6)
    assert jnp.allclose(w.sum(-1), cfg.routed_scale, rtol=1e-6)
    assert jnp.allclose(plain_w.sum(-1), cfg.routed_scale, rtol=1e-6)
    # a token that keeps its choice keeps its weights
    same = (jnp.sort(e, -1) == jnp.sort(plain_e, -1)).all(-1)
    order, plain_order = jnp.argsort(e, -1), jnp.argsort(plain_e, -1)
    assert jnp.allclose(jnp.take_along_axis(w, order, -1)[same],
                        jnp.take_along_axis(plain_w, plain_order, -1)[same])
    loose = dataclasses.replace(cfg, norm_topk_prob=False)
    assert jnp.allclose(hybrid_decoder._route(s, bias, loose)[1],
                        cfg.routed_scale * top_s)
    assert k == e.shape[1]


@pytest.mark.parametrize("held", [4, 16])
def test_the_whole_model_trains_its_router_and_a_share_does_not(held):
    """A share alone has the held experts' terms of the router's gradient
    and nothing for the absent ones: applied alone it only teaches the
    router to choose the experts held here. So a share's scores carry no
    gradient, in the program and in the reference alike; the whole model's
    router learns through the weights of the chosen experts."""
    cfg = _cfg(experts_count=held, experts_offset=0 if held == 16 else 4)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lm_loss)(params, batch, cfg)
    want = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    for kind, g, w in zip(cfg.kinds, got["blocks"], want["blocks"]):
        if kind != "E":
            continue
        assert not g["router_bias"].any() and not w["router_bias"].any()
        if held < cfg.experts_total:
            assert not g["router"].any() and not w["router"].any()
        else:
            assert float(jnp.abs(w["router"]).max()) > 0
            assert jnp.allclose(g["router"], w["router"], rtol=2e-4,
                                atol=2e-5 * float(jnp.abs(w["router"]).max()))
        # what the experts held here learn does not wait for the router
        assert g["experts"]["w2"].any() and g["down"].any()


def test_routing_drops_nothing_when_every_token_picks_the_same_experts():
    """The worst case the buffer is sized for: every token takes all the
    held experts, ``tokens x min(k, held)`` rows. With the router's matrix
    zero every score is 0.5 and the bias alone chooses."""
    cfg = _cfg(layers=1, pattern="E")          # holds experts 4-7, k = 6
    bp = _params(cfg)["blocks"][0]
    n = B * T
    u = jax.random.normal(jax.random.PRNGKey(6), (n, cfg.hidden))
    bias = jnp.zeros((16,)).at[jnp.asarray([4, 5, 6, 7, 0, 1])].set(1.0)
    bp = dict(bp, router=jnp.zeros_like(bp["router"]), router_bias=bias)
    with jax.default_matmul_precision("highest"):
        routed, shared, counters = hybrid_decoder._expert_parts(bp, u, cfg)
        want, _, chosen = ref.layer(dict(bp, ln={"scale": jnp.ones(32)}),
                                    u, "E", _sizes(cfg))
    assert counters["rows_per_expert"].tolist() == [n] * 4
    assert int(counters["choices_here"]) == n * min(6, 4)
    assert int(counters["tokens_without_expert"]) == 0
    assert (counters["chosen"] == chosen).all() \
        and chosen[0].tolist() == [-1, -1, 4, 5, 6, 7]
    # the reference normalises u again: feed it rows of unit mean square
    unit = u / jnp.sqrt((u * u).mean(-1, keepdims=True) + cfg.rms_eps)
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = hybrid_decoder._expert_parts(bp, unit, cfg)
    assert jnp.allclose(u + routed + shared, want, atol=1e-4, rtol=1e-4)
    # and the other extreme: nobody picks a held expert
    bp = dict(bp, router_bias=jnp.zeros((16,)).at[:4].set(1.0).at[8:10]
              .set(1.0))
    routed, _, counters = hybrid_decoder._expert_parts(bp, unit, cfg)
    assert int(counters["choices_here"]) == 0 and not routed.any()
    assert int(counters["tokens_without_expert"]) == n


@pytest.mark.parametrize("held,per_token,slots", [(4, 6, 4), (8, 3, 3),
                                                  (6, 6, 6)])
def test_the_buffer_has_tokens_times_min_of_k_and_held_rows(
        held, per_token, slots):
    cfg = _cfg(layers=1, pattern="E", experts_count=held, experts_offset=2,
               experts_per_token=per_token)
    bp = _params(cfg)["blocks"][0]
    n = B * T
    u = jax.random.normal(jax.random.PRNGKey(7), (n, cfg.hidden))
    jaxpr = jax.make_jaxpr(
        lambda bp_, u_: hybrid_decoder._expert_parts(bp_, u_, cfg)[0])(bp, u)
    rows = {v.aval.shape[0] for eqn in jaxpr.jaxpr.eqns
            for v in eqn.outvars
            if getattr(v.aval, "shape", ()) [1:] == (cfg.expert_dim,)}
    assert rows == {n * slots}
    x = u.reshape(B, T, -1)
    with jax.default_matmul_precision("highest"):
        got, counters = hybrid_decoder._block(bp, x, "E", cfg)
        want = jnp.stack([ref.layer(bp, xb, "E", _sizes(cfg))[0]
                          for xb in x])
    assert jnp.allclose(got, want, atol=1e-4, rtol=1e-4)
    assert counters["chosen"].shape == (n, per_token)


def test_the_step_returns_counters_stacked_over_the_expert_layers():
    cfg = _cfg(remat=True)
    params, batch = _params(cfg), _batch()
    init, step = make_train_step(cfg)
    _, _, loss, counters = step(params, init(params), batch)
    assert np.isfinite(float(loss))
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (cfg.kinds.count("E"), cfg.experts_count)
    assert (rows.sum(1) == np.asarray(counters["choices_here"])).all()
    assert (rows <= B * T).all()
    assert np.asarray(counters["chosen"]).shape == (2, B * T, 6)
    assert counters["buffer_rows"].tolist() == [B * T * 4] * 2      # no rung


# ----------------------------------------------------------------- the rung
def _held_bias(cfg, forced: bool):
    """A selection bias that sends every token to the held experts, or to
    none of them; the weights still come from the scores."""
    off, count = cfg.experts_held
    bias = jnp.zeros((cfg.experts_total,))
    return bias.at[off:off + count].set(1.0 if forced else -1.0)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("router,buffer_rows", [
    ("seeded", 64), ("all_held", 256), ("none_held", 64)])
def test_a_share_with_a_rung_is_the_reference_on_either_route(
        monkeypatch, remat, router, buffer_rows):
    """4 of 64 experts held at 6 a token: 64 tokens have a rung of 64 rows
    under the buffer's 256. Loss, counters and gradients against the plain
    reference and against the program without a rung: with the seeded
    router (about 24 rows a layer: the small route), with a bias that sends
    every token to the 4 held experts (256 rows: the whole buffer) and with
    one that sends none."""
    cfg = _cfg(experts_total=64, remat=remat)
    assert moe_decoder._rung(B * T, 6, 4, 64) == 64
    params, batch = _params(cfg), _batch()
    if router != "seeded":
        for kind, bp in zip(cfg.kinds, params["blocks"]):
            if kind == "E":
                bp["router_bias"] = _held_bias(cfg, router == "all_held")
    both = jax.value_and_grad(hybrid_decoder.lm_loss_and_counters,
                              has_aux=True)
    with jax.default_matmul_precision("highest"):
        (got_loss, counters), got_grads = both(params, batch, cfg)
        monkeypatch.setattr(moe_decoder, "_rung", lambda *a: None)
        (full_loss, full_counters), full_grads = both(params, batch, cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    want_grads = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    assert counters["buffer_rows"].tolist() == [buffer_rows] * 2
    assert full_counters.pop("buffer_rows").tolist() == [256] * 2
    routed = np.asarray(counters["choices_here"])
    assert ((routed < 64) == (buffer_rows == 64)).all()
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


def _rows_of(var):
    shape = getattr(var.aval, "shape", ())
    return shape[0] if len(shape) > 1 else None


def _outside_kernels(jaxpr):
    """The equations of a jaxpr and of the jaxprs they hold, but for the
    kernels' own bodies."""
    kernels = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    inside = {id(e) for k in kernels for e in _eqns(k.params["jaxpr"])}
    return [e for e in _eqns(jaxpr) if id(e) not in inside]


def _conditionals(cfg, params, batch):
    """The ``cond`` equations of the train step's gradient outside its
    kernels (a kernel's own ``pl.when`` is one too, and off the chip the
    interpreter lowers a kernel's grid to ``case``, so the lowered text
    cannot be counted here; compiled for a described v5e the benchmark's
    share holds 10 ``stablehlo.case`` and the routed-expert decoder's step
    none: PERF.md section 6, PR 33)."""
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm_loss(p, batch, cfg)))(params)
    return [e for e in _outside_kernels(jaxpr.jaxpr)
            if e.primitive.name == "cond"]


@pytest.mark.parametrize("family", ["hybrid", "routed"])
def test_a_layer_without_a_rung_has_no_conditional(family):
    """The routed-expert decoder's share (16 of 64 held at 6 a token in the
    benchmark, 4 of 8 at 2 here) and this family's at 4 of 16: the program
    is the one it was."""
    cfg = _cfg(remat=True) if family == "hybrid" else _routed_cfg(layers=2)
    off, count = cfg.experts_held
    assert moe_decoder._rung(B * T, cfg.experts_per_token, count,
                             cfg.experts_total) is None
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert _conditionals(cfg, params, _batch()) == []


def test_a_rung_is_two_conditionals_a_layer_and_no_buffer_in_the_small_one():
    """The train step of a share with a rung holds one conditional for each
    expert layer and direction. In the branch that runs where the count
    fits, nothing has the buffer's rows at the experts' inner width, no
    kernel and no gather reads or writes an array of the buffer's rows, and
    the only array of ``tokens x slots`` rows that is no index array is the
    picks that the combine (forward) or the dispatch's backward gathers
    *out of* the rung's rows; the other branch is the layer as it was."""
    cfg = _cfg(experts_total=64, remat=True)
    layers, tokens, full, rung = cfg.kinds.count("E"), B * T, B * T * 4, 64
    conds = _conditionals(cfg, _params(cfg), _batch())
    assert len(conds) == 2 * layers
    for cond in conds:
        whole, small = (list(_eqns(b.jaxpr)) for b in cond.params["branches"])
        for eqns, rows in ((whole, full), (small, rung)):
            wide = {_rows_of(v) for e in eqns for v in e.outvars
                    if getattr(v.aval, "shape", ())[1:] == (cfg.expert_dim,)}
            assert wide - {cfg.latent_dim} == {rows}     # less one expert's W1
            at_kernels = {_rows_of(v) for e in eqns
                          if e.primitive.name == "pallas_call"
                          for v in (*e.invars, *e.outvars)}
            assert rows in at_kernels and at_kernels & {full, rung} == {rows}
        picks = [e for e in small for v in e.outvars
                 if _rows_of(v) == full
                 and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        # (the backward's branch traces the combine's forward too, which
        # nothing reads: the compiled branch holds the dispatch's alone)
        assert picks and all(e.primitive.name == "gather"
                             and _rows_of(e.invars[0]) == rung for e in picks)
        assert all(_rows_of(e.invars[0]) in (None, tokens, rung)
                   for e in small if e.primitive.name == "gather")


@pytest.mark.parametrize("remat", [True, False])
def test_the_streamed_kernels_run_once_an_attention_layer(remat):
    """The gradient holds, for each attention layer, one ``flash_fwd`` (the
    block's checkpoint keeps its results) and one fused ``flash_bwd_dkv``,
    which makes dq too: no ``flash_bwd_dq``."""
    cfg = _cfg(remat=remat)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: lm_loss(p, b, cfg)))(
        _params(cfg), _batch())
    calls = collections.Counter(_pallas_names(jaxpr.jaxpr))
    attention_layers = cfg.kinds.count("*")
    assert attention_layers == 1
    assert calls["flash_fwd"] == calls["flash_bwd_dkv"] == attention_layers
    assert calls["flash_bwd_dq"] == 0


# ------------------------------------- what a block's checkpoint keeps
_ROUTED = {"route_weight": 1, "route_order": 1, "route_back": 1,
           "route_sizes": 1, "latent_rows": 1, "shared_hidden": 1,
           "moe_part": 1}


@pytest.mark.parametrize("kind,kept,kw", [
    ("M", {"ssm_projected": 1, "ssm_taps": 1}, {}),
    ("E", _ROUTED, {}),
    ("E", _ROUTED, {"experts_total": 64}),
    ("E", dict(_ROUTED, router_logits=1, router_choice=1),
     {"experts_count": 16, "experts_offset": 0}),
    ("*", {"attn": 3, "flash": 2}, {})],
    ids=["M", "E", "E-rung", "E-whole", "*"])
def test_a_block_keeps_its_input_and_what_is_named(capsys, kind, kept, kw):
    """``print_saved_residuals`` of one block under ``encode``'s policy:
    the block's arguments and, by kind: the input projection's float32
    result, the convolution's taps before their silu and nothing of the
    scan; of an expert layer the weights of the held choices, the sort by
    expert, its inverse and the group sizes (``moe_decoder._ROUTE_NAMES``),
    the latent rows, the shared expert's hidden rows before their relu and
    the combined latent rows ``W_up`` reads; q, k, v and the kernel's output
    and logsumexp. A share's
    backward reads the router's logits and choice no longer (they made the
    weights, which are kept, and a share's weights carry no gradient), the
    whole model's does. Nothing else: every kept value is made in
    ``_expert_parts``, ``_route`` or ``routed_experts`` before a row moves,
    none has the experts' inner width, and no floating-point array has the
    expert buffer's rows (the rung's, where the layer has one, are the
    tokens' 64 here: 64 of 256 at 4 of 64 held; its conditional keeps the
    layer's inputs, which the replay no longer makes again)."""
    cfg = _cfg(remat=True, layers=1, pattern=kind, **kw)
    bp = _params(cfg)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    ck = jax.checkpoint(
        functools.partial(hybrid_decoder._block, kind=kind, cfg=cfg),
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_SAVED_NAMES, *moe_decoder._QKV_NAMES,
            *hybrid_decoder._KEPT_NAMES))
    jax.ad_checkpoint.print_saved_residuals(
        lambda bp_, x_: ck(bp_, x_)[0].sum(), bp, x)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "from the argument" not in ln]
    assert len(lines) == sum(kept.values())
    assert set(kept) - {"attn", "flash"} <= set(
        hybrid_decoder._KEPT_NAMES)
    # a kept value that the block goes on to use reads "output of
    # reduce_precision" at the line that named it (tests/test_moe_decoder.py)
    if kind == "M":
        widths = hybrid_decoder._mamba_widths(cfg)
        conv = sum(widths[n] for n in "xBC")
        assert sorted(ln.split()[0] for ln in lines) == sorted(
            [f"f32[{B},{T},{sum(widths.values())}]", f"f32[{B},{T},{conv}]"])
        assert all("(_mamba)" in ln for ln in lines)
    if kind == "E":
        N, total = B * T, cfg.experts_total
        slots = min(cfg.experts_per_token, cfg.experts_count)
        whole = cfg.experts_count == total
        assert sorted(ln.split()[0] for ln in lines) == sorted(
            [f"f32[{N},{cfg.latent_dim}]"] * 2       # latent rows in, out
            + [f"f32[{N},{cfg.shared_columns}]", f"f32[{N},{slots}]",
               f"i32[{N * slots}]", f"i32[{N},{slots}]",
               f"i32[{cfg.experts_count + 1}]"]
            + [f"f32[{N},{total}]", f"i32[{N},6]"] * whole)
        assert sum("'router_choice'" in ln for ln in lines) == whole
        for name in ("route_order", "route_back", "route_sizes"):
            assert sum(f"'{name}'" in ln for ln in lines) == 1
        assert all(ln.rstrip().endswith(
            ("(_expert_parts)", "(_route)", "(routed_experts)"))
            for ln in lines)
        assert not any(ln.startswith(("f32", "bf16"))
                       and (f"[{N * slots}," in ln
                            or f",{cfg.expert_dim}]" in ln) for ln in lines)
        assert (moe_decoder._rung(N, 6, cfg.experts_count, total) is None) \
            == (total == 16)
    if kind == "*":
        assert len(lines) == 5
        assert sum("pallas_kernels.py" in ln for ln in lines) == 2


@pytest.mark.parametrize("total", [16, 64], ids=["no-rung", "rung"])
def test_a_replay_makes_no_kept_value_again(monkeypatch, total):
    """The gradient's jaxpr of a rematerialised step holds, for each expert
    layer, one sort of ``tokens x slots`` keys, one inverse-permutation
    scatter of as many indices, one ``W_down`` and one shared ``W_1``
    product, and for each state-space layer one product of the input
    projection's width: the forward's. Under the list of PR 32 (the
    router's logits and choice, the combined latent rows) each stood
    twice, the second in the block's replay."""
    cfg = _cfg(remat=True, experts_total=total)
    params, batch = _params(cfg), _batch()
    N, slots = B * T, min(cfg.experts_per_token, cfg.experts_count)
    proj = sum(hybrid_decoder._mamba_widths(cfg).values())

    def counts():
        eqns = _outside_kernels(jax.make_jaxpr(jax.grad(
            lambda p: lm_loss(p, batch, cfg)))(params).jaxpr)

        def made(primitive, out, operand=None):
            return sum(e.primitive.name == primitive
                       and e.outvars[0].aval.shape == out
                       and operand in (None, e.invars[0].aval.shape)
                       for e in eqns)
        return {"sort": made("sort", (N * slots,)),
                "scatter": made("scatter", (N * slots,)),
                "ssm_in": made("dot_general", (B, T, proj)),
                "w_down": made("dot_general", (N, cfg.latent_dim),
                               (N, cfg.hidden)),
                "shared_w1": made("dot_general", (N, cfg.shared_columns),
                                  (N, cfg.hidden))}

    experts, mixers = cfg.kinds.count("E"), cfg.kinds.count("M")
    once = counts()
    assert (once["sort"], once["scatter"], once["ssm_in"]) \
        == (experts, experts, mixers)
    monkeypatch.setattr(hybrid_decoder, "_KEPT_NAMES",
                        ("router_logits", "router_choice", "moe_part"))
    twice = counts()
    assert (twice["sort"], twice["scatter"], twice["ssm_in"]) \
        == (2 * experts, 2 * experts, 2 * mixers)
    # the backward's own products have these shapes too (the cotangent
    # through ``W_up`` and through the shared ``W_2``): the replay's is one
    for name in ("w_down", "shared_w1"):
        assert twice[name] - once[name] == experts


@pytest.mark.parametrize("total", [16, 64], ids=["no-rung", "rung"])
def test_rematerialisation_changes_no_gradient(total):
    cfg = _cfg(experts_total=total)
    assert (moe_decoder._rung(B * T, 6, 4, total) is None) == (total == 16)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(lm_loss)(params, batch, cfg)
        got = jax.value_and_grad(lm_loss)(
            params, batch, dataclasses.replace(cfg, remat=True))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5,
                            atol=1e-6 * float(jnp.abs(b).max()))


# ----------------------------------------------------------- the share
def _whole_cfg(kind):
    """One uncut layer: 8 state-space heads in 4 groups, 8 query heads on 2
    key/value heads, a shared expert of 32 columns, 16 experts."""
    return _cfg(layers=1, pattern=kind, mamba_heads=8, mamba_groups=4,
                heads=8, kv_heads=2, shared_dim=32, experts_count=16,
                experts_offset=0)


def _share_cfg(kind, rank=0, offset=0):
    """A quarter of it by heads, an eighth of its experts."""
    return _cfg(layers=len(kind), pattern=kind, mamba_heads=2, mamba_groups=1,
                heads=2, kv_heads=1, kv_heads_total=2, shared_dim=32,
                model_share=4, model_rank=rank, vocab_size=V // 4,
                experts_count=2, experts_offset=offset)


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(kind):
    """Heads split 4 ways (state-space heads and groups, query heads with a
    key/value head replicated over the two ranks that read it, the shared
    expert's columns) and 16 experts in 8 shares of 2: the parts all shares
    give, the residual counted once and what every chip computes alike
    (norm, router, latent projections) once, add up to what the uncut
    reference gives."""
    whole = _whole_cfg(kind)
    assert _share_cfg(kind, 3, 6).whole == dataclasses.replace(
        whole, vocab_size=V)
    params = _params(dataclasses.replace(whole, vocab_size=V))
    bp = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, whole.hidden))
    want = jnp.stack([ref.layer(bp, xb, kind, _sizes(whole))[0] for xb in x])
    parts = []
    with jax.default_matmul_precision("highest"):
        if kind == "E":
            u = moe_decoder._rmsnorm(x, bp["ln"], whole.rms_eps).reshape(
                B * T, -1)
            rows = []
            for offset in range(0, 16, 2):
                cfg = _share_cfg(kind, 0, offset)
                mine = hybrid_decoder.share_of(params, cfg)["blocks"][0]
                assert mine["experts"]["w1"].shape[0] == 2
                routed, _, counters = hybrid_decoder._expert_parts(
                    mine, u, cfg)
                parts.append(routed)
                rows.append(int(counters["choices_here"]))
            assert sum(rows) == B * T * whole.experts_per_token
            for rank in range(4):
                cfg = _share_cfg(kind, rank)
                mine = hybrid_decoder.share_of(params, cfg)["blocks"][0]
                assert mine["shared"]["w1"].shape == (32, 8)
                parts.append(hybrid_decoder._expert_parts(mine, u, cfg)[1])
            got = x + sum(parts).reshape(x.shape)
        else:
            for rank in range(4):
                cfg = _share_cfg(kind, rank)
                mine = hybrid_decoder.share_of(params, cfg)["blocks"][0]
                out, _ = hybrid_decoder._block(mine, x, kind, cfg)
                # each share against the reference given the same share
                alone = jnp.stack([ref.layer(mine, xb, kind, _sizes(cfg))[0]
                                   for xb in x])
                assert jnp.allclose(out, alone, atol=2e-5, rtol=2e-5)
                parts.append(out - x)
            got = x + sum(parts)
    assert jnp.allclose(got, want, atol=5e-5, rtol=5e-5)
    # one share alone is not the layer
    assert not jnp.allclose(x + parts[0].reshape(x.shape), want, atol=1e-2)


def test_a_share_holds_what_param_pspecs_shards():
    """``share_of`` cuts exactly the axes ``param_pspecs`` names, to the
    shapes ``init_params`` makes for the share; a mesh is refused by
    name."""
    cfg = _share_cfg("MEM*E", 2, 6)
    whole = cfg.whole
    assert whole.model_share == 1 and whole.heads == 8 \
        and whole.kv_heads == 2 and whole.mamba_groups == 4 \
        and whole.experts_held == (0, 16) and whole.vocab_size == V
    mine = hybrid_decoder.share_of(_params(whole), cfg)
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
    with pytest.raises(NotImplementedError, match="all-reduce"):
        lm_loss(mine, _batch(), cfg, mesh=object())


def test_the_benchmarks_share_has_the_stated_parameter_count():
    cfg = HybridDecoderConfig(
        vocab_size=16384, layers=11, pattern="MEMEMEMEM*E", mamba_heads=16,
        mamba_groups=1, heads=4, kv_heads=1, kv_heads_total=2,
        experts_count=8, model_share=8)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 508_189_680
    assert cfg.kinds.count("M") == cfg.kinds.count("E") == 5
    assert cfg.shared_columns == 672 and cfg.whole.mamba_heads == 128
    assert HybridDecoderConfig().kinds.count("*") == 8


# ------------------------------- the routed-expert decoder's program stays
def _experts_before_factoring(bp, m, r, cfg):
    """``moe_decoder._experts`` as it stood before ``routed_experts`` was
    factored out of it (PR 31), line for line, with the one counter the
    layer has gained since and the names of its index arrays and weights,
    which ``moe_decoder.encode``'s policy keeps."""
    N = m.shape[0]
    k = cfg.experts_per_token
    off, held = cfg.experts_held
    with jax.named_scope("router"):
        top_e, top_w = moe_decoder._route(r, cfg)
        local = top_e - off
        here = (local >= 0) & (local < held)
        weight = jnp.where(here, top_w, 0.0)
    with jax.named_scope("moe_dispatch"):
        group = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32), unique_indices=True,
            mode="promise_in_bounds").reshape(N, k)
        sizes = (group[None, :] == jnp.arange(held + 1)[:, None]).sum(
            1, dtype=jnp.int32)
        weight, order, back, sizes = map(
            jax.ad_checkpoint.checkpoint_name, (weight, order, back, sizes),
            moe_decoder._ROUTE_NAMES)
        xs = moe_decoder._dispatch(m.astype(cfg.dtype), order, back)
    with jax.named_scope("experts"):
        ys = moe_decoder._grouped_ffn(xs, bp["experts"], sizes)
    with jax.named_scope("moe_combine"):
        out = moe_decoder._combine(ys, weight, order, back)
    counters = {"rows_per_expert": sizes[:held],
                "choices_here": sizes[:held].sum(),
                "buffer_rows": jnp.int32(N * k),   # PR 33's, a constant here
                "tokens_without_expert": N - here.any(-1).sum(),
                "chosen": jnp.sort(jnp.where(here, top_e, -1), axis=-1)}
    return out, counters


def test_the_routed_expert_decoders_step_lowers_to_the_same_text(
        monkeypatch):
    """More experts held than a token takes (16 of 64 at 6 a token in the
    benchmark, 4 of 8 at 2 here): a slot is a choice, the buffer has
    ``tokens x k`` rows, and the train step is the text it was, **up to the
    counter in the names of JAX's private functions**: equations that lower
    to nothing can number the functions traced after them higher
    (``@sort_287`` and ``@sort_291`` are one function). With the counter
    stripped the two texts are equal line for line."""
    cfg = _routed_cfg()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))

    def text():
        init, step = make_train_step(cfg)
        lowered = step.lower(shapes, jax.eval_shape(init, shapes),
                             _batch()).as_text()
        return re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1", lowered)

    factored = text()
    monkeypatch.setattr(moe_decoder, "_experts", _experts_before_factoring)
    assert factored == text()
    assert "stablehlo" in factored and len(factored) > 100_000
    assert "@sort(" in factored and "@sort_" not in factored


@pytest.mark.parametrize("family", ["routed", "convolution"])
def test_the_other_routed_families_keep_what_they_kept(family, monkeypatch):
    """``routed_experts`` serves three families and names its index arrays
    and weights for whoever lists them: this family does, and so does
    ``moe_decoder.encode``; ``conv_decoder.encode`` does not. Neither of the
    two lists the names this family gave its own layers, so their blocks
    keep exactly what they kept (a policy ignores a name it does not
    list)."""
    own = {"latent_rows", "shared_hidden", "ssm_projected", "ssm_taps"}
    new = {*moe_decoder._ROUTE_NAMES, *own}
    assert new <= set(hybrid_decoder._KEPT_NAMES)
    listed = []
    real = jax.checkpoint_policies.save_only_these_names
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: listed.extend(names) or real(*names))
    cfg = _family_cfg(family)
    jax.eval_shape(lambda p: models.family_of(cfg).loss_and_aux(
        p, _batch(), cfg), jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)))
    route = set(moe_decoder._ROUTE_NAMES) if family == "routed" else set()
    assert listed and new & set(listed) == route
    assert set(listed) >= {*FLASH_SAVED_NAMES, *moe_decoder._QKV_NAMES}


def _family_cfg(family):
    if family == "transformer":
        return TransformerConfig(vocab_size=V, hidden=32, layers=1, heads=4,
                                 mlp_dim=64, max_seq=64, causal=True,
                                 dtype=jnp.float32)
    if family == "routed":
        return MoEDecoderConfig(vocab_size=V, hidden=32, layers=1, heads=4,
                                kv_heads=2, head_dim=8, expert_dim=16,
                                experts_total=8, experts_per_token=2,
                                window=16, max_seq=64, dtype=jnp.float32)
    if family == "hybrid":
        return _cfg()
    return ConvDecoderConfig(vocab_size=V, hidden=32, layers=2,
                             mixers=("conv", "full_attention"),
                             dense_layers=1, heads=4, kv_heads=2, mlp_dim=64,
                             expert_dim=16, experts_total=8,
                             experts_per_token=2, max_seq=64,
                             dtype=jnp.float32)


@pytest.mark.parametrize("family", ["transformer", "routed", "hybrid",
                                    "convolution"])
def test_one_entry_point_serves_all_four_families(family):
    batch, cfg = _batch(), _family_cfg(family)
    assert models.family_of(cfg) is not None
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.structure(
        param_pspecs(cfg),
        is_leaf=lambda s: isinstance(s, PartitionSpec)) \
        == jax.tree.structure(params)
    assert forward(params, batch["tokens"], cfg).shape == (B, T, V)
    assert np.isfinite(float(lm_loss(params, batch, cfg)))
    init, step = make_train_step(cfg)
    out = step(params, init(params), batch)
    assert np.isfinite(float(out[2]))
    # a family with routed experts returns its counters fourth
    assert len(out) == (3 if family == "transformer" else 4)
