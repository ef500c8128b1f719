"""The causal decoder with routed experts (``models/moe_decoder.py``) against
its plain reference (``benchmarks/references/routed_expert_decoder.py``) at
tiny sizes on seeded weights: logits, loss and gradients; the window mask;
positions on rotary and position-free layers; grouped-query heads; routing
that drops nothing; how the expert layer moves its rows (five gathers a
layer, a combine with its own backward, backward views slot-major) against
the plain formula kept here; and the share test — the parts of a layer's result that
all the shares of its experts give add up to the uncut layer."""
import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import routed_expert_decoder as ref
from deeplearning4j_tpu import models
from deeplearning4j_tpu.models import (
    MoEDecoderConfig, forward, init_params, lm_loss, make_train_step,
    moe_decoder, param_pspecs)
from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES
from deeplearning4j_tpu.profiler import OpProfiler, ProfilerConfig
from tests.test_trace_names import _eqns, _pallas_names

B, T, V = 2, 32, 128


@pytest.fixture(autouse=True)
def _no_x64():
    """The suite turns x64 on (tests/conftest.py); the interpreter of the
    grouped-matmul kernel (megablox, a JAX library) mixes int32 grid indices
    with default-width integers and needs it off, as on the chip."""
    with jax.enable_x64(False):
        yield


def _cfg(**kw):
    base = dict(vocab_size=V, hidden=32, layers=4, heads=4, kv_heads=2,
                head_dim=8, expert_dim=16, experts_total=8,
                experts_per_token=2, experts_count=4, experts_offset=2,
                window=16, max_seq=64, attention_impl="flash",
                dtype=jnp.float32, remat=False)
    return MoEDecoderConfig(**dict(base, **kw))


def _sizes(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _params(cfg, seed=0, scale=5.0):
    """Seeded weights, the matrices scaled up so that the router's choices
    are sharp and every term is far from rounding."""
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
    for got, wanted in zip(jax.tree.leaves(got_grads),
                           jax.tree.leaves(want_grads)):
        assert jnp.allclose(got, wanted, rtol=2e-4,
                            atol=2e-5 * float(jnp.abs(wanted).max()))


def test_bfloat16_compute_stays_within_the_stated_tolerance():
    """What the benchmark's ``correct`` compares, at tiny size: loss over
    all positions, logits where no top-k choice is close."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params, batch = _params(cfg, scale=1.0), _batch()
    got = forward(params, batch["tokens"], cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    loss, counters = moe_decoder.lm_loss_and_counters(params, batch, cfg)
    chosen = np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
    flipped = (chosen != np.asarray(want["chosen"])).any((0, 3))
    gap = np.asarray(jnp.abs(got - want["logits"]).max(-1))
    assert flipped.mean() < 0.2 and gap[~flipped].max() < 0.02
    # a choice differs only where the reference says it was close
    assert not flipped.any() or want["margin"][flipped].max() < 0.05
    assert abs(float(loss) - float(want["loss"])) < 1e-3 * float(want["loss"])


@pytest.mark.parametrize("window,t", [(16, 64), (64, 64), (100, 64)],
                         ids=["T_over_window", "T_is_window",
                              "T_under_window"])
def test_window_mask(window, t):
    """Key j is visible to query i iff 0 <= i - j < window, on the window
    layers only; at T <= window the band is the whole triangle."""
    cfg = _cfg(window=window)
    params, batch = _params(cfg), _batch(t=t)
    with jax.default_matmul_precision("highest"):
        got = forward(params, batch["tokens"], cfg)
        every = forward(params, batch["tokens"],
                        dataclasses.replace(cfg, window=10 ** 6))
    want = ref.logits_at(params, batch["tokens"], _all(t), _sizes(cfg))
    assert jnp.allclose(got, want, atol=2e-5, rtol=2e-5)
    # the first ``window`` positions see everything either way
    assert jnp.allclose(got[:, :window], every[:, :window], atol=1e-5)
    assert (window >= t) == bool(jnp.allclose(got, every, atol=1e-5))


def test_positions_move_a_rotary_layer_and_not_a_position_free_one():
    """A layer without positional encoding never reads ``positions``. A
    rotary layer reads their differences: shifting every position changes
    nothing (RoPE is relative), shifting the second half of the sequence
    against the first does."""
    cfg = _cfg(layers=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    at = jnp.arange(T)
    all_shifted = at + 7
    half_shifted = at + 7 * (at >= T // 2)
    for rope in (0, 1):
        one = dataclasses.replace(cfg, rope_layout=(rope,),
                                  window_layout=(1,))
        bp = _params(one)["blocks"][0]
        base, _ = moe_decoder._block(bp, x, at, 0, one)
        same, _ = moe_decoder._block(bp, x, all_shifted, 0, one)
        other, _ = moe_decoder._block(bp, x, half_shifted, 0, one)
        assert jnp.allclose(base, same, atol=1e-4)
        assert bool(jnp.allclose(base, other, atol=1e-4)) == (rope == 0)


def test_grouped_query_heads_equal_repeated_keys_and_values():
    cfg = _cfg()
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, 4, T, 8))
    k, v = (jax.random.normal(kk, (B, 2, T, 8)) for kk in ks[1:])
    wide = dataclasses.replace(cfg, kv_heads=4)
    for window in (None, 16):
        got = moe_decoder._attention(q, k, v, window, cfg)
        want = moe_decoder._attention(q, jnp.repeat(k, 2, 1),
                                      jnp.repeat(v, 2, 1), window, wide)
        assert jnp.allclose(got, want, atol=1e-6)


# ------------------------------------- what a block's checkpoint keeps
def _bare_encode(params, token_ids, cfg, positions=None):
    """``moe_decoder.encode`` under the parent's ``jax.checkpoint``, which
    keeps nothing but each block's inputs."""
    if positions is None:
        positions = jnp.arange(token_ids.shape[1])
    with jax.default_matmul_precision("default"):
        x, counters = params["tok_emb"][token_ids], []
        for layer, bp in enumerate(params["blocks"]):
            x, c = jax.checkpoint(functools.partial(
                moe_decoder._block, layer=layer, cfg=cfg))(bp, x, positions)
            counters.append(c)
        x = moe_decoder._rmsnorm(x, params["ln_f"], cfg.rms_eps)
    return x, jax.tree.map(lambda *c: jnp.stack(c), *counters)


@pytest.mark.parametrize("remat,forwards", [(True, 1), (False, 1),
                                            ("bare", 2)])
def test_the_streamed_forward_runs_once_a_layer_in_the_gradient(
        monkeypatch, remat, forwards):
    """The block's checkpoint keeps the kernel's output and logsumexp, so
    its replay holds no ``flash_fwd``; a checkpoint that keeps nothing (the
    parent's) runs the kernel again. The backward is one kernel a layer,
    the fused ``flash_bwd_dkv``, and no ``flash_bwd_dq``."""
    if remat == "bare":
        monkeypatch.setattr(moe_decoder, "encode", _bare_encode)
    cfg = _cfg(remat=bool(remat))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: lm_loss(p, b, cfg)))(
        _params(cfg), _batch())
    calls = collections.Counter(_pallas_names(jaxpr.jaxpr))
    assert calls["flash_fwd"] == forwards * cfg.layers
    assert calls["flash_bwd_dkv"] == cfg.layers
    assert calls["flash_bwd_dq"] == 0


@pytest.mark.parametrize("impl", ["flash", "full"])
def test_keeping_the_kernels_results_changes_no_bit(monkeypatch, impl):
    """Loss and every gradient leaf under the block's checkpoint are those
    of a checkpoint that keeps nothing: the backward kernels read the
    ``out`` and ``lse`` the first forward wrote, not an equal second copy."""
    cfg = _cfg(attention_impl=impl, remat=True)
    params, batch = _params(cfg), _batch()
    got = jax.value_and_grad(lm_loss)(params, batch, cfg)
    monkeypatch.setattr(moe_decoder, "encode", _bare_encode)
    want = jax.value_and_grad(lm_loss)(params, batch, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_a_block_keeps_its_inputs_and_what_attention_made(capsys):
    """``print_saved_residuals`` of one block under ``encode``'s policy: the
    block's arguments, then q, k and v in the kernels' layout, the kernel's
    output, and the logsumexp under its name. The other four read "output
    of reduce_precision" at the line that named them: JAX puts a same-width
    ``reduce_precision`` on a kept value that the block goes on to use.
    Nothing with the expert buffer's rows is kept, and a policy without a
    name does not keep its value."""
    cfg = _cfg(remat=True)
    bp, at = _params(cfg)["blocks"][1], jnp.arange(T)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    blk = functools.partial(moe_decoder._block, layer=1, cfg=cfg)

    def kept_by(*names):
        ck = jax.checkpoint(
            blk, policy=jax.checkpoint_policies.save_only_these_names(
                *names))
        jax.ad_checkpoint.print_saved_residuals(
            lambda bp_, x_, at_: ck(bp_, x_, at_)[0].sum(), bp, x, at)
        lines = capsys.readouterr().out.splitlines()
        return [ln for ln in lines if "from the argument" not in ln]

    def shape(heads):
        return f"f32[{B},{heads},{T},{cfg.head_dim}]"

    lse = FLASH_SAVED_NAMES[1]
    q, kv = shape(cfg.heads), shape(cfg.kv_heads)
    kept = kept_by(*FLASH_SAVED_NAMES, *moe_decoder._QKV_NAMES)
    shapes = {src: sorted(ln.split()[0] for ln in kept if src in ln)
              for src in ("moe_decoder.py", "pallas_kernels.py")}
    assert shapes["moe_decoder.py"] == sorted([q, kv, kv])
    assert shapes["pallas_kernels.py"] == sorted(
        [q, f"f32[{B * cfg.heads},1,{T}]"])
    assert len(kept) == 5 and sum(f"named '{lse}'" in ln for ln in kept) == 1
    rows = B * T * cfg.experts_per_token
    assert not any(f"[{rows}," in ln for ln in kept)
    # each name keeps its own value and no other
    assert kept_by(*FLASH_SAVED_NAMES) == [
        ln for ln in kept if "pallas_kernels.py" in ln]
    assert kept_by(lse) == [ln for ln in kept if lse in ln]
    assert kept_by() == []


# ---------------------------------------------------------------- routing
def test_routing_drops_nothing_when_every_token_picks_the_same_experts():
    """The worst case the buffer is sized for: all tokens x experts_per_token
    choices land on held experts."""
    cfg = _cfg(experts_total=8, experts_count=4, experts_offset=2,
               experts_per_token=2, layers=1)
    bp = _params(cfg)["blocks"][0]
    n = B * T
    m = jax.random.normal(jax.random.PRNGKey(6), (n, cfg.hidden))
    r = jnp.tile(jnp.asarray([0., 0, 0, 3, 2, 0, 0, 0])[None], (n, 1))
    out, counters = moe_decoder._experts(bp, m, r, cfg)
    assert counters["rows_per_expert"].tolist() == [0, n, n, 0]
    assert int(counters["choices_here"]) == 2 * n
    assert int(counters["tokens_without_expert"]) == 0
    with jax.default_matmul_precision("highest"):
        want, _, chosen = ref._experts(m, r, bp["experts"], 2, 2, True)
        out, _ = moe_decoder._experts(bp, m, r, cfg)
    assert jnp.allclose(out, want, atol=1e-5, rtol=1e-5)
    assert (counters["chosen"] == chosen).all() \
        and chosen[0].tolist() == [3, 4]
    # and the other extreme: nobody picks a held expert
    r = jnp.tile(jnp.asarray([3., 2, 0, 0, 0, 0, 0, 0])[None], (n, 1))
    out, counters = moe_decoder._experts(bp, m, r, cfg)
    assert int(counters["choices_here"]) == 0 and not out.any()
    assert int(counters["tokens_without_expert"]) == n


def test_the_step_returns_counters_a_span_can_carry():
    cfg = _cfg(remat=True)
    params, batch = _params(cfg), _batch()
    init, step = make_train_step(cfg)
    _, _, loss, counters = step(params, init(params), batch)
    assert np.isfinite(float(loss))
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (cfg.layers, cfg.experts_count)
    assert (rows.sum(1) == np.asarray(counters["choices_here"])).all()
    assert (np.asarray(counters["choices_here"]) <= B * T * 2).all()
    assert (np.asarray(counters["tokens_without_expert"]) < B * T).all()
    # no rung at 4 of 8 held: every layer ran on the whole buffer
    assert counters["buffer_rows"].tolist() == [B * T * 2] * cfg.layers
    prof = OpProfiler(ProfilerConfig())
    with prof.span("train.step", choices_here=int(
            counters["choices_here"].sum()),
            buffer_rows=int(counters["buffer_rows"].max())):
        pass
    assert prof.spans[-1].args["choices_here"] == rows.sum()
    assert prof.spans[-1].args["buffer_rows"] == B * T * 2


# ------------------------------------------- how the layer moves its rows
@jax.custom_vjp
def _plain_take_rows(x, rows, back):
    del back
    return x[rows]


_plain_take_rows.defvjp(
    lambda x, rows, back: (x[rows], back),
    lambda back, g: (g[back.reshape(-1)].reshape(
        back.shape + g.shape[1:]).sum(1), None, None))


def _plain_experts(bp, m, r, cfg):
    """The plain reference of the layer's row movement: the choices
    flattened token-major (``token * k + slot``), both gathers with a
    gather-and-sum transpose, and the weighted sum an einsum over a
    (N, k, H) view that keeps the gathered rows for the weights' gradient
    (the formulation before PR 31). Same choices, rows and weights."""
    N, H = m.shape
    k = cfg.experts_per_token
    off, held = cfg.experts_held
    top_e, top_w = moe_decoder._route(r, cfg)
    local = top_e - off
    here = (local >= 0) & (local < held)
    weight = jnp.where(here, top_w, 0.0)
    group = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32)).reshape(N, k)
    sizes = (group[None, :] == jnp.arange(held + 1)[:, None]).sum(
        1, dtype=jnp.int32)
    xs = _plain_take_rows(m.astype(cfg.dtype), order // k, back)
    ys = moe_decoder._grouped_ffn(xs, bp["experts"], sizes)
    picked = _plain_take_rows(ys, back.reshape(-1), order[:, None])
    out = jnp.einsum("nkh,nk->nh", picked.reshape(N, k, H), weight,
                     preferred_element_type=jnp.float32)
    return out, {"rows_per_expert": sizes[:held],
                 "choices_here": sizes[:held].sum(),
                 "tokens_without_expert": N - here.any(-1).sum(),
                 "chosen": jnp.sort(jnp.where(here, top_e, -1), axis=-1)}


def _gradient_eqns(cfg):
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: lm_loss(p, b, cfg)))(
        _params(cfg), _batch())
    return list(_eqns(jaxpr.jaxpr))


def _shapes(eqns):
    return {tuple(v.aval.shape) for eqn in eqns
            for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape")}


@pytest.mark.parametrize("formula,per_layer,from_buffer", [
    ("own_backward", 5, 1), ("plain", 6, 3)])
def test_a_layer_gathers_its_routed_rows_five_times_in_the_gradient(
        monkeypatch, formula, per_layer, from_buffer):
    """Forward dispatch and combine, the replayed dispatch, and the two
    backward rules. The plain formula's einsum keeps the gathered rows for
    the weights' gradient, so its replay gathers them a sixth time. A
    block's backward equation (its replay and its transposed rules) reads
    the buffer once, in the dispatch's backward: the replay holds no
    combine, and the combine's backward reads the cotangent's rows (the
    plain formula: the gather back again, and the combine's cotangent).
    That equation views the routed rows (k, N, H) and never (N, k, H); the
    first pass keeps the plain formula's view (PERF.md section 6, PR 31)."""
    if formula == "plain":
        monkeypatch.setattr(moe_decoder, "_experts", _plain_experts)
    cfg = _cfg(remat=True, experts_per_token=3)
    rows, k, H = B * T * 3, 3, cfg.hidden

    def row_gathers(eqns_):
        return [e for e in eqns_ if e.primitive.name == "gather"
                and e.outvars[0].aval.shape == (rows, H)]

    eqns = _gradient_eqns(cfg)
    assert len(row_gathers(eqns)) == per_layer * cfg.layers
    backward = [e for e in eqns if e.primitive.name in ("checkpoint",
                                                        "remat", "remat2")]
    assert len(backward) == cfg.layers
    for eqn in backward:
        inner = list(_eqns(eqn.params["jaxpr"]))
        sources = sorted(g.invars[0].aval.shape[0]
                         for g in row_gathers(inner))
        # all of a layer's gathers but the first pass's two
        assert sources == [B * T] * (per_layer - 2 - from_buffer) \
            + [rows] * from_buffer
        shapes = _shapes(inner)
        assert ((B * T, k, H) in shapes) == (formula == "plain")
        assert ((k, B * T, H) in shapes) == (formula == "own_backward")


@pytest.mark.parametrize("remat", [False, True])
def test_own_backward_rules_give_the_plain_formulas_loss_and_gradients(
        monkeypatch, remat):
    cfg = _cfg(remat=remat, experts_per_token=3)
    params, batch = _params(cfg), _batch()
    both = jax.value_and_grad(moe_decoder.lm_loss_and_counters, has_aux=True)
    with jax.default_matmul_precision("highest"):
        (got_loss, got_counters), got_grads = both(params, batch, cfg)
        monkeypatch.setattr(moe_decoder, "_experts", _plain_experts)
        (want_loss, want_counters), want_grads = both(params, batch, cfg)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert jnp.allclose(a, b, rtol=1e-5,
                            atol=1e-6 * float(jnp.abs(b).max()))
    for name, value in want_counters.items():
        assert (np.asarray(got_counters[name]) == np.asarray(value)).all()


@pytest.mark.parametrize("k", [2, 6])
@pytest.mark.parametrize("held", ["every", "none", "mixed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_combines_own_backward_is_the_plain_formulas(k, held, dtype):
    """``_combine``'s rule against ``jax.grad`` of the plain formula
    (``ys[back]`` viewed (N, k, H), an einsum with the weights): the rows'
    cotangent, rounded to the compute dtype once, and the weights' gradient,
    which is zero for a choice held elsewhere (its row of the buffer holds
    zeros, as the grouped products leave it)."""
    N, H, groups = 24, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(k), 5)
    share = {"every": 1.0, "none": 0.0, "mixed": 0.5}[held]
    here = jax.random.uniform(ks[0], (N, k)) < share
    group = jnp.where(here, jax.random.randint(ks[1], (N, k), 0, groups),
                      groups).reshape(-1)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    weight = jnp.where(here, jax.random.uniform(ks[2], (N, k)), 0.0)
    ys = jax.random.normal(ks[3], (N * k, H))
    ys = jnp.where((group[order] < groups)[:, None], ys, 0.0).astype(dtype)
    d_out = jax.random.normal(ks[4], (N, H))

    def plain(ys_, weight_):
        picked = ys_[back.reshape(-1)].reshape(N, k, H)
        return jnp.einsum("nkh,nk->nh", picked, weight_,
                          preferred_element_type=jnp.float32)

    want_out, want_vjp = jax.vjp(plain, ys, weight)
    got_out, got_vjp = jax.vjp(
        lambda ys_, weight_: moe_decoder._combine(ys_, weight_, order, back),
        ys, weight)
    assert got_out.dtype == jnp.float32 and (got_out == want_out).all()
    (got_ys, got_w), (want_ys, want_w) = got_vjp(d_out), want_vjp(d_out)
    assert got_ys.dtype == dtype and got_w.dtype == jnp.float32
    if dtype == jnp.bfloat16:       # one rounding, after the multiplication
        assert (got_ys == want_ys).all()
    else:
        assert jnp.allclose(got_ys, want_ys, rtol=1e-6, atol=1e-7)
    assert jnp.allclose(got_w, want_w, rtol=1e-5, atol=1e-5)
    assert not got_w[~here].any() and not want_w[~here].any()
    assert bool(got_w.any()) == (held != "none")


@pytest.mark.parametrize("k", [2, 6])
def test_the_dispatchs_own_backward_sums_each_tokens_rows(k):
    """``_dispatch``'s rule (the buffer's rows gathered slot by slot and
    summed over the leading axis) against the scatter-add ``jax.grad``
    makes of the plain gather."""
    N, H = 24, 16
    ks = jax.random.split(jax.random.PRNGKey(k), 3)
    order = jnp.argsort(jax.random.randint(ks[0], (N * k,), 0, 4),
                        stable=True).astype(jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    x, g = jax.random.normal(ks[1], (N, H)), jax.random.normal(ks[2],
                                                               (N * k, H))
    want = jax.vjp(lambda x_: x_[order // k], x)[1](g)[0]
    got = jax.vjp(lambda x_: moe_decoder._dispatch(x_, order, back), x)[1](g)
    assert jnp.allclose(got[0], want, rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------- the rung
@pytest.mark.parametrize("tokens,k,count,total,rung", [
    (16_384, 22, 8, 512, 16_384),   # the hybrid decoder's share: 1/8
    (16_384, 6, 16, 64, None),      # the routed-expert decoder's: 1/2 is none
    (32, 6, 4, 64, 32),             # the cases below: 1/4, the largest
    (64, 2, 4, 8, None), (64, 3, 4, 8, None)])    # this file's ``_cfg``
def test_the_rung_follows_from_the_shapes(tokens, k, count, total, rung):
    """The smallest power-of-two fraction of ``tokens x min(k, count)`` rows
    that holds twice what a uniform router sends, where that is a quarter
    of the worst case or less: 16,384 of 131,072 rows at the benchmark's
    hybrid share (2 x 5,632 wanted), none at its routed-expert share
    (49,152 of 98,304 is a half)."""
    assert moe_decoder._rung(tokens, k, count, total) == rung
    if rung is not None:
        full, sent = tokens * min(k, count), tokens * k * count / total
        assert 2 * sent <= rung <= full // 4 and rung // 2 < 2 * sent


def _choices(tokens, k, total, held, routed, seed=0):
    """Each token's ``k`` different experts, ``routed`` of all the choices
    on a held expert (the first tokens take every held one), in a shuffled
    order, and positive weights."""
    off, count = held
    rng = np.random.default_rng(seed)
    away = [e for e in range(total) if not off <= e < off + count]
    top_e = np.empty((tokens, k), np.int32)
    for n in range(tokens):
        here = min(count, max(0, routed - n * count))
        row = list(range(off, off + here)) \
            + [away[(n + j) % len(away)] for j in range(k - here)]
        top_e[n] = rng.permutation(row)
    return jnp.asarray(top_e), jnp.asarray(
        rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32))


def _plain_routed(m, top_e, top_w, held, experts):
    """The layer's formula with no buffer: every held expert's ReGLU on
    every row, weighted by the token's weight for it, or zero."""
    out = 0.0
    for e in range(held[1]):
        w = jnp.where(top_e == held[0] + e, top_w, 0.0).sum(-1)
        h = jax.nn.relu(m @ experts["gate"][e]) * (m @ experts["up"][e])
        out = out + w[:, None] * (h @ experts["down"][e])
    return out


@pytest.mark.parametrize("routed,buffer_rows", [
    (0, 32), (12, 32), (31, 32),      # fewer than the rung: the small route
    (32, 128), (100, 128)])           # the rung or more: the whole buffer
def test_either_route_is_the_plain_formula_and_the_counter_says_which(
        monkeypatch, routed, buffer_rows):
    """32 tokens take 6 of 64 experts, 4 held: a rung of 32 rows under 128.
    Output, counters and the gradients of rows, weights and the experts'
    matrices from the route the count picks, from the whole buffer (no
    rung) and from the plain formula; the boundary is ``routed == rung``,
    which keeps no row of the "none" group and so does not fit."""
    tokens, k, total, held, H, F = 32, 6, 64, (4, 4), 16, 24
    assert moe_decoder._rung(tokens, k, held[1], total) == 32
    top_e, top_w = _choices(tokens, k, total, held, routed)
    ks = jax.random.split(jax.random.PRNGKey(routed), 5)
    m, d_out = (jax.random.normal(k_, (tokens, H)) for k_ in ks[:2])
    experts = {n: jax.random.normal(k_, shape) * 0.3 for n, k_, shape in zip(
        ("gate", "up", "down"), ks[2:],
        [(held[1], H, F), (held[1], H, F), (held[1], F, H)])}

    def layer(m_, top_w_, experts_):
        return moe_decoder.routed_experts(
            m_, top_e, top_w_, held, total, jnp.float32,
            moe_decoder._grouped_ffn, experts_)

    def run():
        with jax.default_matmul_precision("highest"):
            out, pull, counters = jax.vjp(layer, m, top_w, experts,
                                          has_aux=True)
            return out, counters, pull(d_out)

    got_out, got_counters, got_grads = run()
    assert int(got_counters["buffer_rows"]) == buffer_rows
    assert int(got_counters["choices_here"]) == routed
    monkeypatch.setattr(moe_decoder, "_rung", lambda *a: None)
    full_out, full_counters, full_grads = run()
    assert int(full_counters.pop("buffer_rows")) == 128
    # (the CPU's products round by the tile's rows, 32 here against 128;
    # on the chip both buffers are tiled by 512)
    assert jnp.allclose(got_out, full_out, rtol=1e-6, atol=1e-6)
    for name, value in full_counters.items():
        assert (np.asarray(got_counters[name]) == np.asarray(value)).all()
    with jax.default_matmul_precision("highest"):
        want_out, pull = jax.vjp(
            lambda *a: _plain_routed(a[0], top_e, a[1], held, a[2]),
            m, top_w, experts)
        want_grads = pull(d_out)
    assert jnp.allclose(got_out, want_out, rtol=1e-5, atol=1e-5)
    for got, full, want in zip(*map(jax.tree.leaves,
                                    (got_grads, full_grads, want_grads))):
        assert jnp.isfinite(got).all()
        assert jnp.allclose(got, full, rtol=1e-5, atol=1e-5)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- the share test
def test_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Expert parallelism's cut against the model: the four shares (experts
    0-3, 4-7, 8-11, 12-15 of 16) of one layer on the same input, with what
    every chip computes alike (the residual and attention) counted once, add
    up to the uncut reference's layer."""
    whole = _cfg(experts_total=16, experts_count=16, experts_offset=0,
                 experts_per_token=3, layers=1, window_layout=(1,),
                 rope_layout=(1,))
    bp = _params(whole)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, whole.hidden))
    at = jnp.arange(T)
    sizes = _sizes(whole)
    want, y = zip(*(ref.layer(bp, seq, at, 0, sizes)[:2] for seq in x))
    want, y = jnp.stack(want), jnp.stack(y)
    total, rows = y, 0
    with jax.default_matmul_precision("highest"):
        for off in (0, 4, 8, 12):
            share = dataclasses.replace(whole, experts_count=4,
                                        experts_offset=off)
            held = dict(bp, experts=jax.tree.map(
                lambda a: a[off:off + 4], bp["experts"]))
            out, counters = moe_decoder._block(held, x, at, 0, share)
            # the share's own reference gives the same partial result
            part = jnp.stack([ref.layer(held, seq, at, 0, _sizes(share))[0]
                              for seq in x])
            assert jnp.allclose(out, part, atol=2e-5, rtol=2e-5)
            total = total + (out - y)
            rows += int(counters["choices_here"])
    assert rows == B * T * 3            # every choice landed on one share
    assert jnp.allclose(total, want, atol=5e-5, rtol=5e-5)


# ------------------------------------------------------- the shared entry
def test_one_entry_point_serves_both_families():
    moe, dense = _cfg(), models.TransformerConfig(
        vocab_size=V, hidden=32, layers=1, heads=2, mlp_dim=64, max_seq=T)
    for cfg in (moe, dense):
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        specs = param_pspecs(cfg)
        assert jax.tree.structure(params) == jax.tree.structure(
            specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))
        assert models.forward(params, _batch()["tokens"], cfg).shape \
            == (B, T, V)
    expert = param_pspecs(moe)["blocks"][0]["experts"]["gate"]
    assert expert[0] == moe_decoder.EXPERT_AXIS
    with pytest.raises(NotImplementedError):
        lm_loss(_params(moe), _batch(), moe, mesh=object())
