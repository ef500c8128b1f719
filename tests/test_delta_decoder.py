"""The gated delta-rule / gated attention decoder with routed SwiGLU experts
beside a shared expert (``models/delta_decoder.py``) against its plain
reference (``benchmarks/references/delta_expert_decoder.py``) at tiny sizes
on seeded weights: the chunked scan against the recurrence one position at a
time, values and gradients, at decays that overflow a whole-chunk
``exp(G) exp(-G)``; logits, loss and gradients, uncut and on a share; the
shares of a layer, by heads and by experts, adding up to the uncut layer;
``beta`` in (0, 2); the one entry point with its counters; the names a trace
and a checkpoint read."""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from benchmarks.references import delta_expert_decoder as ref
from deeplearning4j_tpu import models
from deeplearning4j_tpu.models import (
    DeltaDecoderConfig, delta_decoder, forward, init_params, lm_loss,
    make_train_step, moe_decoder, param_pspecs)
from deeplearning4j_tpu.ops.pallas_kernels import FLASH_SAVED_NAMES

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
    """A share like the benchmark's, small: rank 1 of 2 by heads (1 of 2
    delta-rule heads, 2 of 4 query heads on 1 of 2 kv heads, half the shared
    expert's columns), 2 of 16 experts at 2 a token (128 tokens have a rung
    of 64 rows), an attention layer then a delta-rule layer (the step's test
    runs the benchmark's four), two chunks of two sub-blocks a sequence."""
    base = dict(vocab_size=V, hidden=32, layers=2, attention_layers=(0,),
                delta_heads=1, delta_head_dim=8, chunk=32, heads=2,
                kv_heads=1, kv_heads_total=2, head_dim=8, expert_dim=24,
                shared_dim=32, experts_total=16, experts_count=2,
                experts_offset=4, experts_per_token=2, model_share=2,
                model_rank=1, max_seq=64, attention_impl="flash",
                dtype=jnp.float32, remat=False)
    return DeltaDecoderConfig(**dict(base, **kw))


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


# ------------------------------------------ the chunked scan, satellite (1)
def _scan_inputs(t, strength, heads=2, d=8, seed=0):
    """q, k, v, g (1, heads, t, d) and beta (1, heads, t) as the mixer makes
    them: unit keys, scaled unit queries, ``g`` down to ``-strength`` a
    position and channel, ``beta`` over all of (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(kk, (1, heads, t, d)) for kk in ks[:3])
    g = -strength * jax.random.uniform(ks[3], (1, heads, t, d))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (1, heads, t)))
    return (delta_decoder._l2norm(q) * d ** -0.5, delta_decoder._l2norm(k),
            v, g, beta)


def _by_positions(q, k, v, g, beta):
    """The reference's recurrence on the program's layout."""
    seq = lambda a: a[0].transpose(1, 0, 2)          # noqa: E731
    return ref.delta_rule(seq(q), seq(k), seq(v), seq(g),
                          beta[0].T).transpose(1, 0, 2)[None]


@pytest.mark.parametrize("t,chunk,strength", [
    (37, 8, 0.1),       # a chunk below a sub-block, five chunks, padded
    (50, 16, 0.5),      # one sub-block a chunk
    (70, 32, 8.0),      # two sub-blocks; exp(-G) overflows inside a chunk
    (130, 64, 8.0),     # the benchmark's chunk, padded; G reaches -4000
    (64, 64, 40.0)])    # a single chunk whose every ratio underflows
def test_the_chunked_scan_is_the_recurrence_values_and_gradients(
        t, chunk, strength):
    args = _scan_inputs(t, strength)
    chunked = functools.partial(delta_decoder._delta_rule, chunk=chunk)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (1, 2, t, 8))

    def both(scan):
        return jax.jit(lambda *a: (scan(*a), jax.grad(
            lambda *b: (scan(*b) * weigh).sum(), tuple(range(5)))(*a)))

    with jax.default_matmul_precision("highest"):
        (got, got_g), (want, want_g) = both(chunked)(*args), \
            both(_by_positions)(*args)
    assert got.shape == want.shape == (1, 2, t, 8)
    assert jnp.allclose(got, want, atol=2e-5, rtol=2e-5)
    for a, b in zip(got_g, want_g):
        assert jnp.isfinite(a).all() and _close(a, b)
    if strength >= 8.0:
        # what the reference points are for: over a whole chunk the
        # factored form exp(G_r) * exp(-G_i) is inf * 0 in float32
        G = jnp.cumsum(args[3][:, :, :chunk], axis=2)
        assert jnp.isinf(jnp.exp(-G)).any() and (jnp.exp(G) == 0).any()
        assert float(jnp.abs(want).max()) > 0.05


def test_the_solve_and_the_state_one_precision_lower_are_seen():
    """With the triangular solve and the carried state in bfloat16 the scan
    is a hundred times further from the recurrence than in float32. The
    benchmark's ``correct`` does not see that difference (PERF.md section 6,
    PR 38): this test is what holds a kernel for the scan to float32."""
    args = _scan_inputs(130, 0.5)
    with jax.default_matmul_precision("highest"):
        want = _by_positions(*args)
        exact = delta_decoder._delta_rule(*args, 64)
        lower = delta_decoder._delta_rule(*args, 64,
                                          state_dtype=jnp.bfloat16)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(exact - want).max()) < 2e-5 * scale
    assert float(jnp.abs(lower - want).max()) > 2e-3 * scale


def test_beta_above_one_flips_the_sign_of_what_the_state_holds():
    """Satellite (4): with ``beta`` in (1, 2) the transition
    ``I - beta k k^T`` has the eigenvalue ``1 - beta`` < 0 along ``k``. The
    seeded mixer reaches it, and the program with its factor 2 taken off is
    another function than the reference."""
    cfg = _cfg(layers=1, attention_layers=())
    params, batch = _params(cfg), _batch()
    bp = params["blocks"][0]
    x = params["tok_emb"][batch["tokens"]]
    u = moe_decoder._rmsnorm(x, bp["ln1"], cfg.rms_eps)
    beta = 2.0 * jax.nn.sigmoid(u @ bp["beta"])
    assert float(beta.max()) > 1.2 and float(beta.min()) < 0.8
    mixer = jax.jit(delta_decoder._delta_mixer, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        got = mixer(bp, x, cfg)
        halved = mixer(bp, x, dataclasses.replace(cfg, neg_eigval=False))
        want = jnp.stack([
            xb + ref._delta_mixer(bp, ub, cfg.rms_eps, True)
            for xb, ub in zip(x, u)])
    assert jnp.allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(halved - want).max()) \
        > 1e-2 * float(jnp.abs(want - x).max())
    # one head, one key, no decay: writing v and reading it back with the
    # same key gives beta v, and a second write of zero leaves
    # (1 - beta) beta v: the sign flips where beta > 1
    k = jnp.zeros((1, 1, 2, 8)).at[..., 0].set(1.0)
    v = jnp.stack([jnp.ones((8,)), jnp.zeros((8,))])[None, None]
    o = delta_decoder._delta_rule(k, k, v, jnp.zeros_like(k),
                                  jnp.full((1, 1, 2), 1.5), 8)
    assert jnp.allclose(o[0, 0, 0], 1.5) and jnp.allclose(o[0, 0, 1], -0.75)


# ------------------------------- program against reference, satellite (2)
def _program(cfg):
    """Logits, and loss with its gradients, as one compiled program."""
    return jax.jit(lambda p, b: (
        forward(p, b["tokens"], cfg),
        jax.value_and_grad(lm_loss)(p, b, cfg)))


@pytest.mark.parametrize("share,impl,remat", [
    ("share", "flash", False), ("share", "flash", True),
    ("share", "full", True), ("uncut", "flash", True)])
def test_float32_logits_loss_and_gradients_match_the_reference(
        share, impl, remat):
    cfg = _cfg(attention_impl=impl, remat=remat)
    if share == "uncut":
        cfg = cfg.whole
        assert cfg.delta_heads == 2 and cfg.heads == 4 and cfg.kv_heads == 2
        assert cfg.experts_held == (0, 16) and cfg.shared_columns == 32
        assert cfg.vocab_size == 2 * V
        cfg = dataclasses.replace(cfg, vocab_size=V)
    params, batch = _params(cfg), _batch()
    with jax.default_matmul_precision("highest"):
        got_logits, (got_loss, got_grads) = _program(cfg)(params, batch)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    want_grads = jax.grad(ref.loss)(params, batch, _sizes(cfg))
    assert jnp.allclose(got_logits, want["logits"], atol=3e-5, rtol=3e-5)
    assert jnp.allclose(got_loss, want["loss"], rtol=2e-6)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for got, wanted in zip(jax.tree.leaves(got_grads),
                           jax.tree.leaves(want_grads)):
        assert _close(got, wanted)
    for g in got_grads["blocks"]:
        # a share does not train its router, the whole model does; the
        # bias is a constant of the step on both
        assert not g["router_bias"].any()
        assert bool(g["router"].any()) == (share == "uncut")
        assert g["experts"]["down"].any() and g["shared"]["down"].any()
    for name in ("A_log", "dt_bias", "beta", "f_up", "g_down", "conv"):
        assert all(np.any(np.asarray(leaf)) for leaf in jax.tree.leaves(
            got_grads["blocks"][1][name])), name


def test_bfloat16_compute_stays_within_the_stated_tolerance():
    """What the benchmark's ``correct`` compares, at tiny size: loss over
    all positions, logits where no held choice differs."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params, batch = _params(cfg, scale=1.0), _batch()
    got = jax.jit(forward, static_argnums=2)(params, batch["tokens"], cfg)
    want = ref.check(params, batch, _all(), _sizes(cfg))
    loss, counters = jax.jit(delta_decoder.lm_loss_and_counters,
                             static_argnums=2)(params, batch, cfg)
    chosen = np.asarray(counters["chosen"]).reshape(want["chosen"].shape)
    assert chosen.shape == (2, B, T, cfg.experts_per_token)
    flipped = (chosen != np.asarray(want["chosen"])).any((0, 3))
    gap = np.asarray(jnp.abs(got - want["logits"]).max(-1))
    assert flipped.mean() < 0.2 and gap[~flipped].max() < 0.05
    assert not flipped.any() or want["margin"][flipped].max() < 0.05
    assert abs(float(loss) - float(want["loss"])) < 1e-3 * float(want["loss"])


def test_a_sequence_reads_nothing_that_comes_after_it():
    cfg = _cfg()
    params, batch = _params(cfg), _batch()
    other = batch["tokens"].at[:, 40:].set((batch["tokens"][:, 40:] + 1) % V)
    run = jax.jit(forward, static_argnums=2)
    a, b = run(params, batch["tokens"], cfg), run(params, other, cfg)
    assert jnp.array_equal(a[:, :40], b[:, :40])
    assert not jnp.allclose(a[:, 40:], b[:, 40:])


# ------------------------------------- the shares add up, satellite (3)
def _whole_cfg(kind):
    return _cfg(layers=1, attention_layers=(0,) if kind == "a" else (),
                delta_heads=8, heads=8, kv_heads=2, kv_heads_total=None,
                shared_dim=64, experts_count=16, experts_offset=0,
                model_share=1, model_rank=0)


@pytest.mark.parametrize("kind", ["d", "a"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(kind):
    """8 chips by heads (1 of 8 delta-rule heads, 1 of 8 query heads on 1 of
    2 kv heads, 8 of the shared expert's 64 columns) and the 16 experts in 8
    shares of 2: each half's partial results over all the shares, with what
    every chip computes alike (the norms, the router, the two gate
    bottlenecks, the residual) counted once, add up to what the uncut
    reference gives for the whole layer; and a share's layer is the
    reference given the same share."""
    whole = _whole_cfg(kind)
    assert whole.kinds == kind
    params = _params(whole)
    bp = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, whole.hidden))
    want = jnp.stack([ref.layer(bp, xb, _sizes(whole))[0] for xb in x])
    mixer = jax.jit(delta_decoder._attend if kind == "a"
                    else delta_decoder._delta_mixer, static_argnums=2)
    expert_parts = jax.jit(delta_decoder._expert_parts, static_argnums=2)
    mixed, shares = [], []
    with jax.default_matmul_precision("highest"):
        for rank in range(8):
            cfg = dataclasses.replace(
                whole, model_share=8, model_rank=rank, delta_heads=1,
                heads=1, kv_heads=1, kv_heads_total=2, experts_count=2,
                experts_offset=2 * rank, vocab_size=V // 8)
            assert cfg.whole == dataclasses.replace(
                whole, kv_heads_total=2, vocab_size=V)
            mine = delta_decoder.share_of(params, cfg)["blocks"][0]
            shares.append((cfg, mine))
            # a mixer reads its sizes and neither rank nor offset: one trace
            mixed.append(mixer(mine, x, shares[0][0]) - x)
        h = x + sum(mixed)
        m = moe_decoder._rmsnorm(h, bp["ln2"], whole.rms_eps).reshape(
            B * T, -1)
        parts, rows = [], []
        for cfg, mine in shares:
            routed, shared, counters = expert_parts(mine, m, cfg)
            parts.append((routed + shared).reshape(h.shape))
            rows.append(int(counters["choices_here"]))
        # one share's whole layer against the reference given the same share
        cfg, mine = shares[5]
        out, _ = jax.jit(delta_decoder._block, static_argnums=(2, 3))(
            mine, x, kind, cfg)
        alone = jnp.stack([ref.layer(mine, xb, _sizes(cfg))[0] for xb in x])
        assert jnp.allclose(out, alone, atol=3e-5, rtol=3e-5)
    assert sum(rows) == B * T * whole.experts_per_token
    assert jnp.allclose(h + sum(parts), want, atol=5e-5, rtol=5e-5)
    # one share alone is not the layer, in either half
    assert not jnp.allclose(x + mixed[0], x + sum(mixed), atol=1e-2)
    assert not jnp.allclose(h + parts[0], want, atol=1e-2)


def test_a_share_holds_what_param_pspecs_shards():
    """Cutting exactly the axes ``param_pspecs`` names gives the shapes
    ``init_params`` makes for the share; a mesh is refused by name."""
    cfg = _cfg(vocab_size=V // 2)
    whole = cfg.whole
    mine = delta_decoder.share_of(_params(whole), cfg)
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
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "solar-open2-250b-tp8ep40.json")) as f:
        config = json.load(f)
    from benchmarks.lib import model

    cfg = DeltaDecoderConfig(**model.sizes(config, False))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree.leaves(shapes))
    assert count == 785_822_360 == config["deployment"]["parameters"]
    assert cfg.kinds == "addd" and cfg.model_share == 8
    assert cfg.experts_held == (0, 8) and cfg.shared_columns == 160
    # 4,096 rows under the worst case's 65,536, a shape no other cell runs
    assert moe_decoder._rung(8192, cfg.experts_per_token, 8, 320) == 4096
    # every width, the router's outputs, the taps and the chunk are the
    # source's (the defaults are the published model)
    published = DeltaDecoderConfig()
    assert published.kinds == "addd" * 12
    assert cfg.whole == dataclasses.replace(
        published, layers=4, attention_layers=(0,), max_seq=cfg.max_seq)
    linear = config["published"]["linear_attn_config"]
    assert (published.delta_heads, published.delta_head_dim,
            published.conv_kernel) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"])


# -------------------------------- the one entry point, satellite (5)
def test_the_family_trains_through_the_one_entry_point_with_its_counters():
    cfg = _cfg(remat=True, layers=4)
    assert cfg.kinds == "addd" and models.family_of(cfg) is not None
    params, batch = _params(cfg), _batch()
    assert forward(params, batch["tokens"], cfg).shape == (B, T, V)
    init, step = make_train_step(cfg)
    _, _, loss, counters = step(params, init(params), batch)
    assert np.isfinite(float(loss))
    rows = np.asarray(counters["rows_per_expert"])
    assert rows.shape == (4, cfg.experts_count)
    assert (rows.sum(1) == np.asarray(counters["choices_here"])).all()
    assert np.asarray(counters["chosen"]).shape == (4, B * T, 2)
    assert np.asarray(counters["tokens_without_expert"]).shape == (4,)
    assert counters["buffer_rows"].tolist() == [64] * 4        # the rung


# ----------------------- names a trace and a checkpoint read, satellite (6)
@pytest.fixture(scope="module")
def lowered_step():
    with jax.enable_x64(False):
        cfg = _cfg(remat=True)
        params, batch = _params(cfg), _batch()
        init, step = make_train_step(cfg)
        lowered = step.lower(params, init(params), batch)
        return set(re.findall(r'loc\("([^"]+)"',
                              lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("scope", [
    n for n in delta_decoder.SCOPES
    if n not in ("mlp", "kv_write", "kv_gather", "sample", "head_rows",
                 "rope")])
def test_scope_name_is_in_the_lowered_steps_op_metadata(lowered_step, scope):
    word = re.compile(r"(?<![\w.])" + scope + r"(?![\w.])")
    paths = [p for p in lowered_step if word.search(p)]
    assert paths, scope
    if scope.startswith("kda_"):
        # flat: forward, replay and backward, never inside another name
        others = [n for n in delta_decoder.SCOPES if n != scope]
        assert not any(re.search(r"(?<![\w.])" + n + r"(?![\w.])", p)
                       for p in paths for n in others)
        assert any("transpose" in p for p in paths)
        assert any("rematted_computation" in p for p in paths)


@pytest.fixture(scope="module")
def traced_loss():
    with jax.enable_x64(False):
        cfg = _cfg(remat=True)
        return str(jax.make_jaxpr(
            lambda p, b: delta_decoder.lm_loss_and_counters(p, b, cfg))(
                _params(cfg), _batch()))


@pytest.mark.parametrize("name", [
    *delta_decoder._KEPT_NAMES, *moe_decoder._QKV_NAMES])
def test_every_kept_name_is_in_the_traced_step(traced_loss, name):
    """The policy's names are the program's: a name nothing carries keeps
    nothing, in silence."""
    assert f"name={name}" in traced_loss


def test_the_vocabulary_is_moe_decoders_and_five_names():
    assert delta_decoder.SCOPES[:len(moe_decoder.SCOPES)] \
        == moe_decoder.SCOPES
    assert delta_decoder.SCOPES[len(moe_decoder.SCOPES):] == (
        "kda_in", "kda_conv", "kda_scan", "kda_out", "moe_shared")


@pytest.mark.parametrize("kind,kept", [
    ("d", {"moe_decoder.py": 4, f"[{B},{T},24]": 1}),
    ("a", {"moe_decoder.py": 4, "delta_decoder.py": 3,
           "pallas_kernels.py": 2})])
def test_a_block_keeps_its_input_and_what_is_named(capsys, kind, kept):
    """``print_saved_residuals`` of one block under ``encode``'s policy: the
    block's arguments and what is named, told apart by the file that named
    it: of the expert layer the four inputs of its backward rule
    (``moe_decoder._ROUTE_NAMES``; a share's weights carry no gradient, so
    its backward reads the router's logits and choice no longer and they
    fall out), of attention q, k, v and the kernel's output and logsumexp,
    of the delta-rule mixer the projection's q, k and v before their taps
    (``kda_qkv``, (B, T, 3 x heads x d)) and nothing of its scan; nothing
    with the expert buffer's rows."""
    cfg = _cfg(remat=True, layers=1,
               attention_layers=(0,) if kind == "a" else ())
    bp = _params(cfg)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.hidden))
    ck = jax.checkpoint(
        functools.partial(delta_decoder._block, kind=kind, cfg=cfg),
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_SAVED_NAMES, *moe_decoder._QKV_NAMES,
            *delta_decoder._KEPT_NAMES))
    jax.ad_checkpoint.print_saved_residuals(
        lambda bp_, x_: ck(bp_, x_)[0].sum(), bp, x)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "from the argument" not in ln]
    assert len(lines) == sum(kept.values()), lines
    for source, count in kept.items():
        assert sum(source in ln for ln in lines) == count, (source, lines)
    assert sum("'route_" in ln for ln in lines) == 3    # the integer three
    assert not any(f"[{B * T * 2}," in ln for ln in lines)
