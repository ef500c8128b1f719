"""The masked-LM head on the positions that carry loss (``models/bert.py``
``_head_loss``): the compacted and the dense route give the dense formula's
loss and gradients, the input alone decides the route, and a causal model or
a mesh builds the dense program with no conditional in it."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.models import bert
from deeplearning4j_tpu.models.bert import (
    TransformerConfig, init_params, lm_loss, loss_from_logits,
    make_train_step)
from deeplearning4j_tpu.ops import pallas_kernels
from deeplearning4j_tpu.parallel import make_mesh
from tests.test_trace_names import _pallas_names

B, T, V = 2, 128, 64
ROWS = 64       # a quarter of B * T
CFG = TransformerConfig(vocab_size=V, hidden=32, layers=2, heads=2,
                        mlp_dim=64, max_seq=T, dtype=jnp.float32,
                        remat=False)


def dense_loss(params, batch, cfg=CFG, mesh=None):
    """The formula the parent's ``lm_loss`` was: every position through the
    head."""
    return loss_from_logits(
        bert._forward_raw(params, batch["tokens"], cfg, mesh), batch)


def _some(n, rng, values=(1.0,)):
    w = np.zeros(B * T, np.float32)
    w[rng.choice(B * T, n, replace=False)] = rng.choice(values, n)
    return w.reshape(B, T)


def _one_sequence(rng):
    w = np.zeros((B, T), np.float32)
    w[1, rng.choice(T, 40, replace=False)] = 1.0
    return w


# name -> (weights from a generator, whether the compacted route takes it)
WEIGHTS = {
    "bernoulli_15pct": (lambda r: (r.random((B, T)) < 0.15)
                        .astype(np.float32), True),
    "buffer_full": (lambda r: _some(ROWS, r), True),
    "one_too_many": (lambda r: _some(ROWS + 1, r), False),
    "all_zero": (lambda r: np.zeros((B, T), np.float32), True),
    "all_one": (lambda r: np.ones((B, T), np.float32), False),
    "not_binary": (lambda r: _some(50, r, (0.5, 2.0)), True),
    "one_sequence": (_one_sequence, True),
}


def _batch(name, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "targets": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "weights": jnp.asarray(WEIGHTS[name][0](rng))}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def both():
    """The new ``lm_loss`` and the dense formula under ``value_and_grad``,
    and the new one forward only (what the benchmark's check calls)."""
    return (jax.jit(jax.value_and_grad(lambda p, b: lm_loss(p, b, CFG))),
            jax.jit(jax.value_and_grad(dense_loss)),
            jax.jit(lambda p, b: lm_loss(p, b, CFG)))


def test_the_buffer_is_a_quarter_of_the_positions_in_whole_sublanes():
    assert bert.HEAD_ROWS_DIVISOR == 4
    assert bert._head_rows(B * T) == ROWS
    assert bert._head_rows(96 * 512) == 12288
    assert bert._head_rows(4 * 64) == 64
    assert bert._head_rows(100) == 32 and bert._head_rows(3) == 0


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_loss_and_every_gradient_leaf_agree_with_the_dense_formula(
        params, both, name):
    batch = _batch(name)
    new, dense, forward_only = both
    fits = int(np.count_nonzero(batch["weights"])) <= ROWS
    assert fits == WEIGHTS[name][1]
    (loss, grads), (want, want_grads) = new(params, batch), dense(params,
                                                                  batch)
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    np.testing.assert_allclose(float(forward_only(params, batch)),
                               float(want), rtol=2e-6)
    leaves, want_leaves = jax.tree.leaves(grads), jax.tree.leaves(want_grads)
    assert len(leaves) == len(want_leaves) == 5 + 12 * CFG.layers
    for got, ref in zip(leaves, want_leaves):
        assert got.dtype == ref.dtype and np.all(np.isfinite(got))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-7, rtol=1e-5)
    if name == "all_zero":
        assert float(loss) == 0.0
        assert all(not np.any(np.asarray(g)) for g in leaves)


@pytest.mark.parametrize("name", sorted(
    n for n, (_, fits) in WEIGHTS.items() if fits))
def test_the_compacted_route_alone_is_the_dense_route(params, name):
    """The two functions the conditional chooses between, called directly
    on a batch that fits: same loss, same two gradients."""
    batch = _batch(name, seed=1)
    x = bert.encode(params, batch["tokens"], CFG)
    args = (x, params["lm_head"], batch["targets"], batch["weights"])
    got = jax.value_and_grad(bert._compact_head_loss, argnums=(0, 1))(*args)
    want = jax.value_and_grad(bert._dense_head_loss, argnums=(0, 1))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-7, rtol=1e-5)
    # unweighted positions get exactly nothing back
    unweighted = np.asarray(batch["weights"]) == 0
    assert not np.any(np.asarray(got[1][0])[unweighted])


def test_a_cotangent_scales_both_gradients(params):
    batch = _batch("bernoulli_15pct")
    grads = jax.grad(lambda p: 3.0 * lm_loss(p, batch, CFG))(params)
    want = jax.grad(lambda p: 3.0 * dense_loss(p, batch))(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=6e-7, rtol=1e-5)


@pytest.mark.parametrize("name", ["bernoulli_15pct", "one_too_many"])
def test_one_train_step_on_each_route_gives_the_dense_steps_parameters(
        params, name):
    batch = _batch(name)
    init_state, step = make_train_step(CFG, learning_rate=1e-3)
    tx = optax.adamw(1e-3, weight_decay=0.01)

    @jax.jit
    def dense_step(p, state, b):
        loss, grads = jax.value_and_grad(dense_loss)(p, b)
        updates, state = tx.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    copy = jax.tree.map(jnp.copy, params)       # ``step`` donates
    got, _, loss = step(copy, init_state(copy), batch)
    want, _, want_loss = dense_step(params, tx.init(params), batch)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    moved = 0.0
    for a, b, start in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
        moved = max(moved, float(jnp.max(jnp.abs(b - start))))
    assert moved > 5e-4     # the step did move the weights


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


def parent_step(cfg, mesh=None, learning_rate=1e-4, weight_decay=0.01):
    """``make_train_step``'s step as it was before a second model family
    registered with it: ``value_and_grad`` of ``lm_loss``, AdamW."""
    tx = optax.adamw(learning_rate, weight_decay=weight_decay)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm_loss)(params, batch, cfg, mesh)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss
    return tx.init, step


@pytest.mark.parametrize("causal", [False, True],
                         ids=["bidirectional", "causal"])
def test_the_train_step_is_the_parents_program(params, causal):
    """The family registry (``bert.register_family``) and the counters a
    family may return change nothing in this family's step: the jaxpr is
    the parent's, line for line."""
    cfg = TransformerConfig(**{**CFG.__dict__, "causal": causal})
    batch = _batch("bernoulli_15pct")
    init, step = make_train_step(cfg)
    want_init, want_step = parent_step(cfg)
    opt_state = init(params)
    assert jax.tree.structure(opt_state) == jax.tree.structure(
        want_init(params))
    got = jax.make_jaxpr(step)(params, opt_state, batch)
    want = jax.make_jaxpr(jax.jit(want_step, donate_argnums=(0, 1)))(
        params, opt_state, batch)
    assert str(got) == str(want)


# name -> (settings over CFG, T, flash_fwd calls in the step's jaxpr)
_TAG_CASES = {
    # the benchmark cell's route: no checkpoint, the packed kernel
    "cell_packed_no_remat": (dict(remat=False), T, 0),
    # the streamed kernel under this family's checkpoint, whose policy
    # names nothing: the kernel is replayed, as before
    "streamed_remat": (dict(remat=True), 2048, 2 * CFG.layers),
    "streamed_no_remat": (dict(remat=False), 2048, CFG.layers),
}


def _functions_renumbered(lowered: str) -> str:
    """StableHLO text with every function symbol's counter (``@_var_195``:
    the lowering numbers its private functions as it goes) replaced by the
    symbol's order of first appearance."""
    order = {}
    return re.sub(r"@[\w.]+", lambda m: order.setdefault(
        m.group(), f"@f{len(order)}"), lowered)


@pytest.mark.parametrize("case", sorted(_TAG_CASES))
def test_the_streamed_kernels_checkpoint_names_are_inert_here(
        monkeypatch, case):
    """``flash_attention``'s forward rule names its output and logsumexp
    for a checkpoint that asks for them (the routed-expert decoder's). This
    family's does not: the program handed to the compiler is the one built
    with the names taken out, letter for letter, and where the streamed
    kernel is not on the route so is the jaxpr."""
    settings, t, forwards = _TAG_CASES[case]
    cfg = TransformerConfig(**{**CFG.__dict__, "attention_impl": "flash",
                               "max_seq": t, **settings})
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, t), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens,
             "weights": jnp.ones((1, t), jnp.float32).at[:, ::7].set(0.0)}

    def program():
        init, step = make_train_step(cfg)
        traced = step.trace(params, init(params), batch)
        return traced.jaxpr, traced.lower().as_text()

    jaxpr, lowered = program()
    monkeypatch.setattr(pallas_kernels, "checkpoint_name",
                        lambda x, name: x)
    bare_jaxpr, bare_lowered = program()
    assert _functions_renumbered(lowered) \
        == _functions_renumbered(bare_lowered)
    kernels = collections.Counter(_pallas_names(jaxpr.jaxpr))
    assert kernels["flash_fwd"] == forwards
    assert ("name" in set(_primitives(jaxpr.jaxpr))) == (forwards > 0)
    assert "name" not in set(_primitives(bare_jaxpr.jaxpr))
    assert (str(jaxpr) == str(bare_jaxpr)) == (forwards == 0)


def _gate_cases():
    return {"causal": (TransformerConfig(**{**CFG.__dict__, "causal": True}),
                       lambda: None),
            "mesh": (CFG, lambda: make_mesh({"data": 2, "model": 2})),
            "mesh_of_one": (CFG, lambda: make_mesh(
                {"data": 1}, jax.devices()[:1]))}


@pytest.mark.parametrize("case", sorted(_gate_cases()))
def test_a_causal_model_or_a_mesh_builds_the_dense_program(params, case):
    cfg, mesh_of = _gate_cases()[case]
    mesh, batch = mesh_of(), _batch("bernoulli_15pct")
    for wrap in (lambda f: f, jax.value_and_grad):
        got = jax.make_jaxpr(wrap(
            lambda p, b: lm_loss(p, b, cfg, mesh)))(params, batch)
        want = jax.make_jaxpr(wrap(
            lambda p, b: dense_loss(p, b, cfg, mesh)))(params, batch)
        assert "cond" not in set(_primitives(got.jaxpr))
        assert str(got) == str(want)


def test_one_device_and_bidirectional_builds_one_conditional(params):
    batch = _batch("bernoulli_15pct")
    for wrap in (lambda f: f, jax.value_and_grad):
        jaxpr = jax.make_jaxpr(wrap(lambda p, b: lm_loss(p, b, CFG)))(
            params, batch)
        assert list(_primitives(jaxpr.jaxpr)).count("cond") == 1


# ------------------------------------------- the compiled step's conditional
def _computations(hlo_text):
    """name -> the instruction lines of each computation of an HLO module."""
    out, current = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            current = out.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    return out


def _reached(comps, name, seen):
    """The computation ``name`` and every computation it calls (fusions
    and nested control flow; not the scalar reducers of ``to_apply``, whose
    parameters are no operations of the program)."""
    if name in seen or name not in comps:
        return seen
    seen.add(name)
    for line in comps[name]:
        for called in re.findall(
                r"(?:calls|body|condition|branch_computations)="
                r"\{?([%\w.\-, ]+)\}?", line):
            for one in called.split(","):
                _reached(comps, one.strip().lstrip("%"), seen)
    return seen


@pytest.fixture(scope="module")
def step_branches(params):
    """The instruction lines of the dense and of the compacted branch of
    the compiled train step's one conditional."""
    init_state, step = make_train_step(CFG)
    text = step.lower(params, init_state(params),
                      _batch("bernoulli_15pct")).compile().as_text()
    comps = _computations(text)
    conditionals = re.findall(
        r"conditional\(.*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}",
        text)
    assert len(conditionals) == 1
    return [[line for c in _reached(comps, branch, set())
             for line in comps[c]] for branch in conditionals[0]]


def test_every_operation_inside_the_conditional_is_under_lm_head_or_loss(
        step_branches):
    for lines in step_branches:
        named = [re.search(r'op_name="([^"]+)"', line) for line in lines]
        paths = [m.group(1) for m in named if m]
        assert len(paths) > 20
        stray = [p for p in paths if not re.search(
            r"(?<![\w.])(lm_head|loss)(?![\w.])", p)]
        assert stray == []
    # and the compaction's own operations carry their name, in that branch
    dense, compacted = step_branches
    assert not any("head_rows" in line for line in dense)
    for primitive in ("sort", "gather", "scatter-add"):
        assert any(re.search(r"head_rows\)*/[^\"]*" + primitive, line)
                   for line in compacted), primitive


def test_the_compacted_branch_never_holds_a_row_per_position_of_logits(
        step_branches):
    """Differentiating *through* a conditional would make this branch
    write the dense branch's (B, T, vocab) residuals as zeros."""
    dense, compacted = step_branches
    per_position = re.compile(r"\[%d,%d,%d\]|\[%d,%d\]" % (B, T, V, B * T, V))
    assert any(per_position.search(line) for line in dense)
    assert not any(per_position.search(line) for line in compacted)
    assert any("[%d,%d]" % (ROWS, V) in line for line in compacted)
