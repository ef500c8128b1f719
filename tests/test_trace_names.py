"""The names the device trace keeps and the phases on the trace's clock:
``jax.named_scope`` names in the programs ``models/bert.py`` builds, kernel
names on every ``pallas_call``, ``OpProfiler`` spans as annotations of a
``jax.profiler`` trace, the profiler's bounded ring, and the scheduler's
phase spans and counters in ``GenerationEngine`` (PERF.md section 3 lists
which metric reads which).
"""
import contextlib
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import profiler as profiler_pkg
from deeplearning4j_tpu import models
from deeplearning4j_tpu.models import (
    ConvDecoderConfig, HybridDecoderConfig, MoEDecoderConfig, bert,
    moe_decoder)
from deeplearning4j_tpu.models.bert import (
    SCOPES, TransformerConfig, init_params)
from deeplearning4j_tpu.ops import pallas_kernels
from deeplearning4j_tpu.ops.pallas_kernels import KERNEL_NAMES
from deeplearning4j_tpu.profiler import OpProfiler, ProfilerConfig
from deeplearning4j_tpu.serving import GenerationEngine

TRAIN_SCOPES = SCOPES[:9]
BLOCK = ("embed", "attn_qkv", "attention", "attn_out", "mlp", "final_ln",
         "lm_head")
S, MAX_LEN, BLOCK_SIZE = 4, 64, 8
# the routed-expert layer's nested names and the three they nest in
NESTED = moe_decoder.SCOPES[18:]
OUTER = ("moe_dispatch", "moe_combine", "experts")
ROUTED_STEPS = ("train_moe", "train_hybrid_rung", "train_conv_rung")


def _cfg(**kw):
    base = dict(vocab_size=64, hidden=32, layers=2, heads=2, mlp_dim=64,
                max_seq=128, attention_impl="flash")
    return TransformerConfig(**dict(base, **kw))


def _slot_args():
    return (jnp.zeros(S, jnp.int32), jnp.zeros((S, 2), jnp.uint32),
            jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.float32),
            jnp.zeros(S, jnp.int32))


def _routed_cfg(name):
    """The three routed families at tiny sizes. The two shares take the
    rung (``moe_decoder._tiered``): 64 tokens, 4 of 64 held at 6 a token (a
    rung of 64 rows under 256) and 2 of 16 at 2 (32 under 128)."""
    if name == "train_moe":
        return MoEDecoderConfig(
            vocab_size=64, hidden=32, layers=2, heads=4, kv_heads=2,
            head_dim=8, expert_dim=16, experts_total=8, experts_per_token=2,
            experts_count=4, window=64, max_seq=128)
    if name == "train_hybrid_rung":
        return HybridDecoderConfig(
            vocab_size=128, hidden=32, layers=5, pattern="MEM*E",
            mamba_heads=4, mamba_head_dim=8, mamba_groups=2, state_dim=16,
            chunk=8, heads=4, kv_heads=2, head_dim=8, latent_dim=16,
            expert_dim=24, shared_dim=32, experts_total=64,
            experts_per_token=6, experts_count=4, experts_offset=4,
            max_seq=64)
    return ConvDecoderConfig(
        vocab_size=128, hidden=32, layers=5, mixers=(
            "conv", "full_attention", "conv", "conv", "conv"),
        dense_layers=1, heads=4, kv_heads=2, head_dim=8, mlp_dim=96,
        expert_dim=24, experts_total=16, experts_count=2, experts_offset=4,
        experts_per_token=2, max_seq=64)


def _rung_step(name):
    """A share's train step and shapes for its arguments (enough to
    lower)."""
    cfg = _routed_cfg(name)
    assert cfg.remat and moe_decoder._rung(
        64, cfg.experts_per_token, cfg.experts_count,
        cfg.experts_total) is not None
    flat = jnp.zeros((2, 32), jnp.int32)
    shapes = jax.eval_shape(
        lambda: models.init_params(jax.random.PRNGKey(0), cfg))
    init, step = bert.make_train_step(cfg)
    return step, (shapes, jax.eval_shape(init, shapes), {
        "tokens": flat, "targets": flat,
        "weights": jnp.ones((2, 32), jnp.float32)})


def _programs():
    """name -> (jitted program, its arguments), at tiny sizes."""
    cfg = _cfg(causal=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    init, step = bert.make_train_step(cfg)
    flat = jnp.zeros((2, 128), jnp.int32)
    out = {"train": (step, (params, init(params), {
        "tokens": flat, "targets": flat,
        "weights": jnp.ones((2, 128), jnp.float32)}))}

    moe = _routed_cfg("train_moe")
    params = moe_decoder.init_params(jax.random.PRNGKey(0), moe)
    init, step = bert.make_train_step(moe)
    out["train_moe"] = (step, (params, init(params), out["train"][1][2]))

    for name in ROUTED_STEPS[1:]:
        out[name] = _rung_step(name)

    cfg = _cfg(causal=True, max_seq=MAX_LEN)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = np.zeros(2, np.uint32)
    prompt = jnp.zeros((1, 16), jnp.int32)
    tokens, keys, steps, temps, top_ks = _slot_args()
    cache = bert.init_kv_cache(cfg, S, MAX_LEN)
    out["prefill"] = (bert.make_prefill(cfg), (
        params, cache, prompt, np.int32(0), np.int32(5), key,
        np.float32(0), np.int32(0)))
    out["decode"] = (bert.make_decode_step(cfg), (
        params, cache, tokens, jnp.ones(S, bool), keys, steps, temps, top_ks))
    pool = bert.init_kv_cache(cfg, S, MAX_LEN, block_size=BLOCK_SIZE)
    tables = jnp.zeros((S, MAX_LEN // BLOCK_SIZE), jnp.int32)
    out["paged_prefill"] = (bert.make_paged_prefill(cfg, BLOCK_SIZE), (
        params, pool, prompt, jnp.zeros(2, jnp.int32), np.int32(5), key,
        np.float32(0), np.int32(0), np.int32(0)))
    paged = (params, pool, tables, tokens, tokens, keys, steps, temps,
             top_ks, tokens, tokens)
    out["paged_decode"] = (bert.make_paged_decode_step(cfg, BLOCK_SIZE),
                           paged)
    out["paged_decode_fused"] = (bert.make_paged_decode_step(
        cfg, BLOCK_SIZE, paged_attention="fused"), paged)
    out["verify"] = (bert.make_verify_step(cfg, BLOCK_SIZE, 2), (
        params, pool, tables, tokens, jnp.zeros((S, 3), jnp.int32), keys,
        steps, temps, top_ks, tokens, tokens))
    draft = bert.init_draft_kv_cache(cfg, S, MAX_LEN)
    out["draft_step"] = (bert.make_draft_step(cfg), (
        params, draft, tokens, tokens, keys, steps, temps, top_ks))
    return out


@pytest.fixture(scope="module")
def programs():
    return _programs()


def _lower(fn, args):
    with jax.enable_x64(False):     # as on the chip (megablox)
        return fn.lower(*args)


@pytest.fixture(scope="module")
def lowered(programs):
    """name of a program -> its lowered module."""
    cache = {}

    def of(name):
        if name not in cache:
            cache[name] = _lower(*programs[name])
        return cache[name]
    return of


@pytest.fixture(scope="module")
def op_names(lowered):
    """name of a program -> the op names (scope paths) in the metadata of
    its lowered module."""
    cache = {}

    def of(name):
        if name not in cache:
            cache[name] = set(re.findall(
                r'loc\("([^"]+)"', lowered(name).as_text(debug_info=True)))
        return cache[name]
    return of


SERVE = BLOCK + ("kv_write", "sample")
EXPECTED = (
    [("train", n) for n in TRAIN_SCOPES + ("head_rows",)]
    + [("prefill", n) for n in SERVE]
    + [("decode", n) for n in SERVE]
    + [("paged_prefill", n) for n in SERVE]
    + [("paged_decode", n) for n in SERVE + ("kv_gather",)]
    + [("paged_decode_fused", n) for n in ("kv_write", "attention")]
    + [("verify", n) for n in SERVE + ("kv_gather",)]
    + [("draft_step", n) for n in SERVE]
    + [("train_moe", n) for n in (
        "embed", "attn_qkv", "attention", "attn_out", "final_ln", "lm_head",
        "loss", "optimizer") + moe_decoder.SCOPES[13:]]
    + [(p, n) for p in ROUTED_STEPS[1:] for n in OUTER + NESTED])


@pytest.mark.parametrize("program,scope", EXPECTED)
def test_scope_name_is_in_the_lowered_programs_op_metadata(
        op_names, program, scope):
    assert scope in moe_decoder.SCOPES
    word = re.compile(r"(?<![\w.])" + scope + r"(?![\w.])")
    assert any(word.search(path) for path in op_names(program)), (
        program, scope)


@pytest.mark.parametrize("scope,primitives", [
    ("moe_dispatch", {"gather", "reduce_sum"}),
    ("moe_combine", {"gather", "mul", "dot_general"})])
def test_the_row_movements_backward_rules_read_under_their_scope(
        op_names, scope, primitives):
    """The expert layer's dispatch and combine carry their own backward
    rules (``moe_decoder._dispatch``, ``_combine``). The transposed name
    stack keeps the forward's scope, so what the rules add (the row gathers,
    the sum over the slots, the weighted cotangent and the row-wise dot
    product) reads under ``experts.route_ms.moe``'s names."""
    backward = [p for p in op_names("train_moe")
                if p.startswith("jit(step)/transpose(")]
    under = {p.rsplit("/", 1)[-1] for p in backward
             if f"/{scope}/" in p}
    assert primitives <= under
    gathers = {re.findall(r"moe_\w+", p)[-1] for p in backward
               if p.endswith("/gather") and "moe_" in p}
    assert gathers == {"moe_dispatch", "moe_combine"}
    # and no gather of the backward pass reads under no name of the layer's
    assert all(re.search(r"moe_dispatch|moe_combine|lm_head|loss|embed", p)
               for p in backward if p.endswith("/gather"))


def test_the_vocabulary_is_what_the_programs_use(op_names):
    """No scope in the programs that the vocabulary does not list: every
    path component that is no JAX transform or primitive is a SCOPES name,
    a kernel name or the jitted function."""
    used = set()
    for program, _ in EXPECTED:
        for path in op_names(program):
            used.update(re.findall(r"[A-Za-z_]\w*", path.split("/", 1)[-1]))
    every = moe_decoder.SCOPES      # bert's thirteen, then the decoder's
    ours = {n for n in used if n in every or n in KERNEL_NAMES}
    assert every[:13] == SCOPES and set(every) <= ours
    assert len(set(every)) == len(every) == 21
    assert ours - set(every) <= set(KERNEL_NAMES)


# ------------------------------ the routed-expert layer's work, by kind
def _names_in(path):
    return re.findall(r"[A-Za-z_][\w.\-]*", path)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("branch", [0, 1])
@pytest.mark.parametrize("scope", NESTED)
@pytest.mark.parametrize("program", ROUTED_STEPS[1:])
def test_the_nested_names_read_the_same_on_both_routes(
        op_names, program, scope, branch, direction):
    """A share with a rung holds the layer twice, behind a conditional
    forward and another backward (``moe_decoder._tiered``): the three
    nested names are inside both branches of both."""
    want = f"/cond/branch_{branch}_fun/"
    found = [p for p in op_names(program)
             if want in p and scope in _names_in(p.split(want, 1)[1])
             and ("transpose(" in p) == (direction == "backward")]
    assert found, (program, scope, branch, direction)


@pytest.mark.parametrize("program", ROUTED_STEPS)
def test_a_nested_name_is_only_ever_inside_one_of_the_layers_three(
        op_names, program):
    """``rows_moved``, ``row_index`` and ``gmm`` never stand at top level
    nor around a whole outer scope, so a metric file that does not list
    them reads what it read (PERF.md section 3)."""
    held = 0
    for path in op_names(program):
        names = _names_in(path)
        for i, name in enumerate(names):
            if name in NESTED:
                held += 1
                assert set(names[:i]) & set(OUTER), path
            if name in OUTER:
                assert not set(names[:i]) & set(NESTED), path
    assert held > 30


_GATHER = re.compile(
    r'"stablehlo\.gather"\(.*> : \(tensor<((?:\d+x)*)\w+>, .* loc\((#loc\d+)\)')


def _gathers(text):
    """(scope path, operand's shape) of every gather of a lowered module."""
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    return [(paths[loc], tuple(int(d) for d in shape.split("x") if d))
            for shape, loc in _GATHER.findall(text)]


@pytest.mark.parametrize("program", ROUTED_STEPS)
def test_every_gather_of_the_layer_is_rows_moved_or_row_index(
        lowered, program):
    """Full-width rows by index read under ``rows_moved`` (the dispatch,
    the combine and their two backward rules), scalars by index under
    ``row_index`` (the weights into buffer order, the dot products back),
    forward, replay and backward, and no gather of the layer under
    neither."""
    text = lowered(program).as_text(debug_info=True)
    ours = [(p, shape) for p, shape in _gathers(text)
            if re.search(r"moe_dispatch|moe_combine", p)]
    backward = [(p, shape) for p, shape in ours if "transpose(" in p
                and "rematted_computation" not in p]
    for path, shape in ours:
        want = "rows_moved" if len(shape) == 2 else "row_index"
        assert len(shape) in (1, 2) and want in _names_in(path), (path, shape)
    for outer, rows, scalars in (("moe_dispatch", 1, 0),
                                 ("moe_combine", 1, 2)):
        rule = rf"transpose\(jvp\({outer}\)\)|checkpoint/{outer}/"
        mine = [len(shape) for p, shape in backward if re.search(rule, p)]
        assert mine.count(2) >= rows and mine.count(1) >= scalars, (
            outer, backward)


def _eqns_with_stacks(jaxpr, outer=()):
    """Every equation of a jaxpr and of the jaxprs its equations hold, with
    the name stacks down to it: a nested jaxpr's stacks are relative to the
    equation that holds it."""
    for eqn in jaxpr.eqns:
        here = outer + (str(eqn.source_info.name_stack),)
        yield eqn, here
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns_with_stacks(inner, here)


@pytest.mark.parametrize("program", ROUTED_STEPS)
def test_every_megablox_call_is_under_gmm(programs, program):
    """The grouped products are JAX's megablox kernels, whose
    ``pallas_call`` carries no name: ``gmm`` inside ``experts`` is what
    reads them apart from the activation, the casts and the update."""
    fn, args = programs[program]
    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    stacks = [_names_in("/".join(stack))
              for eqn, stack in _eqns_with_stacks(jaxpr)
              if eqn.primitive.name == "pallas_call"
              and eqn.params["name"] is None]
    assert len(stacks) >= 6
    for names in stacks:
        assert "gmm" in names and "experts" in names[:names.index("gmm")]


@pytest.mark.parametrize("program", ROUTED_STEPS)
def test_a_blocks_replay_reads_under_jaxs_own_name(op_names, program):
    """``trainer.replay_ms.moe`` reads ``rematted_computation``, which
    ``jax.checkpoint`` puts into the path of every replayed operation: a
    JAX that renames it fails here and not in a metric."""
    replayed = [p for p in op_names(program)
                if "rematted_computation" in _names_in(p)]
    assert len(replayed) > 20
    assert all(p.startswith("jit(step)/transpose(") or
               p.startswith("checkpoint/") for p in replayed)
    assert any("moe_dispatch" in _names_in(p) for p in replayed)


@pytest.mark.parametrize("program", ROUTED_STEPS)
def test_names_are_metadata_and_nothing_else(
        programs, lowered, monkeypatch, program):
    """With ``jax.named_scope`` a no-op the step lowers to the same text
    but for the debug info: a name costs nothing in the program."""
    named = lowered(program).as_text()
    assert "moe_dispatch" not in named and "stablehlo" in named
    assert "moe_dispatch" in lowered(program).as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, step = bert.make_train_step(_routed_cfg(program))    # traced anew
    bare = _lower(step, programs[program][1])
    assert bare.as_text() == named
    assert not re.search(r"moe_dispatch|moe_combine|rows_moved|row_index",
                         bare.as_text(debug_info=True))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    checkpoint's replay, a custom rule's body, a kernel's)."""
    return (eqn for eqn, _ in _eqns_with_stacks(jaxpr))


def _pallas_names(jaxpr):
    return (eqn.params["name"] for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "pallas_call")


def _kernel_cases():
    q = jnp.ones((2, 256, 8), jnp.float32)

    def flash(q_):
        return pallas_kernels.flash_attention(
            q_, q_, q_, True, 128, 128, None, True).sum()

    def two_pass(q_):
        """The launchers of the ring backward and of a head past the fused
        kernel's VMEM, on residuals of any value."""
        stat = jnp.zeros((2, 1, 256), jnp.float32)
        args = (q_, q_, q_, q_, stat, stat, True, 128, 128, 1.0, True)
        return (pallas_kernels._launch_bwd_dq(*args),
                pallas_kernels._launch_bwd_dkv(*args))

    return {
        # the fused backward is ``flash_bwd_dkv`` grown by the dq product
        "flash": (jax.grad(flash), (q,), {"flash_fwd", "flash_bwd_dkv"}),
        "flash_two_pass": (two_pass, (q,),
                           {"flash_bwd_dq", "flash_bwd_dkv"}),
    }


@pytest.mark.parametrize("case,want", [
    ("train", {"mha_packed_fwd", "mha_packed_bwd"}),
    ("paged_decode_fused", {"paged_decode_attention"}),
    ("flash", None), ("flash_two_pass", None),
    ("prefill", {"mha_packed_fwd"}), ("decode", set()),
    ("train_moe", {"flash_fwd", "flash_bwd_dkv"}),
])
def test_every_pallas_call_carries_its_kernel_name(programs, case, want):
    if want is None:
        fn, args, want = _kernel_cases()[case]
    else:
        fn, args = programs[case]
    names = list(_pallas_names(jax.make_jaxpr(fn)(*args).jaxpr))
    if case == "train_moe":
        # the grouped products are JAX's megablox kernels, whose
        # pallas_call carries no name: the scope ``experts`` reads them
        assert None in names
        names = [n for n in names if n is not None]
    assert set(names) == want
    assert all(n in KERNEL_NAMES for n in names)


def test_kernel_names_cover_every_pallas_call_site():
    """Every call site is named, and ``KERNEL_NAMES`` lists the names in the
    order of their first site. One name belongs to two sites: the fused
    streamed backward is ``flash_bwd_dkv`` grown by the dq product."""
    with open(pallas_kernels.__file__) as f:
        source = f.read()
    sites = source.count("pl.pallas_call(")
    named = re.findall(r'\n\s+name="(\w+)",\n', source)
    assert sites == len(named) == len(KERNEL_NAMES) + 1
    assert tuple(dict.fromkeys(named)) == KERNEL_NAMES
    assert [n for n in KERNEL_NAMES if named.count(n) > 1] == [
        "flash_bwd_dkv"]


# ------------------------------------------------------------ the profiler
def _host_events(logdir):
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events]
    return out


def test_a_span_is_a_host_plane_event_of_a_running_trace(tmp_path):
    prof = OpProfiler()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with prof.span("serving.decode_step", engine="e0", live=3, step=17):
            with prof.span("serving.decode.readback",
                           parent="serving.decode_step", step=17):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = {e[0]: e for e in _host_events(str(tmp_path))
              if e[0].startswith("serving.")}
    outer, inner = (events["serving.decode_step"],
                    events["serving.decode.readback"])
    assert outer[3] == {"engine": "e0", "live": 3, "step": 17}
    assert inner[3] == {"parent": "serving.decode_step", "step": 17}
    # on the trace's clock the child lies inside its parent
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    assert inner[2] >= 2e6
    # and the same two spans are the profiler's, with the same durations
    mine = {s.name: s for s in prof.spans}
    assert set(mine) == set(events)
    assert mine["serving.decode.readback"].dur_us * 1e3 \
        == pytest.approx(inner[2], rel=0.2)


def test_spans_work_with_no_trace_running_and_collecting_off():
    prof = OpProfiler(ProfilerConfig(collectSpans=False))
    with prof.span("a", x=1) as args:
        args["y"] = 2
    assert prof.spans == [] and prof.dropped == 0


def test_the_ring_drops_the_oldest_and_counts_it(monkeypatch):
    from deeplearning4j_tpu.profiler import profiler as profiler_module

    assert profiler_module.SPAN_CAPACITY == 1 << 16
    monkeypatch.setattr(profiler_module, "SPAN_CAPACITY", 4)
    prof = OpProfiler()
    for i in range(10):
        with prof.span("s", i=i):
            pass
    assert [s.args["i"] for s in prof.spans] == [6, 7, 8, 9]
    assert prof.dropped == 6
    assert prof.summary()["s"]["count"] == 4
    prof.reset()
    assert prof.spans == [] and prof.dropped == 0


def test_absolute_starts_agree_with_perf_counter():
    prof = OpProfiler()
    assert prof.base <= time.perf_counter()
    before = time.perf_counter()
    with prof.span("timed"):
        time.sleep(0.001)
    after = time.perf_counter()
    s = prof.spans[0]
    assert before <= s.start <= after - 0.001
    assert prof.base + s.start_us / 1e6 == pytest.approx(s.start, abs=1e-9)
    assert s.start + s.dur_us / 1e6 <= after


def test_args_added_inside_the_block_are_kept_with_the_span():
    prof = OpProfiler()
    with prof.span("serving.admit", step=3) as did:
        did["admitted"] = 2
    assert prof.spans[0].args == {"step": 3, "admitted": 2}
    with prof.span("bare"):
        pass
    assert prof.spans[1].args is None


def test_export_chrome_trace_output_is_what_it_was(tmp_path):
    prof = OpProfiler()
    with prof.span("outer", phase="train"):
        with prof.span("inner"):
            pass
    path = prof.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    inner, outer = prof.spans
    assert trace == {"displayTimeUnit": "ms", "traceEvents": [
        {"name": "inner", "ph": "X", "ts": inner.start_us,
         "dur": inner.dur_us, "pid": 1, "tid": inner.tid},
        {"name": "outer", "ph": "X", "ts": outer.start_us,
         "dur": outer.dur_us, "pid": 1, "tid": outer.tid,
         "args": {"phase": "train"}}]}


@pytest.mark.parametrize("name", ["device_trace", "timeit"])
def test_what_nothing_read_is_gone(name):
    assert not hasattr(profiler_pkg, name)
    assert not hasattr(OpProfiler, name)
    assert name not in profiler_pkg.__all__


# ------------------------------------------------- the scheduler's phases
ENGINE_CFG = TransformerConfig(
    vocab_size=50, hidden=32, layers=2, heads=2, mlp_dim=64, max_seq=64,
    dtype=jnp.float32, causal=True, attention_impl="full", remat=False)
PHASES = {"serving.decode.stage": None, "serving.decode.commit": None,
          "serving.admit": None, "serving.prefill": "serving.admit",
          "serving.decode.dispatch": "serving.decode_step",
          "serving.decode.readback": "serving.decode_step",
          "serving.prefill.dispatch": "serving.prefill",
          "serving.prefill.readback": "serving.prefill"}


@pytest.fixture(scope="module", params=["contiguous", "paged"])
def engine_run(request):
    """Six requests of mixed lengths through a two-slot engine with a
    profiler of its own; returns the spans and the metrics."""
    params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
    prof = OpProfiler()
    kw = {"block_size": 8} if request.param == "paged" else {}
    rng = np.random.default_rng(0)
    with GenerationEngine(params, ENGINE_CFG, slots=2, max_len=32,
                          profiler=prof, **kw) as eng:
        handles = [eng.submit(
            rng.integers(1, 50, n).astype(np.int32), max_new_tokens=m)
            for n, m in ((3, 6), (9, 4), (5, 8), (12, 3), (4, 5), (7, 7))]
        tokens = [h.result(timeout=120) for h in handles]
        snapshot = eng.metrics.snapshot()
    assert [len(t) for t in tokens] == [6, 4, 8, 3, 5, 7]
    return prof.spans, snapshot


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_spans_say_what_caused_them(engine_run, phase):
    spans = _by_name(engine_run[0])
    assert spans[phase], phase
    for s in spans[phase]:
        assert s.args["step"] >= 1
        assert s.args.get("parent") == PHASES[phase]


@pytest.mark.parametrize("parent", ["serving.decode_step",
                                    "serving.prefill"])
def test_children_nest_inside_their_parent_and_fit(engine_run, parent):
    spans = _by_name(engine_run[0])
    children = [s for name, want in PHASES.items() if want == parent
                for s in spans[name]]
    assert len(children) == 2 * len(spans[parent])
    for p in spans[parent]:
        mine = [c for c in children
                if p.start <= c.start <= p.start + p.dur_us / 1e6]
        assert sorted(c.name for c in mine) == sorted(
            n for n, want in PHASES.items() if want == parent)
        assert all(c.args["step"] == p.args["step"] for c in mine)
        assert all(c.start + c.dur_us / 1e6 <= p.start + p.dur_us / 1e6
                   + 1e-9 for c in mine)
        # self time is never negative: the children fit in the parent
        assert sum(c.dur_us for c in mine) <= p.dur_us + 1e-3
        dispatch, readback = sorted(mine, key=lambda c: c.start)
        assert dispatch.name.endswith(".dispatch")
        assert dispatch.start + dispatch.dur_us / 1e6 <= readback.start


def test_an_iteration_is_stage_then_step_then_commit(engine_run):
    spans = _by_name(engine_run[0])
    steps = {s.args["step"]: s for s in spans["serving.decode_step"]}
    stages = {s.args["step"]: s for s in spans["serving.decode.stage"]}
    commits = {s.args["step"]: s for s in spans["serving.decode.commit"]}
    assert set(steps) == set(stages) == set(commits)
    for k, step in steps.items():
        assert stages[k].start + stages[k].dur_us / 1e6 <= step.start
        assert step.start + step.dur_us / 1e6 <= commits[k].start
    assert sum(c.args["emitted"] for c in commits.values()) \
        == engine_run[1]["generated_tokens_total"] \
        - engine_run[1]["prefills_total"]
    assert sum(c.args["retired"] for c in commits.values()) <= 6


def test_admit_spans_count_what_they_admitted(engine_run):
    admits = _by_name(engine_run[0])["serving.admit"]
    assert all({"admitted", "queue_depth", "step"} <= set(s.args)
               for s in admits)
    # an idle scheduler waits for work outside the span, so no admission
    # and no prefill is outside one
    assert sum(s.args["admitted"] for s in admits) == 6
    prefills = _by_name(engine_run[0])["serving.prefill"]
    assert all(any(
        a.start <= p.start and p.start + p.dur_us / 1e6
        <= a.start + a.dur_us / 1e6 + 1e-9
        and a.args["step"] == p.args["step"] for a in admits)
        for p in prefills)


def test_admit_self_time_leaves_out_the_prefills_it_ran(engine_run):
    spans = _by_name(engine_run[0])
    admits, prefills = spans["serving.admit"], spans["serving.prefill"]
    own = whole = 0.0
    for a in admits:
        mine = [p for p in prefills if p.args["step"] == a.args["step"]]
        assert len(mine) == a.args["admitted"]
        assert sum(p.dur_us for p in mine) <= a.dur_us + 1e-3
        own += a.dur_us - sum(p.dur_us for p in mine)
        whole += a.dur_us
    # the model step is most of an admission, and none of its self time
    assert own < 0.5 * whole


def test_counters_agree_with_the_spans(engine_run):
    spans, snap = _by_name(engine_run[0]), engine_run[1]
    prefills, steps = spans["serving.prefill"], spans["serving.decode_step"]
    assert snap["prefills_total"] == len(prefills) == 6
    assert snap["decode_steps_total"] == len(steps)
    # the counter's clock starts just before the span opens and stops just
    # after it closes
    summed = sum(s.dur_us for s in prefills) / 1e3
    assert summed <= snap["prefill_wall_ms"] <= summed + 2.0 * len(prefills)
    live = [s.args["live"] for s in steps]
    assert snap["live_slot_steps_total"] == sum(live)
    assert snap["live_slot_steps_total"] / snap["decode_steps_total"] \
        == pytest.approx(sum(live) / len(live))
    assert 1.0 <= sum(live) / len(live) <= 2.0
