"""Pallas kernel tests — interpret mode on the CPU mesh (the kernels compile
natively on TPU; interpret=True runs identical logic here). Numerics are
checked against plain-jnp oracles, forward AND backward."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import pallas_kernels
from deeplearning4j_tpu.ops.pallas_kernels import (
    _attention_reference, flash_attention, mha_attention,
    mha_attention_packed,
)
from tests.test_trace_names import _pallas_names

RNG = np.random.default_rng(11)


def _rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _rand(3, 128, 16), _rand(3, 128, 16), _rand(3, 128, 16)
        got = flash_attention(q, k, v, causal, 64, 32, None, True)
        want = _attention_reference(q, k, v, causal, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_4d_batch_heads_layout(self):
        q, k, v = _rand(2, 4, 64, 8), _rand(2, 4, 64, 8), _rand(2, 4, 64, 8)
        got = flash_attention(q, k, v, False, 32, 32, None, True)
        want = _attention_reference(q, k, v, False, None)
        assert got.shape == (2, 4, 64, 8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_auto_block_default_and_awkward_lengths(self):
        """block_q/block_k=None resolves via auto_flash_block, which must
        always return a DIVISOR of T — incl. T with no power-of-2
        structure (100, 24) and tiny T (4), which the old fixed-128
        default served via its min(block, t) clamp."""
        from deeplearning4j_tpu.ops.pallas_kernels import auto_flash_block
        for t in (4, 8, 24, 100, 512, 640, 1000, 8192):
            assert t % auto_flash_block(t) == 0, t
        assert auto_flash_block(8192) == 512
        for t in (100, 24):
            q, k, v = _rand(2, t, 8), _rand(2, t, 8), _rand(2, t, 8)
            got = flash_attention(q, k, v, False, None, None, None, True)
            want = _attention_reference(q, k, v, False, None)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5, rtol=2e-5)
        # blockless LONG T: the auto default must refuse the degenerate
        # whole-(T, T) tile with an actionable error, not launch it
        q, k, v = _rand(1, 8191, 8), _rand(1, 8191, 8), _rand(1, 8191, 8)
        with pytest.raises(ValueError, match="no power-of-2 block"):
            flash_attention(q, k, v, False, None, None, None, True)
        # mixed explicit/auto: an explicit big block is the CALLER'S
        # choice and must not trip the auto-side guard
        q, k, v = _rand(1, 2048, 8), _rand(1, 2048, 8), _rand(1, 2048, 8)
        got = flash_attention(q, k, v, False, 2048, None, None, True)
        want = _attention_reference(q, k, v, False, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("bq,bk", [(32, 32), (64, 16), (16, 64)])
    def test_gradients_match_reference(self, causal, bq, bk):
        """Two-pass Pallas backward (round 4) parity across causal modes
        and asymmetric q/k block sizes."""
        q, k, v = _rand(2, 64, 8), _rand(2, 64, 8), _rand(2, 64, 8)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, bq, bk, None, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_attention_reference(q, k, v, causal, None) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("heads,kv_heads,window,bq,bk", [
        (6, 2, None, 16, 16),       # grouped-query heads, no window
        (4, 4, 24, 16, 16),         # a window off the block grid
        (6, 2, 24, 16, 32),         # both, asymmetric blocks
        (6, 2, 24, 32, 16),
        (4, 1, 100, 16, 16),        # the window covers the whole sequence
        (2, 2, 1, 32, 32),          # every query sees itself alone
        (8, 2, 32, None, None),     # auto blocks, band edge on a block edge
    ])
    def test_window_and_kv_groups_match_reference(self, heads, kv_heads,
                                                  window, bq, bk):
        """Query head h reads kv head h // (heads // kv_heads) by index map;
        key j is visible iff 0 <= i - j < window: forward, dq and dk/dv
        against the reference with K and V repeated and a dense mask."""
        q, g = _rand(2, heads, 64, 8), _rand(2, heads, 64, 8)
        k, v = _rand(2, kv_heads, 64, 8), _rand(2, kv_heads, 64, 8)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, True, bq, bk, None, True,
                                    window) * g).sum()

        def loss_ref(q, k, v):
            return (_attention_reference(q, k, v, True, None, window)
                    * g).sum()

        got = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_window_reference_is_the_band(self):
        """The reference's own mask: a one-hot value per key shows which
        keys each query averages."""
        t, w = 8, 3
        q = k = jnp.zeros((1, t, 4))
        got = _attention_reference(q, k, jnp.eye(t)[None], True, None, w)[0]
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        band = (i - j >= 0) & (i - j < w)
        np.testing.assert_allclose(
            np.asarray(got), band / band.sum(1, keepdims=True), atol=1e-6)

    def test_a_window_needs_causal(self):
        q = _rand(1, 32, 8)
        with pytest.raises(AssertionError):
            flash_attention(q, q, q, False, 16, 16, None, True, 8)

    def test_gradients_4d_and_custom_scale(self):
        q, k, v = (_rand(2, 3, 32, 8) for _ in range(3))
        g = _rand(2, 3, 32, 8)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, False, 16, 16, 0.5, True) * g).sum()

        def loss_ref(q, k, v):
            return (_attention_reference(q, k, v, False, 0.5) * g).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.shape == (2, 3, 32, 8)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_causal_ignores_future(self):
        """Perturbing future keys/values must not change earlier outputs."""
        q, k, v = _rand(1, 64, 8), _rand(1, 64, 8), _rand(1, 64, 8)
        out1 = flash_attention(q, k, v, True, 32, 32, None, True)
        k2 = k.at[:, 48:].set(999.0)
        v2 = v.at[:, 48:].set(-999.0)
        out2 = flash_attention(q, k2, v2, True, 32, 32, None, True)
        np.testing.assert_allclose(np.asarray(out1[:, :48]),
                                   np.asarray(out2[:, :48]), atol=1e-5)
        assert not np.allclose(np.asarray(out1[:, 48:]), np.asarray(out2[:, 48:]))

    def test_custom_scale(self):
        q, k, v = _rand(1, 32, 8), _rand(1, 32, 8), _rand(1, 32, 8)
        got = flash_attention(q, k, v, False, 32, 32, 0.5, True)
        want = _attention_reference(q, k, v, False, 0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_under_jit_and_vmap_free_shapes(self):
        q, k, v = _rand(2, 64, 16), _rand(2, 64, 16), _rand(2, 64, 16)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, False, 64, 64,
                                                    None, True))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(_attention_reference(q, k, v, False, None)), atol=2e-5)


class TestFusedFlashBackward:
    """``flash_attention``'s backward inside the fused kernel's VMEM
    envelope: ONE pallas_call (named ``flash_bwd_dkv``) rebuilds the scores
    once a visible block pair and makes dq, dk and dv."""

    T = 64

    @pytest.mark.parametrize(
        "causal,window,heads,kv_heads,d,bq,bk,four_d", [
            (False, None, 4, 4, 64, 16, 16, False),   # bidirectional
            (True, None, 4, 4, 64, 16, 16, False),    # causal
            (True, 32, 4, 4, 64, 16, 16, False),      # window T/2
            (True, 15, 4, 4, 64, 16, 16, False),      # one under a block
            (True, None, 4, 1, 64, 16, 16, False),    # one kv head for all
            (True, 32, 8, 2, 64, 16, 16, False),      # groups of 4, window
            (True, None, 4, 4, 128, 16, 16, False),   # head of 128
            (True, 15, 8, 2, 128, 16, 32, False),     # bq < bk
            (True, 32, 4, 1, 64, 32, 16, False),      # bq > bk
            (False, None, 8, 2, 64, 32, 16, False),   # bidirectional groups
            (True, 15, 8, 2, 64, 16, 16, True),       # (B, H, T, D) layout
            (True, None, 4, 1, 128, 32, 32, True),
        ])
    def test_one_kernel_makes_dq_dk_dv(self, causal, window, heads, kv_heads,
                                       d, bq, bk, four_d):
        """The fused gradients against the float32 reference's, and bit for
        bit against the two-pass launchers on the same residuals: every sum
        runs in their order (dq over ascending k-blocks, dk/dv over
        ascending q-blocks and then the group's heads)."""
        b, t = 2, self.T
        q, g = _rand(b, heads, t, d), _rand(b, heads, t, d)
        k, v = _rand(b, kv_heads, t, d), _rand(b, kv_heads, t, d)
        if not four_d:
            q, g, k, v = pallas_kernels._merge_heads(q, g, k, v)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal, bq, bk, None, True,
                                   window)

        def reference(q, k, v):
            return _attention_reference(q, k, v, causal, None, window)

        fused = jax.make_jaxpr(lambda *a: jax.vjp(flash, *a)[1](g))(q, k, v)
        assert sorted(_pallas_names(fused.jaxpr)) == [
            "flash_bwd_dkv", "flash_fwd"]
        got = jax.vjp(flash, q, k, v)[1](g)
        want = jax.vjp(reference, q, k, v)[1](g)
        for a, w in zip(got, want):
            assert a.shape == w.shape and a.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       atol=1e-4, rtol=1e-4)
        # the same residuals through the two kernels of the route beyond
        # the envelope (and of the ring backward)
        out, lse = pallas_kernels._flash_forward(
            q, k, v, causal=causal, block_q=bq, block_k=bk, scale=None,
            interpret=True, window=window)
        qm, km, vm, om, gm = (x.reshape((-1,) + x.shape[-2:])
                              for x in (q, k, v, out, g))
        delta = jnp.sum(gm * om, axis=-1).reshape(b * heads, 1, t)
        args = (qm, km, vm, gm, lse, delta, causal, bq, bk, 1.0 / d ** 0.5,
                True, window)
        two_pass = (pallas_kernels._launch_bwd_dq(*args),
                    *pallas_kernels._launch_bwd_dkv(*args))
        for a, w in zip(got, two_pass):
            np.testing.assert_array_equal(np.asarray(a).reshape(w.shape),
                                          np.asarray(w))

    @pytest.mark.parametrize("t,d,want", [
        (8192, 128, {"flash_fwd", "flash_bwd_dkv"}),
        (8192, 64, {"flash_fwd", "flash_bwd_dkv"}),
        (16384, 128, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ])
    def test_the_route_follows_the_heads_vmem(self, t, d, want):
        """The backward of a head whose buffers fit the kernels' VMEM limit
        is the fused kernel; a longer head takes the two passes. Read from
        the kernel names of the traced gradient: nothing runs."""
        blk = pallas_kernels.auto_flash_block(t)
        assert pallas_kernels.fused_bwd_fits(t, d, jnp.bfloat16, blk, blk) \
            == ("flash_bwd_dq" not in want)
        q = jax.ShapeDtypeStruct((1, 4, t, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 2, t, d), jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(q, k, v, True, None, None, None,
                                   True).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv)
        names = list(_pallas_names(jaxpr.jaxpr))
        assert set(names) == want and len(names) == len(want)


class TestMhaAttention:
    """Whole-head VMEM kernel (round 4): fwd AND bwd are Pallas; the (T, T)
    scores never reach HBM. This is the flagship-bench attention path at
    T<=1024 (bench: 135.4k -> 164.8k tok/s on one v5e chip)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v = (_rand(4, 2, 64, 32) for _ in range(3))
        got = mha_attention(q, k, v, causal, None, True)
        want = _attention_reference(q, k, v, causal, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal):
        q, k, v = (_rand(2, 2, 32, 16) for _ in range(3))
        g = _rand(2, 2, 32, 16)

        def kernel_loss(q, k, v):
            return (mha_attention(q, k, v, causal, None, True) * g).sum()

        def ref_loss(q, k, v):
            return (_attention_reference(q, k, v, causal, None) * g).sum()

        got = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_3d_layout(self):
        q, k, v = (_rand(6, 32, 16) for _ in range(3))
        got = mha_attention(q, k, v, False, None, True)
        want = _attention_reference(q, k, v, False, None)
        assert got.shape == (6, 32, 16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_custom_scale(self):
        q, k, v = (_rand(2, 16, 8) for _ in range(3))
        got = mha_attention(q, k, v, False, 0.5, True)
        want = _attention_reference(q, k, v, False, 0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestMhaAttentionPacked:
    """Packed-layout kernel: consumes (B, T, H*D) projections directly so
    the (B, H, T, D) head transposes never materialize."""

    B, T, H, D = 3, 64, 4, 32

    def _ref(self, q, k, v, causal):
        B, T, H, D = self.B, self.T, self.H, self.D

        def hsplit(t):
            return t.reshape(B, T, H, D).transpose(0, 2, 1, 3)

        o = _attention_reference(hsplit(q), hsplit(k), hsplit(v), causal, None)
        return o.transpose(0, 2, 1, 3).reshape(B, T, H * D)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v = (_rand(self.B, self.T, self.H * self.D) for _ in range(3))
        got = mha_attention_packed(q, k, v, self.H, causal, None, True)
        want = self._ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal):
        q, k, v = (_rand(self.B, self.T, self.H * self.D) for _ in range(3))
        g = _rand(self.B, self.T, self.H * self.D)

        def kernel_loss(q, k, v):
            return (mha_attention_packed(q, k, v, self.H, causal, None, True)
                    * g).sum()

        def ref_loss(q, k, v):
            return (self._ref(q, k, v, causal) * g).sum()

        got = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_single_head_is_plain_attention(self):
        q, k, v = (_rand(2, 32, 16) for _ in range(3))
        got = mha_attention_packed(q, k, v, 1, False, None, True)
        want = _attention_reference(q, k, v, False, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestHigherOrderAutodiff:
    """The Pallas attention backwards are first-order custom-VJP kernels.
    Default: grad-of-grad raises (JAX's custom_vjp error). Escape hatch:
    higher_order_attention() routes the public entry points to the
    differentiable XLA reference (round-5 verdict #7)."""

    def _hvp(self, f, x, v):
        return jax.jvp(jax.grad(f), (x,), (v,))[1]

    def test_double_grad_raises_explanatory_error(self):
        """Not the raw pallas internal error ('safe_zip() argument 2 is
        longer') — a message naming the higher_order_attention() switch."""
        q, k, v = (_rand(2, 32, 16) for _ in range(3))

        def loss(q):
            return jnp.sum(mha_attention_packed(q, k, v, 2, False, None, True) ** 2)

        with pytest.raises(NotImplementedError, match="higher_order_attention"):
            self._hvp(loss, q, jnp.ones_like(q))

        def loss_flash(q):
            return jnp.sum(flash_attention(q, k, v, False, 16, 16, None, True) ** 2)

        with pytest.raises(NotImplementedError, match="higher_order_attention"):
            self._hvp(loss_flash, q, jnp.ones_like(q))

    def test_higher_order_context_routes_to_reference(self):
        from deeplearning4j_tpu.ops.pallas_kernels import higher_order_attention

        q, k, v = (_rand(2, 32, 16) for _ in range(3))
        tang = jnp.asarray(RNG.normal(size=q.shape).astype(np.float32))

        def loss_ref(q):
            h = q.reshape(2, 32, 2, 8).transpose(0, 2, 1, 3)
            hk = k.reshape(2, 32, 2, 8).transpose(0, 2, 1, 3)
            hv = v.reshape(2, 32, 2, 8).transpose(0, 2, 1, 3)
            return jnp.sum(_attention_reference(h, hk, hv, False, None) ** 2)

        want = self._hvp(loss_ref, q, tang)
        with higher_order_attention():
            def loss(q):
                return jnp.sum(
                    mha_attention_packed(q, k, v, 2, False, None, True) ** 2)

            got = self._hvp(loss, q, tang)
            # first-order results must also still match inside the context
            g = jax.grad(loss)(q)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_context_restores_kernel_path(self):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            _HIGHER_ORDER, higher_order_attention)
        import deeplearning4j_tpu.ops.pallas_kernels as pk

        assert not pk._HIGHER_ORDER
        with higher_order_attention():
            assert pk._HIGHER_ORDER
        assert not pk._HIGHER_ORDER


class TestLayerMhaKernelRoute:
    """Round 5: the layer-DSL multiHeadDotProductAttention op routes its
    unmasked square case through the packed VMEM Pallas kernel (auto on
    TPU; use_kernel=True forces it for these interpret-mode parity tests).
    The einsum path remains for masked / cross-length attention."""

    def _setup(self, B=2, T=32, D=24, O=32, H=4):
        # 0.15 weight scale keeps the softmax un-saturated — saturated
        # attention has degenerate gradients that amplify benign fp32
        # reduction-order differences between the two paths
        ws = {n: _rand(*s) * 0.15 for n, s in (
            ("wq", (D, O)), ("wk", (D, O)), ("wv", (D, O)), ("wo", (O, O)))}
        return _rand(B, T, D), ws

    def test_kernel_route_matches_einsum_fwd_and_grads(self):
        from deeplearning4j_tpu.ops.nn_defs import multi_head_attention

        x, ws = self._setup()
        g = _rand(2, 32, 32)

        def run(use_kernel, xx, w):
            return (multi_head_attention(
                xx, xx, w["wq"], w["wk"], w["wv"], w["wo"], 4,
                use_kernel=use_kernel) * g).sum()

        got = run(True, x, ws)
        want = run(False, x, ws)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        gk = jax.grad(lambda xx, w: run(True, xx, w), argnums=(0, 1))(x, ws)
        ge = jax.grad(lambda xx, w: run(False, xx, w), argnums=(0, 1))(x, ws)
        for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(ge)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=1e-4)

    def test_layer_attention_kernel_knob(self):
        """SelfAttentionLayer.attentionKernel plumbs through to the op:
        True (interpret-mode kernel here) must match the default einsum
        path through a full MLN forward."""
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (GlobalPoolingLayer,
                                                       OutputLayer,
                                                       SelfAttentionLayer)
        from deeplearning4j_tpu.train import Adam

        x = np.asarray(RNG.normal(size=(2, 16, 16)), np.float32)
        outs = {}
        for knob in (True, False):
            conf = (NeuralNetConfiguration.Builder().seed(9)
                    .updater(Adam(1e-3)).list()
                    .layer(SelfAttentionLayer(nOut=32, nHeads=4,
                                              attentionKernel=knob))
                    .layer(GlobalPoolingLayer())
                    .layer(OutputLayer(nOut=3, lossFunction="MCXENT"))
                    .setInputType(InputType.recurrent(16, 16)).build())
            net = MultiLayerNetwork(conf).init()
            outs[knob] = np.asarray(net.output(x).toNumpy())
        np.testing.assert_allclose(outs[True], outs[False],
                                   atol=2e-5, rtol=1e-4)

    def test_auto_route_disabled_under_active_mesh(self, monkeypatch):
        """use_kernel=None (auto) must NOT take the monolithic pallas_call
        while a global mesh context is active (ParallelWrapper's sharded
        fit traces inside ``with mesh:``) — GSPMD would all-gather the
        sharded operands. Explicit use_kernel=True still overrides."""
        import deeplearning4j_tpu.ops.pallas_kernels as pk
        from deeplearning4j_tpu.ops import nn_defs

        calls = []

        def stub(q, k, v, heads, *a, **kw):
            calls.append(1)
            return jnp.zeros_like(q)

        monkeypatch.setattr(pk, "mha_attention_packed", stub)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        x, ws = self._setup()

        def run(use_kernel):
            return nn_defs.multi_head_attention(
                x, x, ws["wq"], ws["wk"], ws["wv"], ws["wo"], 4,
                use_kernel=use_kernel)

        run(None)
        assert len(calls) == 1          # auto, no mesh: kernel route
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        with jax.set_mesh(mesh):
            run(None)
            assert len(calls) == 1      # auto under mesh: einsum route
            run(True)
            assert len(calls) == 2      # explicit force still respected

    def test_masked_and_cross_length_stay_on_einsum(self):
        """Mask or Tq != Tk makes the case ineligible — use_kernel=True must
        not change results (the einsum path serves it)."""
        from deeplearning4j_tpu.ops.nn_defs import multi_head_attention

        x, ws = self._setup()
        mask = jnp.asarray(RNG.integers(0, 2, (2, 32)).astype(np.float32))
        a = multi_head_attention(x, x, ws["wq"], ws["wk"], ws["wv"],
                                 ws["wo"], 4, mask=mask, use_kernel=True)
        b = multi_head_attention(x, x, ws["wq"], ws["wk"], ws["wv"],
                                 ws["wo"], 4, mask=mask, use_kernel=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        xkv = _rand(2, 16, 24)   # cross-attention, Tk != Tq
        c = multi_head_attention(x, xkv, ws["wq"], ws["wk"], ws["wv"],
                                 ws["wo"], 4, use_kernel=True)
        d = multi_head_attention(x, xkv, ws["wq"], ws["wk"], ws["wv"],
                                 ws["wo"], 4, use_kernel=False)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d), atol=1e-6)


class TestActiveMeshProbe:
    """active_global_mesh() is ONE public probe (get_abstract_mesh): it
    reports the ``jax.set_mesh`` context the package's sharded callers
    open, inside and outside a jit trace, and nothing else."""

    def test_sees_set_mesh_context_outside_and_inside_jit(self):
        from deeplearning4j_tpu.ops.pallas_kernels import active_global_mesh
        from deeplearning4j_tpu.parallel import make_mesh

        mesh = make_mesh({"data": jax.device_count()})
        seen = []

        @jax.jit
        def traced(x):
            seen.append(active_global_mesh())
            return x + 1

        with jax.set_mesh(mesh):
            got = active_global_mesh()
            traced(jnp.zeros(()))
        assert got is not None and dict(got.shape) == dict(mesh.shape)
        assert seen[0] is not None and not seen[0].empty

    def test_no_context_means_no_mesh_without_warning(self):
        import warnings

        from deeplearning4j_tpu.ops.pallas_kernels import active_global_mesh

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert active_global_mesh() is None

    def test_parallel_wrapper_fit_traces_under_visible_mesh(self, monkeypatch):
        """The caller the probe exists for: ParallelWrapper.fit must open
        a context the probe sees while the layer DSL's step traces."""
        from deeplearning4j_tpu.data import DataSet
        from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        from deeplearning4j_tpu.parallel import ParallelWrapper
        from deeplearning4j_tpu.train import Sgd

        seen = []
        real = jax.random.split

        def spy(*a, **kw):       # fit() calls this inside its mesh context
            seen.append(pk.active_global_mesh())
            return real(*a, **kw)

        conf = (NeuralNetConfiguration.Builder().seed(0).updater(Sgd(0.1))
                .list().layer(DenseLayer(nIn=4, nOut=8, activation="relu"))
                .layer(OutputLayer(nIn=8, nOut=2, lossFunction="MCXENT"))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = RNG.standard_normal((16, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[RNG.integers(0, 2, 16)]
        monkeypatch.setattr(jax.random, "split", spy)
        ParallelWrapper(net).fit(DataSet(x, y))
        assert seen and all(m is not None and not m.empty for m in seen)
        assert pk.active_global_mesh() is None
