"""Distributed tests on the virtual 8-device CPU mesh (the reference's
Spark-local[N]/DummyTransport philosophy, SURVEY.md §4.2): DP parity vs
single-device, ring/Ulysses attention vs the full-attention oracle, gradient
compression semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (
    ParallelInference, ParallelWrapper, make_mesh, reference_attention, ring_self_attention,
)
from deeplearning4j_tpu.parallel.gradient_sharing import (
    AdaptiveThresholdAlgorithm, gradient_compression, threshold_encode,
)
from deeplearning4j_tpu.train import Sgd


def mlp_conf(seed=7):
    return (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(nIn=6, nOut=16, activation="TANH"))
            .layer(OutputLayer(nIn=16, nOut=3, lossFunction="MCXENT"))
            .build())


class TestMesh:
    def test_make_mesh_axes(self):
        mesh = make_mesh({"data": 4, "model": 2})
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_default_all_data(self):
        mesh = make_mesh()
        assert mesh.shape["data"] == 8


class TestDataParallel:
    def test_dp_matches_single_device(self):
        """Sharded-DP params after k steps == single-device params (exact
        lockstep psum — the guarantee the reference's averaging only
        approximates)."""
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 6)).astype(np.float32)
        Y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
        it = lambda: ListDataSetIterator([DataSet(X, Y)], batch_size=32)

        single = MultiLayerNetwork(mlp_conf()).init()
        single.fit(it(), epochs=3)

        dp_net = MultiLayerNetwork(mlp_conf()).init()
        pw = ParallelWrapper(dp_net, mesh=make_mesh({"data": 8}))
        pw.fit(it(), epochs=3)

        np.testing.assert_allclose(single.params().toNumpy(), dp_net.params().toNumpy(),
                                   rtol=2e-4, atol=2e-5)

    def test_builder_parity_surface(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        pw = (ParallelWrapper.Builder(net).workers(4).averagingFrequency(5)
              .prefetchBuffer(2).trainingMode("AVERAGING").build())
        assert pw._n == 4

    def test_parallel_inference(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        pi = ParallelInference.Builder(net).workers(8).build()
        x = np.random.rand(13, 6).astype(np.float32)  # deliberately not divisible by 8
        out = pi.output(x)
        assert out.shape == (13, 3)
        np.testing.assert_allclose(out.toNumpy(), net.output(x).toNumpy(), atol=1e-5)


class TestSequenceParallel:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("impl", ["ring", "ring_flash", "ulysses"])
    def test_matches_full_attention(self, causal, impl):
        mesh = make_mesh({"context": 8})
        B, H, T, D = 2, 8, 32, 16  # T divisible by 8; H divisible by 8 for ulysses
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(k1, (B, H, T, D), dtype=jnp.float32)
        k = jax.random.normal(k2, (B, H, T, D), dtype=jnp.float32)
        v = jax.random.normal(k3, (B, H, T, D), dtype=jnp.float32)
        expected = reference_attention(q, k, v, causal=causal)
        got = ring_self_attention(mesh, q, k, v, causal=causal, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def test_ring_attention_differentiable(self):
        mesh = make_mesh({"context": 4})
        B, H, T, D = 1, 2, 16, 8
        q = jax.random.normal(jax.random.key(1), (B, H, T, D))

        def loss_ring(qq):
            return jnp.sum(ring_self_attention(mesh, qq, qq, qq, causal=True) ** 2)

        def loss_ref(qq):
            return jnp.sum(reference_attention(qq, qq, qq, causal=True) ** 2)

        g1 = jax.grad(loss_ring)(q)
        g2 = jax.grad(loss_ref)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_flash_gradients_match_reference(self, causal):
        """The Pallas-backed ring's custom second-ring-pass backward must
        match reference grads for all three operands — incl. the causal
        case where strictly-future blocks skip their kernels entirely."""
        mesh = make_mesh({"context": 4})
        B, H, T, D = 2, 3, 64, 8
        k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
        q = jax.random.normal(k1, (B, H, T, D), jnp.float32) * 0.3
        k = jax.random.normal(k2, (B, H, T, D), jnp.float32) * 0.3
        v = jax.random.normal(k3, (B, H, T, D), jnp.float32) * 0.3

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        ring = loss(lambda q, k, v: ring_self_attention(
            mesh, q, k, v, causal=causal, impl="ring_flash"))
        ref = loss(lambda q, k, v: reference_attention(q, k, v,
                                                       causal=causal))
        gf = jax.grad(ring, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_kernel_route_matches_einsum(self, causal):
        """Ulysses' local full-T attention through the streamed Pallas
        kernel (use_kernel=True, interpret off-TPU) must match its einsum
        path — fwd and grads."""
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            ulysses_attention)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        import functools as ft

        mesh = make_mesh({"context": 4})
        B, H, T, D = 1, 4, 32, 8
        k1, k2, k3 = jax.random.split(jax.random.key(9), 3)
        q = jax.random.normal(k1, (B, H, T, D), jnp.float32) * 0.3
        k = jax.random.normal(k2, (B, H, T, D), jnp.float32) * 0.3
        v = jax.random.normal(k3, (B, H, T, D), jnp.float32) * 0.3
        spec = P(None, None, "context", None)

        def run(use_kernel):
            fn = shard_map(
                ft.partial(ulysses_attention, axis_name="context",
                           causal=causal, use_kernel=use_kernel),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
            return fn(q, k, v)

        np.testing.assert_allclose(np.asarray(run(True)),
                                   np.asarray(run(False)), atol=2e-5)

        def loss(use_kernel):
            def f(q_, k_, v_):
                fn = shard_map(
                    ft.partial(ulysses_attention, axis_name="context",
                               causal=causal, use_kernel=use_kernel),
                    mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                    check_vma=False)
                return jnp.sum(fn(q_, k_, v_) ** 2)
            return f

        ga = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    def test_ulysses_forced_kernel_off_envelope_raises(self):
        """use_kernel=True must not silently fall back to einsum when the
        global T is outside the kernel envelope."""
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            ulysses_attention)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        import functools as ft

        mesh = make_mesh({"context": 2})
        q = jax.random.normal(jax.random.key(2), (1, 2, 36, 8), jnp.float32)
        spec = P(None, None, "context", None)
        fn = shard_map(
            ft.partial(ulysses_attention, axis_name="context",
                       causal=False, use_kernel=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        with pytest.raises(ValueError, match="outside the streamed"):
            fn(q, q, q)  # global T=36: 36 % 8 != 0 -> off-envelope

    def test_ring_flash_higher_order_escape_hatch(self):
        """higher_order_attention() must route the ring to the any-order
        einsum implementation — grad-of-grad works inside the context and
        raises outside it (first-order custom_vjp)."""
        from deeplearning4j_tpu.ops.pallas_kernels import (
            higher_order_attention)
        mesh = make_mesh({"context": 2})
        q = jax.random.normal(jax.random.key(5), (1, 2, 16, 8),
                              jnp.float32) * 0.3

        def loss(s):
            return jnp.sum(ring_self_attention(
                mesh, q * s, q, q, causal=True, impl="ring_flash") ** 2)

        with higher_order_attention():
            h = jax.grad(jax.grad(loss))(1.0)
        assert np.isfinite(float(h))
        with pytest.raises(Exception):
            jax.grad(jax.grad(loss))(1.0)

    @pytest.mark.parametrize("n", [2, 4])
    def test_zigzag_ring_matches_reference(self, n):
        """Balanced causal ring (zigzag layout): fwd + all three grads
        exact vs the full-attention oracle; the whole-array convenience
        owns the permutation round-trip."""
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            zigzag_ring_self_attention)
        mesh = make_mesh({"context": n})
        B, H, T, D = 2, 3, 64, 8
        k1, k2, k3 = jax.random.split(jax.random.key(21), 3)
        q = jax.random.normal(k1, (B, H, T, D), jnp.float32) * 0.3
        k = jax.random.normal(k2, (B, H, T, D), jnp.float32) * 0.3
        v = jax.random.normal(k3, (B, H, T, D), jnp.float32) * 0.3
        want = reference_attention(q, k, v, causal=True)
        got = zigzag_ring_self_attention(mesh, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        gr = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        gz = jax.grad(lambda q, k, v: jnp.sum(zigzag_ring_self_attention(
            mesh, q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-4)

    def test_zigzag_indices_partition(self):
        """The zigzag permutation is a true permutation assigning device d
        chunks (d, 2n-1-d) — the balance invariant."""
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            zigzag_indices)
        T, n = 64, 4
        idx = zigzag_indices(T, n)
        assert sorted(idx.tolist()) == list(range(T))
        c = T // (2 * n)
        shard0 = idx[: T // n]
        assert shard0[:c].tolist() == list(range(0, c))              # chunk 0
        assert shard0[c:].tolist() == list(range(7 * c, 8 * c))      # chunk 7

    def test_zigzag_higher_order_falls_back(self):
        from deeplearning4j_tpu.ops.pallas_kernels import (
            higher_order_attention)
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            zigzag_ring_self_attention)
        mesh = make_mesh({"context": 2})
        q = jax.random.normal(jax.random.key(22), (1, 2, 16, 8),
                              jnp.float32) * 0.3

        def loss(s):
            return jnp.sum(zigzag_ring_self_attention(
                mesh, q * s, q, q) ** 2)

        with higher_order_attention():
            h = jax.grad(jax.grad(loss))(1.0)
        assert np.isfinite(float(h))

    def test_ring_flash_single_shard_degenerates_to_flash(self):
        """axis_size=1: no rotations, just the local streamed kernel."""
        mesh = make_mesh({"context": 1})
        q = jax.random.normal(jax.random.key(3), (1, 2, 32, 8), jnp.float32)
        got = ring_self_attention(mesh, q, q, q, causal=True,
                                  impl="ring_flash")
        want = reference_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


class TestGradientCompression:
    def test_threshold_encode(self):
        g = jnp.asarray([0.5, -0.001, 0.002, -2.0])
        enc = threshold_encode(g, 0.01)
        np.testing.assert_allclose(np.asarray(enc), [0.01, 0.0, 0.0, -0.01])

    def test_residual_carry(self):
        """Small gradients accumulate in the residual until they cross the
        threshold (ref: ResidualPostProcessor semantics)."""
        tx = gradient_compression(AdaptiveThresholdAlgorithm(initial=0.01, decay=1.0))
        params = {"w": jnp.zeros(3)}
        state = tx.init(params)
        g = {"w": jnp.asarray([0.004, 0.0, 0.02])}
        sent1, state = tx.update(g, state)
        assert float(sent1["w"][0]) == 0.0  # below threshold: held back
        assert float(sent1["w"][2]) == pytest.approx(0.01)
        sent2, state = tx.update(g, state)
        sent3, state = tx.update(g, state)
        # 0.004*3 = 0.012 crossed the 0.01 threshold by step 3
        assert float(sent3["w"][0]) == pytest.approx(0.01)

    def test_compression_chain_trains(self):
        import optax
        tx = optax.chain(gradient_compression(AdaptiveThresholdAlgorithm(initial=0.1, max_t=10.0)),
                         optax.sgd(0.2))
        w = jnp.asarray([1.0, -1.0])
        state = tx.init(w)
        for _ in range(200):
            grads = 2 * w  # d/dw ||w||^2
            updates, state = tx.update(grads, state)
            w = optax.apply_updates(w, updates)
        assert float(jnp.sum(jnp.abs(w))) < 0.05


class TestLongContext:
    def test_ring_attention_long_sequence_sharded(self):
        """Long-context path (SURVEY §5.7 beyond-parity): a 2048-token
        sequence over 8 context shards matches the full-attention oracle —
        each device only ever holds T/8=256 of the keys/values."""
        mesh = make_mesh({"context": 8})
        B, H, T, D = 1, 4, 2048, 32
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(k1, (B, H, T, D), jnp.float32) * 0.1
        k = jax.random.normal(k2, (B, H, T, D), jnp.float32) * 0.1
        v = jax.random.normal(k3, (B, H, T, D), jnp.float32)
        got = ring_self_attention(mesh, q, k, v, causal=True, impl="ring")
        want = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_ring_flash_long_sequence_sharded(self):
        """Same 2048-token/8-shard case through the Pallas-backed ring —
        fwd AND grads vs the oracle (the einsum ring's backward saves every
        rotated k/v copy; this one re-rotates instead, O(T_local))."""
        mesh = make_mesh({"context": 8})
        B, H, T, D = 1, 2, 2048, 16
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(k1, (B, H, T, D), jnp.float32) * 0.1
        k = jax.random.normal(k2, (B, H, T, D), jnp.float32) * 0.1
        v = jax.random.normal(k3, (B, H, T, D), jnp.float32)
        got = ring_self_attention(mesh, q, k, v, causal=True,
                                  impl="ring_flash")
        want = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        gf = jax.grad(lambda q: jnp.sum(ring_self_attention(
            mesh, q, k, v, causal=True, impl="ring_flash") ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-3)


class TestEarlyStoppingParallel:
    def test_early_stopping_over_parallel_wrapper(self):
        """(ref: EarlyStoppingParallelTrainer) — the ES loop drives sharded
        DP epochs; best model and termination bookkeeping behave as in the
        single-device trainer."""
        from deeplearning4j_tpu.data.dataset import DataSet, ListDataSetIterator
        from deeplearning4j_tpu.earlystopping import (
            DataSetLossCalculator, EarlyStoppingConfiguration,
            EarlyStoppingParallelTrainer, InMemoryModelSaver,
            MaxEpochsTerminationCondition)
        from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.train.updaters import Adam

        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
        ds = DataSet(x, y)
        conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(nIn=4, nOut=16, activation="RELU"))
                .layer(OutputLayer(nIn=16, nOut=2, activation="SOFTMAX",
                                   lossFunction="MCXENT"))
                .build())
        net = MultiLayerNetwork(conf).init()
        esc = (EarlyStoppingConfiguration.Builder()
               .epochTerminationConditions(MaxEpochsTerminationCondition(5))
               .scoreCalculator(DataSetLossCalculator(
                   ListDataSetIterator(ds.batchBy(16))))
               .modelSaver(InMemoryModelSaver())
               .build())
        trainer = EarlyStoppingParallelTrainer(
            esc, net, ListDataSetIterator(ds.batchBy(16)))
        result = trainer.fit()
        assert result.totalEpochs == 5
        assert result.bestModel is not None
        scores = list(result.scoreVsEpoch.values())
        assert scores[-1] < scores[0]  # DP epochs actually trained the model
