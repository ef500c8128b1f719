"""SameDiff graph engine tests (ref: SameDiffTests / SameDiffTrainingTest in
nd4j platform-tests)."""
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig, VariableType
from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.train import Adam, Sgd
import jax.numpy as jnp


class TestGraphBuild:
    def test_basic_math(self):
        sd = SameDiff.create()
        a = sd.constant("a", np.array([1.0, 2.0]))
        b = sd.constant("b", np.array([3.0, 4.0]))
        c = a + b
        out = c.eval()
        np.testing.assert_allclose(out.toNumpy(), [4, 6])

    def test_chained_ops_single_graph(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 3))
        w = sd.var("w", np.ones((3, 2), np.float32))
        b = sd.var("b", np.zeros((2,), np.float32))
        z = x.mmul(w) + b
        y = sd.math.tanh(z).rename("y")
        out = sd.output({"x": np.array([[1.0, 2.0, 3.0]], np.float32)}, "y")["y"]
        np.testing.assert_allclose(out.toNumpy(), np.tanh([[6.0, 6.0]]), rtol=1e-6)

    def test_variable_types(self):
        sd = SameDiff.create()
        v = sd.var("v", np.zeros((2, 2)))
        c = sd.constant("c", 1.0)
        p = sd.placeHolder("p", shape=(2, 2))
        assert v.varType == VariableType.VARIABLE
        assert c.varType == VariableType.CONSTANT
        assert p.varType == VariableType.PLACEHOLDER

    def test_namespaces_and_reductions(self):
        sd = SameDiff.create()
        x = sd.constant("x", np.array([[1.0, 2.0], [3.0, 4.0]]))
        s = x.sum(1)
        m = sd.reduce.mean(x)
        np.testing.assert_allclose(s.eval().toNumpy(), [3, 7])
        assert float(m.eval().toNumpy()) == 2.5

    def test_multi_output_op(self):
        sd = SameDiff.create()
        B, T, I, H = 2, 3, 4, 5
        x = sd.placeHolder("x", shape=(B, T, I))
        h0 = sd.constant("h0", np.zeros((B, H), np.float32))
        c0 = sd.constant("c0", np.zeros((B, H), np.float32))
        w = sd.var("w", np.random.randn(I, 4 * H).astype(np.float32) * 0.1)
        rw = sd.var("rw", np.random.randn(H, 4 * H).astype(np.float32) * 0.1)
        b = sd.var("b", np.zeros((4 * H,), np.float32))
        ys, (hT, cT) = sd.rnn.lstmLayer(x, h0, c0, w, rw, b)
        out = ys.eval({"x": np.random.rand(B, T, I).astype(np.float32)})
        assert out.shape == (B, T, H)
        assert hT.eval({"x": np.random.rand(B, T, I).astype(np.float32)}).shape == (B, H)


class TestGradients:
    def test_calculate_gradients(self):
        sd = SameDiff.create()
        w = sd.var("w", np.array([2.0, 3.0]))
        loss = (w * w).sum().rename("loss")
        sd.setLossVariables("loss")
        grads = sd.calculateGradients({}, ["w"])
        np.testing.assert_allclose(grads["w"].toNumpy(), [4.0, 6.0])
        assert sd.getVariable("w").gradient() is not None

    def test_grad_through_placeholder_graph(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 2))
        w = sd.var("w", np.ones((2, 1), np.float32))
        out = sd.math.tanh(x.mmul(w))
        loss = (out * out).sum().rename("loss")
        sd.setLossVariables("loss")
        g = sd.calculateGradients({"x": np.array([[0.5, 0.5]], np.float32)}, ["w"])
        assert g["w"].shape == (2, 1)
        assert np.isfinite(g["w"].toNumpy()).all()


class TestTraining:
    def test_linear_regression(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(256, 3)).astype(np.float32)
        true_w = np.array([[1.5], [-2.0], [0.5]], np.float32)
        Y = X @ true_w + 0.3

        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 3))
        y = sd.placeHolder("y", shape=(None, 1))
        w = sd.var("w", np.zeros((3, 1), np.float32))
        b = sd.var("b", np.zeros((1,), np.float32))
        pred = x.mmul(w) + b
        loss = sd.loss.mse(y, pred).rename("loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig(updater=Adam(0.1),
                                            dataSetFeatureMapping=["x"],
                                            dataSetLabelMapping=["y"]))
        ds = DataSet(X, Y)
        history = sd.fit(ListDataSetIterator([ds], batch_size=64), epochs=50)
        assert history[-1] < 0.01
        np.testing.assert_allclose(sd.getVariable("w").getArr().toNumpy(), true_w, atol=0.1)
        np.testing.assert_allclose(float(sd.getVariable("b").getArr().toNumpy()[0]), 0.3, atol=0.1)

    def test_softmax_classifier(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        labels = (X[:, 0] + X[:, 1] > 0).astype(int)
        Y = np.eye(2, dtype=np.float32)[labels]

        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        y = sd.placeHolder("y", shape=(None, 2))
        w = sd.var("w", (4, 2), weightInit="XAVIER", seed=7)
        b = sd.var("b", np.zeros((2,), np.float32))
        logits = x.mmul(w) + b
        probs = sd.nn.softmax(logits).rename("probs")
        loss = sd.loss.mcxent(y, probs).rename("loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig(updater=Adam(0.05),
                                            dataSetFeatureMapping=["x"],
                                            dataSetLabelMapping=["y"]))
        sd.fit(DataSet(X, Y), epochs=100)
        pred = sd.output({"x": X}, "probs")["probs"].toNumpy().argmax(-1)
        assert (pred == labels).mean() > 0.95

    def test_regularization_in_training(self):
        sd = SameDiff.create()
        w = sd.var("w", np.array([10.0], np.float32))
        loss = (w * w).sum().rename("loss")
        sd.setLossVariables("loss")
        from deeplearning4j_tpu.train import L2
        sd.setTrainingConfig(TrainingConfig(updater=Sgd(0.1), regularization=[L2(0.1)]))
        sd.fit({}, epochs=1)  # single empty-placeholder batch
        # dict input path: data={} means one batch with no placeholders
        assert float(sd.getVariable("w").getArr().toNumpy()[0]) < 10.0


class TestPersistence:
    def test_save_load_roundtrip(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 3))
        w = sd.var("w", np.random.rand(3, 2).astype(np.float32))
        b = sd.var("b", np.zeros((2,), np.float32))
        out = sd.math.tanh(x.mmul(w) + b).rename("out")

        path = str(tmp_path / "model.sdz")
        sd.save(path)
        sd2 = SameDiff.load(path)

        xv = np.random.rand(4, 3).astype(np.float32)
        o1 = sd.output({"x": xv}, "out")["out"].toNumpy()
        o2 = sd2.output({"x": xv}, "out")["out"].toNumpy()
        np.testing.assert_allclose(o1, o2, rtol=1e-6)

    def test_batch_output_builder(self):
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 2))
        y = sd.math.exp(x).rename("y")
        out = sd.batchOutput().input("x", np.zeros((1, 2), np.float32)).output("y").execSingle()
        np.testing.assert_allclose(out.toNumpy(), [[1.0, 1.0]])


# ----------------------------------------------------------- control flow
# (ref: InferenceSession Enter/Exit/Merge/Switch — here structured lax
# control flow captured as graph nodes, SURVEY §3.2)

def test_if_cond_both_branches():
    sd = SameDiff.create()
    x = sd.placeHolder("x", shape=(3,), dtype=jnp.float32)
    pred = sd.placeHolder("p", shape=(), dtype=jnp.bool_)
    out = sd.ifCond(pred,
                    lambda s, a: s.math.mul(a, 2.0),
                    lambda s, a: s.math.add(a, 10.0),
                    inputs=[x], name="branchy")
    xs = np.array([1.0, 2.0, 3.0], np.float32)
    hi = sd.output({"x": xs, "p": np.bool_(True)}, [out.name])[out.name].toNumpy()
    lo = sd.output({"x": xs, "p": np.bool_(False)}, [out.name])[out.name].toNumpy()
    np.testing.assert_allclose(hi, xs * 2)
    np.testing.assert_allclose(lo, xs + 10)


def test_while_loop_accumulates():
    sd = SameDiff.create()
    i0 = sd.constant("i0", np.int32(0))
    acc0 = sd.constant("acc0", np.float32(1.0))
    i_out, acc_out = sd.whileLoop(
        [i0, acc0],
        lambda s, i, acc: s.math.lt(i, 5),
        lambda s, i, acc: [s.math.add(i, 1), s.math.mul(acc, 2.0)],
        name="loop")
    res = sd.output({}, [i_out.name, acc_out.name])
    assert int(res[i_out.name].toNumpy()) == 5
    assert float(res[acc_out.name].toNumpy()) == 32.0


def test_for_loop_scan_differentiable():
    """forLoop lowers to lax.scan — gradients flow (the TPU-idiomatic
    trainable loop; plain while has no reverse-mode path, as in XLA)."""
    # loop bodies are self-contained sub-graphs: outer vars enter via state
    sd2 = SameDiff.create()
    w2 = sd2.var("w", np.array([[2.0]], np.float32))
    x = sd2.placeHolder("x", shape=(1, 1), dtype=jnp.float32)
    xN, wN = sd2.forLoop(3, [x, w2],
                         lambda s, i, xx, ww: [s.linalg.matmul(xx, ww), ww],
                         name="powloop")
    val = sd2.output({"x": np.array([[1.0]], np.float32)}, [xN.name])[xN.name]
    assert float(val.toNumpy()) == 8.0  # 2^3
    sd2.setLossVariables(xN.name)
    grads = sd2.calculateGradients({"x": np.array([[1.0]], np.float32)}, ["w"])
    assert abs(float(grads["w"].toNumpy()) - 12.0) < 1e-5  # d(w^3)/dw = 3w^2


def test_grad_through_if_cond():
    sd = SameDiff.create()
    w = sd.var("w", np.array([3.0], np.float32))
    p = sd.placeHolder("p", shape=(), dtype=jnp.bool_)
    out = sd.ifCond(p,
                    lambda s, a: s.math.mul(a, a),      # w^2
                    lambda s, a: s.math.mul(a, 5.0),    # 5w
                    inputs=[w])
    sd.setLossVariables(out.name)
    g_true = sd.calculateGradients({"p": np.bool_(True)}, ["w"])["w"].toNumpy()
    g_false = sd.calculateGradients({"p": np.bool_(False)}, ["w"])["w"].toNumpy()
    np.testing.assert_allclose(g_true, [6.0], atol=1e-6)
    np.testing.assert_allclose(g_false, [5.0], atol=1e-6)


def test_control_flow_save_load_roundtrip(tmp_path):
    sd = SameDiff.create()
    i0 = sd.constant("i0", np.int32(0))
    acc0 = sd.constant("acc0", np.float32(1.0))
    i_out, acc_out = sd.whileLoop(
        [i0, acc0],
        lambda s, i, acc: s.math.lt(i, 4),
        lambda s, i, acc: [s.math.add(i, 1), s.math.mul(acc, 3.0)],
        name="loop")
    p = str(tmp_path / "cf.sdz")
    sd.save(p)
    sd2 = SameDiff.load(p)
    out = sd2.output({}, [acc_out.name])[acc_out.name]
    assert float(out.toNumpy()) == 81.0


class TestEvaluateApi:
    def test_evaluate_classifier(self):
        """sd.evaluate(iterator, output, Evaluation) — ref: SameDiff.evaluate."""
        from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
        from deeplearning4j_tpu.eval import Evaluation
        rng = np.random.default_rng(4)
        X = rng.normal(size=(128, 4)).astype(np.float32)
        labels = (X.sum(-1) > 0).astype(int)
        Y = np.eye(2, dtype=np.float32)[labels]

        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        y = sd.placeHolder("y", shape=(None, 2))
        w = sd.var("w", np.zeros((4, 2), np.float32))
        b = sd.var("b", np.zeros((2,), np.float32))
        logits = x.mmul(w) + b
        probs = sd.nn.softmax(logits).rename("probs")
        loss = sd.loss.mcxent(y, probs).rename("loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig(updater=Adam(0.1),
                                            dataSetFeatureMapping=["x"],
                                            dataSetLabelMapping=["y"]))
        it = ListDataSetIterator([DataSet(X, Y)], batch_size=64)
        sd.fit(it, epochs=40)
        ev = sd.evaluate(ListDataSetIterator([DataSet(X, Y)], batch_size=64),
                         "probs", Evaluation())
        assert ev.accuracy() > 0.9, ev.stats()


def test_random_and_updaters_namespaces():
    """sd.random (ref: SDRandom) and sd.updaters (ref: libnd4j updater ops)
    are graph namespaces over the same registry; static args (shape,
    hyperparams) pass as kwargs."""
    import jax
    sd = SameDiff.create()
    k = sd.constant("key", jax.random.PRNGKey(0))
    r = sd.random.normal(k, shape=(4,))
    out = sd.output({}, r.name)[r.name].toNumpy()
    assert out.shape == (4,) and np.isfinite(out).all()

    sd2 = SameDiff.create()
    g = sd2.var("g", np.ones(3, np.float32))
    u = sd2.updaters.sgdUpdater(g, lr=0.5)
    np.testing.assert_allclose(sd2.output({}, u.name)[u.name].toNumpy(), 0.5)


def _fit_parity_model(seed=17):
    rng = np.random.RandomState(seed)
    sd = SameDiff.create()
    x = sd.placeHolder("x", shape=(None, 4))
    y = sd.placeHolder("y", shape=(None, 1))
    w = sd.var("w", (rng.rand(4, 8).astype(np.float32) - 0.5))
    b = sd.var("b", np.zeros((8,), np.float32))
    w2 = sd.var("w2", (rng.rand(8, 1).astype(np.float32) - 0.5))
    h = sd.math.tanh(x.mmul(w) + b)
    loss = sd.loss.mse(y, h.mmul(w2)).rename("loss")
    sd.setLossVariables("loss")
    sd.setTrainingConfig(TrainingConfig(updater=Adam(1e-2),
                                        dataSetFeatureMapping=["x"],
                                        dataSetLabelMapping=["y"]))
    batches = [{"x": rng.rand(8, 4).astype(np.float32),
                "y": rng.rand(8, 1).astype(np.float32)} for _ in range(11)]
    return sd, batches


class TestFusedFit:
    """SameDiff.fit's de-dispatched multi-step path (round 4: fuseSteps
    lax.scan, the fix that took TF-import config #4 from 29k to >100k
    tok/s on TPU) must be loss- and param-identical to the per-step path."""

    def test_fused_matches_per_step(self):
        runs = {}
        for name, fuse in (("fused", 4), ("single", 0)):
            sd, batches = _fit_parity_model()
            sd.fuseSteps = fuse
            hist = sd.fit(batches)   # 11 batches: 2 chunks of 4 + 3 singles
            runs[name] = (hist, {n: np.asarray(sd.getVariable(n).getArr().toNumpy())
                                 for n in ("w", "b", "w2")})
        assert len(runs["fused"][0]) == len(runs["single"][0]) == 11
        np.testing.assert_allclose(runs["fused"][0], runs["single"][0],
                                   rtol=1e-6)
        for n in ("w", "b", "w2"):
            np.testing.assert_allclose(runs["fused"][1][n],
                                       runs["single"][1][n], atol=1e-6)

    def test_unknown_listeners_force_per_step_history(self):
        """A listener WITHOUT requiresModelAtIteration gets the conservative
        per-step path (the fused path may only replay callbacks when the
        listener declared it doesn't need the live model mid-chunk)."""
        calls = []

        class L:
            def iterationDone(self, model, it, ep):
                calls.append((it, float(model.score())))

        sd, batches = _fit_parity_model()
        sd.listeners = [L()]
        hist = sd.fit(batches[:5])
        assert [c[0] for c in calls] == [1, 2, 3, 4, 5]
        np.testing.assert_allclose([c[1] for c in calls], hist, rtol=1e-6)

    def test_score_listener_fuses_with_identical_callbacks(self):
        """Round-5 verdict #2: a score-only listener must NOT de-fuse
        SameDiff.fit (config #4's 146k tok/s has a ScoreListener attached in
        the representative setup) — callback sequence (iteration, score) and
        final params identical to the per-step path, via the same
        _chunk_limit/replay machinery as MultiLayerNetwork."""
        from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener

        runs = {}
        for name, fuse in (("fused", 4), ("single", 0)):
            sd, batches = _fit_parity_model()
            sd.fuseSteps = fuse
            seq = []

            class Rec(ScoreIterationListener):
                def iterationDone(self, model, it, ep):
                    seq.append((it, float(model.score())))

            sd.listeners = [Rec()]
            hist = sd.fit(batches)   # 11 batches: 2 chunks of 4 + 3 singles
            runs[name] = (hist, seq,
                          {n: np.asarray(sd.getVariable(n).getArr().toNumpy())
                           for n in ("w", "b", "w2")})
        assert len(runs["fused"][1]) == len(runs["single"][1]) == 11
        assert [i for i, _ in runs["fused"][1]] == \
            [i for i, _ in runs["single"][1]]
        np.testing.assert_allclose([s for _, s in runs["fused"][1]],
                                   [s for _, s in runs["single"][1]],
                                   rtol=1e-6)
        for n in ("w", "b", "w2"):
            np.testing.assert_allclose(runs["fused"][2][n],
                                       runs["single"][2][n], atol=1e-6)

    def test_model_boundary_listener_sees_current_values(self):
        """A listener needing the live model at iteration k observes exactly
        the values the per-step path shows at k (scan flushed there)."""
        snaps = {}

        class SnapAt:
            def __init__(self, tag, at):
                self.tag, self.at = tag, at

            def requiresModelAtIteration(self, it):
                return it in self.at

            def iterationDone(self, model, it, ep):
                if it in self.at:
                    snaps.setdefault(self.tag, {})[it] = np.asarray(
                        model.getVariable("w").getArr().toNumpy()).copy()

        for tag, fuse in (("fused", 4), ("single", 0)):
            sd, batches = _fit_parity_model()
            sd.fuseSteps = fuse
            sd.listeners = [SnapAt(tag, {3, 7})]
            sd.fit(batches)
        for it in (3, 7):
            np.testing.assert_allclose(snaps["fused"][it],
                                       snaps["single"][it], atol=1e-6)

    def test_replay_lag_zero_streams_per_chunk(self):
        """listenerReplayLag=0 (live-streaming mode): callbacks still fire in
        exact order with exact scores — parity with the per-step path."""
        runs = {}
        for name, (fuse, lag) in (("lag0", (4, 0)), ("single", (0, 0))):
            sd, batches = _fit_parity_model()
            sd.fuseSteps = fuse
            sd.listenerReplayLag = lag
            seq = []

            class Rec:
                def requiresModelAtIteration(self, it):
                    return False

                def iterationDone(self, model, it, ep):
                    seq.append((it, float(model.score())))

            sd.listeners = [Rec()]
            sd.fit(batches)
            runs[name] = seq
        assert [i for i, _ in runs["lag0"]] == [i for i, _ in runs["single"]]
        np.testing.assert_allclose([s for _, s in runs["lag0"]],
                                   [s for _, s in runs["single"]], rtol=1e-6)

    def test_exception_mid_fit_preserves_completed_callbacks(self):
        """An exception raised while lagged replays are still BUFFERED must
        not lose the completed chunks' callbacks/scores — the except-path
        drain delivers them. The failure is injected into the THIRD fused
        chunk's dispatch, so two chunks sit undelivered in the replay queue
        at raise time (a shape-mismatched batch would be drained as a
        single and deliver them on the normal path, proving nothing)."""
        sd, batches = _fit_parity_model()
        sd.fuseSteps = 4
        calls = []

        class Rec:
            def requiresModelAtIteration(self, it):
                return False

            def iterationDone(self, model, it, ep):
                calls.append((it, float(model.score())))

        sd.listeners = [Rec()]
        orig = sd._train_multi_fn()
        n = {"calls": 0}

        def bomb(*args):
            n["calls"] += 1
            if n["calls"] == 3:
                raise RuntimeError("injected chunk failure")
            return orig(*args)

        sd._jit_cache["train_multi"] = bomb
        with pytest.raises(RuntimeError, match="injected chunk failure"):
            sd.fit((batches + batches)[:12])   # 3 same-signature chunks of 4
        # the two completed chunks' callbacks arrived, in order
        assert [i for i, _ in calls] == list(range(1, 9))
        assert all(np.isfinite(s) for _, s in calls)

    def test_dtype_change_not_stacked_into_chunk(self):
        """Round-4 advisor: same-shaped batches of different dtypes must not
        np.stack into one fused chunk (silent promotion). Parity with the
        per-step path across an fp32/fp64 batch sequence proves the
        signature split."""
        runs = {}
        for name, fuse in (("fused", 4), ("single", 0)):
            sd, batches = _fit_parity_model()
            sd.fuseSteps = fuse
            mixed = []
            for i, b in enumerate(batches[:8]):
                if i >= 4:
                    b = {k: v.astype(np.float64) for k, v in b.items()}
                mixed.append(b)
            hist = sd.fit(mixed)
            runs[name] = (hist,
                          {n: np.asarray(sd.getVariable(n).getArr().toNumpy())
                           for n in ("w", "b", "w2")})
        np.testing.assert_allclose(runs["fused"][0], runs["single"][0],
                                   rtol=1e-6)
        for n in ("w", "b", "w2"):
            np.testing.assert_allclose(runs["fused"][1][n],
                                       runs["single"][1][n], atol=1e-6)

    def test_shape_change_drains_buffer(self):
        sd, batches = _fit_parity_model()
        small = [{"x": b["x"][:4], "y": b["y"][:4]} for b in batches[:3]]
        hist = sd.fit(batches[:5] + small)
        assert len(hist) == 8
        assert all(np.isfinite(h) for h in hist)


class TestMixedPrecisionTraining:
    """TrainingConfig.computeDtype: bf16 compute over fp32 master params
    (the import-time dtype-rewrite for TF/ONNX-imported graphs — BASELINE
    config #4)."""

    def _build(self, compute_dtype):
        rng = np.random.default_rng(4)
        X = rng.normal(size=(128, 6)).astype(np.float32)
        W = rng.normal(size=(6, 3)).astype(np.float32)
        Y = (X @ W + rng.normal(size=(128, 3)) * 0.05).astype(np.float32)
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 6))
        y = sd.placeHolder("y", shape=(None, 3))
        w1 = sd.var("w1", (rng.normal(size=(6, 16)) * 0.3).astype(np.float32))
        w2 = sd.var("w2", (rng.normal(size=(16, 3)) * 0.3).astype(np.float32))
        h = sd.math.tanh(x.mmul(w1))
        pred = h.mmul(w2)
        sd.loss.mse(y, pred).rename("loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig(updater=Adam(0.05),
                                            dataSetFeatureMapping=["x"],
                                            dataSetLabelMapping=["y"],
                                            computeDtype=compute_dtype))
        return sd, DataSet(X, Y)

    def test_bf16_trains_to_fp32_quality(self):
        sd32, ds = self._build(None)
        h32 = sd32.fit(ds, epochs=200)
        sd16, ds = self._build("HALF")
        h16 = sd16.fit(ds, epochs=200)
        assert h32[-1] < 0.05
        # bf16 compute converges to the same loss basin (loose tol: 8-bit
        # mantissa), and params stay fp32 masters
        assert h16[-1] < max(2 * h32[-1], 0.08)
        w1 = sd16.getVariable("w1").getArr().jax
        assert w1.dtype == jnp.float32

    def test_compute_dtype_survives_serde(self, tmp_path):
        sd16, ds = self._build("HALF")
        sd16.fit(ds, epochs=2)
        p = str(tmp_path / "mp.zip")
        sd16.save(p, save_updater_state=True)
        back = SameDiff.load(p)
        assert back._training_config.computeDtype == "HALF"
        h = back.fit(ds, epochs=2)
        assert np.isfinite(h[-1])
