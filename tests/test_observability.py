"""Observability subsystem tests: StatsListener → storage SPI → TensorBoard
export, plus the profiler's span/Chrome-trace/panic paths (reference analog:
deeplearning4j-ui-model's StatsListener tests + nd4j OpProfiler tests,
SURVEY.md §5.1/§5.5)."""
import json
import math
import os

import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.profiler import (
    OpProfiler, PanicException, ProfilerConfig, ProfilingListener,
)
from deeplearning4j_tpu.profiler.profiler import check_tree_finite
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.ui import (
    FileStatsStorage, InMemoryStatsStorage, StatsListener,
    StatsUpdateConfiguration, TensorBoardExporter, TensorBoardStatsListener,
)


def tiny_net(seed=12345):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(lr=1e-2))
            .list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="MCXENT"))
            .setInputType(InputType.feedForward(5))
            .build())
    return MultiLayerNetwork(conf).init()


def tiny_data(n=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return DataSet(x, y)


class TestStatsListener:
    def test_reports_capture_params_grads_updates(self):
        storage = InMemoryStatsStorage()
        lst = StatsListener(storage, frequency=1)
        net = tiny_net()
        net.setListeners(lst)
        net.fit(tiny_data(), epochs=3)

        sessions = storage.listSessionIDs()
        assert sessions == [lst.sessionId]
        reports = storage.getUpdates(lst.sessionId, "StatsListener", "worker_0")
        assert len(reports) == 3
        rep = reports[-1]
        assert math.isfinite(rep["score"])
        assert rep["learningRate"] == pytest.approx(1e-2)
        # params: 2 layers x (W, b)
        assert set(rep["parameterStats"]) == {"0/W", "0/b", "1/W", "1/b"}
        assert rep["parameterStats"]["0/W"]["meanMagnitude"] > 0
        # gradient + update trees came back from the stats step variant
        assert set(rep["gradientStats"]) == set(rep["parameterStats"])
        assert set(rep["updateStats"]) == set(rep["parameterStats"])
        # the update:param ratio — Adam lr=1e-2 on fresh params: > 0, sane
        assert 0 < rep["updateRatios"]["0/W"] < 10
        # histograms have the configured bin count and mass
        h = rep["parameterHistograms"]["0/W"]
        assert len(h["counts"]) == 20
        assert sum(h["counts"]) == 5 * 8

    def test_static_info_and_frequency(self):
        storage = InMemoryStatsStorage()
        lst = StatsListener(storage, frequency=2)
        net = tiny_net()
        net.setListeners(lst)
        net.fit(tiny_data(), epochs=5)
        reports = storage.getUpdates(lst.sessionId, "StatsListener", "worker_0")
        assert len(reports) == 2  # iterations 2, 4
        info = storage.getStaticInfo(lst.sessionId, "StatsListener", "worker_0")
        assert info["modelClass"] == "MultiLayerNetwork"
        assert info["numParams"] == net.numParams()

    def test_stats_training_matches_plain_training(self):
        """The stats step variant must be bit-identical math to the plain
        step — collecting stats must not change training."""
        ds = tiny_data()
        a, b = tiny_net(), tiny_net()
        b.setListeners(StatsListener(InMemoryStatsStorage()))
        a.fit(ds, epochs=4)
        b.fit(ds, epochs=4)
        np.testing.assert_allclose(a.params().toNumpy(), b.params().toNumpy(),
                                   rtol=0, atol=0)


class TestStorage:
    def test_file_storage_roundtrip(self, tmp_path):
        path = str(tmp_path / "stats.jsonl")
        storage = FileStatsStorage(path)
        storage.putStaticInfo("s1", "T", "w0", {"a": 1})
        storage.putUpdate("s1", "T", "w0", {"iteration": 1, "score": 0.5, "timestamp": 10.0})
        storage.putUpdate("s1", "T", "w1", {"iteration": 1, "score": 0.7, "timestamp": 11.0})

        fresh = FileStatsStorage(path)  # re-open: durability
        assert fresh.listSessionIDs() == ["s1"]
        assert fresh.listWorkerIDsForSession("s1") == ["w0", "w1"]
        assert fresh.getStaticInfo("s1", "T", "w0") == {"a": 1}
        assert fresh.getUpdates("s1", "T", "w0")[0]["score"] == 0.5
        assert fresh.getAllUpdatesAfter("s1", "T", "w1", 10.5)[0]["score"] == 0.7

    def test_file_storage_tolerates_torn_tail(self, tmp_path):
        path = str(tmp_path / "stats.jsonl")
        storage = FileStatsStorage(path)
        storage.putUpdate("s1", "T", "w0", {"iteration": 1, "score": 0.5})
        with open(path, "a") as f:
            f.write('{"kind": "update", "sess')  # simulated crash mid-write
        assert len(FileStatsStorage(path).getUpdates("s1", "T", "w0")) == 1

    def test_storage_listener_callbacks(self):
        storage = InMemoryStatsStorage()
        events = []
        storage.registerStatsStorageListener(events.append)
        storage.putUpdate("s", "T", "w", {"iteration": 0})
        assert events and events[0]["kind"] == "update"


def _read_tfevents(path):
    """Readback through TF's own event iterator — proves the hand-rolled
    wire format is byte-valid."""
    tf = pytest.importorskip("tensorflow")
    events = list(tf.compat.v1.train.summary_iterator(path))
    return events


class TestTensorBoard:
    def test_export_readback_with_tensorflow(self, tmp_path):
        storage = InMemoryStatsStorage()
        lst = StatsListener(storage, frequency=1)
        net = tiny_net()
        net.setListeners(lst)
        net.fit(tiny_data(), epochs=2)

        logdir = str(tmp_path / "tb")
        paths = TensorBoardExporter.export(storage, lst.sessionId, logdir)
        assert len(paths) == 1 and os.path.exists(paths[0])

        events = _read_tfevents(paths[0])
        assert events[0].file_version == "brain.Event:2"
        scalar_tags = set()
        histo_tags = set()
        for ev in events[1:]:
            for v in ev.summary.value:
                if v.HasField("simple_value"):
                    scalar_tags.add(v.tag)
                    assert math.isfinite(v.simple_value)
                elif v.HasField("histo"):
                    histo_tags.add(v.tag)
                    assert v.histo.num > 0
                    assert len(v.histo.bucket) == len(v.histo.bucket_limit)
        assert "train/score" in scalar_tags
        assert "train/learning_rate" in scalar_tags
        assert "update_ratio_log10/0/W" in scalar_tags
        assert "parameters/0/W" in histo_tags
        assert "gradients/1/W" in histo_tags

    def test_live_listener_streams(self, tmp_path):
        logdir = str(tmp_path / "tb_live")
        lst = TensorBoardStatsListener(logdir, frequency=1)
        net = tiny_net()
        net.setListeners(lst)
        net.fit(tiny_data(), epochs=2)
        lst.close()
        files = [f for f in os.listdir(logdir) if "tfevents" in f]
        assert len(files) == 1
        events = _read_tfevents(os.path.join(logdir, files[0]))
        steps = sorted({e.step for e in events if e.summary.value})
        assert steps == [1, 2]


class TestProfiler:
    def test_spans_and_chrome_trace(self, tmp_path):
        prof = OpProfiler()
        with prof.span("outer", phase="train"):
            with prof.span("inner"):
                pass
        assert {s.name for s in prof.spans} == {"outer", "inner"}
        summary = prof.summary()
        assert summary["outer"]["count"] == 1
        assert summary["outer"]["total_ms"] >= summary["inner"]["total_ms"]

        path = prof.export_chrome_trace(str(tmp_path / "trace.json"))
        trace = json.load(open(path))
        names = {e["name"] for e in trace["traceEvents"]}
        assert names == {"outer", "inner"}
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in trace["traceEvents"])

    def test_profiling_listener_records_iterations(self, tmp_path):
        prof = OpProfiler()
        net = tiny_net()
        net.setListeners(ProfilingListener(prof))
        net.fit(tiny_data(), epochs=3)
        iters = [s for s in prof.spans if s.name == "iteration"]
        assert len(iters) == 2  # N-1 gaps between N iterationDone calls

    def test_check_tree_finite(self):
        check_tree_finite({"a": np.ones(3), "b": [np.zeros(2)]}, "ok")
        with pytest.raises(PanicException, match="NaN"):
            check_tree_finite({"a": np.array([1.0, np.nan])}, "bad")
        with pytest.raises(PanicException, match="Inf"):
            check_tree_finite({"a": np.array([1.0, np.inf])}, "bad",
                              check_nan=True, check_inf=True)

    def test_nan_panic_on_diverging_model(self):
        class FakeModel:
            _params = {"w": np.array([1.0])}
            def score(self):
                return float("nan")
        lst = ProfilingListener(config=ProfilerConfig(checkForNAN=True))
        with pytest.raises(PanicException, match="NaN score"):
            lst.iterationDone(FakeModel(), 1, 0)

    def test_panic_mode_catches_param_nan(self):
        lst = ProfilingListener(config=ProfilerConfig(checkForNAN=True))
        class FakeModel:
            _params = {"w": np.array([1.0, np.nan])}
            def score(self):
                return 0.5
        with pytest.raises(PanicException, match="parameters"):
            lst.iterationDone(FakeModel(), 1, 0)

    @pytest.mark.parametrize("kind, peak", [
        ("TPU v5 lite", 197e12), ("TPU v4", 275e12),
        ("TPU v5", 459e12), ("TPU v6 lite", 918e12)])
    def test_peak_flops_is_keyed_by_the_real_device_kind(self, kind, peak):
        from types import SimpleNamespace

        from deeplearning4j_tpu.profiler.profiler import peak_flops

        assert peak_flops(SimpleNamespace(device_kind=kind,
                                          platform="tpu")) == peak

    @pytest.mark.parametrize("kind", ["cpu", "v5e", "TPU v7", None])
    def test_peak_flops_of_an_unknown_device_raises(self, kind):
        """No default: an MFU against an assumed peak hides the device."""
        from types import SimpleNamespace

        import jax

        from deeplearning4j_tpu.profiler.profiler import peak_flops

        with pytest.raises(ValueError, match="no published bf16 peak"):
            peak_flops(SimpleNamespace(device_kind=kind, platform="x"))
        with pytest.raises(ValueError, match="no published bf16 peak"):
            peak_flops(jax.devices()[0])        # the suite's CPU device


class TestHtmlReport:
    def test_report_renders_all_panels(self, tmp_path):
        from deeplearning4j_tpu.ui.html_report import render_report
        storage = InMemoryStatsStorage()
        lst = StatsListener(storage, frequency=1)
        net = tiny_net()
        net.setListeners(lst)
        net.fit(tiny_data(), epochs=5)
        path = render_report(storage, lst.sessionId, str(tmp_path / "report.html"))
        page = open(path).read()
        assert "<svg" in page and "Score" in page
        assert "Update:param ratio" in page
        assert "Last-iteration histograms" in page
        assert "MultiLayerNetwork" in page
        # every panel's polyline has points
        assert 'points=""' not in page


class TestSameDiffStats:
    def test_stats_listener_on_samediff_training(self):
        """StatsListener attaches to SameDiff.fit too (param stats from the
        trainable-variable values; grads come via the param-delta fallback)."""
        from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
        rng = np.random.RandomState(0)
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        yv = sd.placeHolder("y", shape=(None, 1))
        w = sd.var("w", np.zeros((4, 1), np.float32))
        pred = x.mmul(w)
        loss = sd.loss.mse(yv, pred).rename("loss")
        sd.setLossVariables("loss")
        sd.setTrainingConfig(TrainingConfig(
            updater=Adam(0.05), dataSetFeatureMapping=["x"],
            dataSetLabelMapping=["y"]))
        storage = InMemoryStatsStorage()
        lst = StatsListener(storage, frequency=1,
                            config=StatsUpdateConfiguration(
                                collectGradientStats=False))
        sd.listeners.append(lst)
        X = rng.rand(32, 4).astype(np.float32)
        Y = (X @ np.ones((4, 1))).astype(np.float32)
        sd.fit(DataSet(X, Y), epochs=4)
        reports = storage.getUpdates(lst.sessionId, "StatsListener", "worker_0")
        assert len(reports) == 4
        assert "w" in reports[-1]["parameterStats"]
        assert reports[-1]["parameterStats"]["w"]["meanMagnitude"] > 0
        # update stats via consecutive-param deltas (no _last_updates on sd)
        assert "w" in reports[-1]["updateStats"]


class TestUIServer:
    """Live dashboard server (ref: VertxUIServer attach/poll lifecycle) +
    remote stats routing (ref: RemoteUIStatsStorageRouter)."""

    def _fetch(self, url):
        import urllib.request
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.read().decode()

    def test_overview_and_api(self):
        from deeplearning4j_tpu.ui import UIServer
        server = UIServer(port=0)  # ephemeral port; not the singleton
        try:
            storage = InMemoryStatsStorage()
            server.attach(storage)
            net = tiny_net()
            lst = StatsListener(storage, frequency=1)
            net.setListeners(lst)
            net.fit(tiny_data(), epochs=3)

            page = self._fetch(server.url)
            assert "Training overview" in page and "api/sessions" in page

            sessions = json.loads(self._fetch(server.url + "api/sessions"))
            assert [s["sessionId"] for s in sessions] == [lst.sessionId]
            assert sessions[0]["info"]["modelClass"] == "MultiLayerNetwork"

            ups = json.loads(self._fetch(
                f"{server.url}api/updates/{lst.sessionId}/worker_0?from=0"))
            assert len(ups) == 3 and ups[-1]["score"] > 0
            # incremental poll: nothing new past the end
            tail = json.loads(self._fetch(
                f"{server.url}api/updates/{lst.sessionId}/worker_0?from=3"))
            assert tail == []
        finally:
            server.stop()

    def test_model_and_system_tabs(self):
        """Round-4: the model-graph and system pages (SURVEY §5.5's train UI
        tabs) — pages served, topology in static info, device/host memory in
        reports, live system endpoint."""
        from deeplearning4j_tpu.ui import UIServer
        server = UIServer(port=0)
        try:
            storage = InMemoryStatsStorage()
            server.attach(storage)
            net = tiny_net()
            lst = StatsListener(storage, frequency=1)
            net.setListeners(lst)
            net.fit(tiny_data(), epochs=2)

            model_page = self._fetch(server.url + "model")
            assert "Model graph" in model_page and "parameterStats" in model_page
            system_page = self._fetch(server.url + "system")
            assert "System" in system_page and "deviceMemMb" in system_page
            # nav cross-links on every page
            for path in ("", "model", "system"):
                page = self._fetch(server.url + path)
                assert '/model"' in page and '/system"' in page

            # topology rides in static info; node ids join onto stats keys
            sessions = json.loads(self._fetch(server.url + "api/sessions"))
            topo = sessions[0]["info"]["topology"]
            assert [n["label"] for n in topo["nodes"]] == [
                "DenseLayer", "OutputLayer"]
            assert topo["edges"] == [["0", "1"]]
            ups = json.loads(self._fetch(
                f"{server.url}api/updates/{lst.sessionId}/worker_0?from=0"))
            stat_prefixes = {k.split("/")[0]
                             for k in ups[-1]["parameterStats"]}
            assert {n["id"] for n in topo["nodes"]} == stat_prefixes
            # system series present in reports
            assert ups[-1]["memoryRssMb"] > 0

            live = json.loads(self._fetch(server.url + "api/system-now"))
            assert live["hostRssMb"] > 0
            assert isinstance(live["devices"], list) and live["devices"]
            assert "kind" in live["devices"][0]
        finally:
            server.stop()

    def test_topology_for_computation_graph(self):
        from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
        from deeplearning4j_tpu.ui.stats import _topology
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2))
                .graphBuilder()
                .addInputs("in")
                .addLayer("h", DenseLayer(nOut=8, activation="TANH"), "in")
                .addLayer("out", OutputLayer(nOut=3, lossFunction="MCXENT"), "h")
                .setOutputs("out")
                .setInputTypes(InputType.feedForward(5)).build())
        net = ComputationGraph(conf).init()
        topo = _topology(net)
        ids = [n["id"] for n in topo["nodes"]]
        assert ids == ["in", "h", "out"]
        assert ["in", "h"] in topo["edges"] and ["h", "out"] in topo["edges"]
        assert topo["nodes"][0]["kind"] == "input"

    def test_remote_router_roundtrip(self):
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter, UIServer
        server = UIServer(port=0)
        try:
            router = RemoteStatsStorageRouter(server.url)
            net = tiny_net()
            # the listener writes through the HTTP router, as a remote
            # worker process would
            lst = StatsListener(router, frequency=1,
                                config=StatsUpdateConfiguration(
                                    collectHistograms=False))
            net.setListeners(lst)
            net.fit(tiny_data(), epochs=2)

            sessions = json.loads(self._fetch(server.url + "api/sessions"))
            assert [s["sessionId"] for s in sessions] == [lst.sessionId]
            ups = json.loads(self._fetch(
                f"{server.url}api/updates/{lst.sessionId}/worker_0?from=0"))
            assert len(ups) == 2
            assert "0/W" in ups[-1]["parameterStats"]
        finally:
            server.stop()

    def test_singleton_lifecycle(self):
        from deeplearning4j_tpu.ui import UIServer
        a = UIServer.getInstance(port=0)
        try:
            assert UIServer.getInstance() is a
        finally:
            a.stop()
        b = UIServer.getInstance(port=0)
        try:
            assert b is not a
        finally:
            b.stop()

    def test_remote_router_survives_server_outage(self):
        """Telemetry must not kill training: router drops reports (with a
        warning) when the UI server is unreachable."""
        import warnings as _w
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter
        router = RemoteStatsStorageRouter("http://127.0.0.1:1",  # nothing listens
                                          timeout=0.2, retries=1, retry_delay=0.01)
        net = tiny_net()
        net.setListeners(StatsListener(router, frequency=1,
                                       config=StatsUpdateConfiguration(
                                           collectHistograms=False)))
        with _w.catch_warnings(record=True) as caught:
            _w.simplefilter("always")
            net.fit(tiny_data(), epochs=2)  # must not raise
        assert router.dropped >= 2
        assert any("unreachable" in str(c.message) for c in caught)


class TestRemoteRouterDelivery:
    """Regression coverage for the RemoteStatsStorageRouter delivery
    contract (ISSUE 10 satellite): the drop-after-retry path, the
    bounded-queue (async) overflow-drop accounting, and
    retry-then-deliver — the cluster heartbeat/trace-aggregation path
    (serving/cluster.py HttpTransport) rides exactly this router."""

    def test_bounded_queue_overflow_drops_and_counts(self):
        """Async mode against an unreachable server: the bounded queue
        fills (the sender is stuck retrying), overflow drops are counted
        separately from network drops, memory stays bounded, and the
        posting thread never blocks or raises."""
        import warnings as _w
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter
        router = RemoteStatsStorageRouter(
            "http://127.0.0.1:1",            # nothing listens
            timeout=0.2, retries=0, retry_delay=0.01, queue_capacity=2)
        try:
            with _w.catch_warnings(record=True) as caught:
                _w.simplefilter("always")
                for i in range(20):
                    router.putUpdate("s", "T", "w", {"i": i})
            assert router.dropped_overflow >= 1
            assert router.dropped >= router.dropped_overflow
            assert len(router._q) <= router.queue_capacity
            assert any("overflow" in str(c.message) or
                       "unreachable" in str(c.message) for c in caught)
            assert router.flush(timeout=10)   # drains (into drops)
            # every report was either delivered (none) or dropped
            assert router.delivered == 0
            assert router.dropped == 20
        finally:
            router.close()

    def test_retry_then_deliver(self, monkeypatch):
        """The first POST attempt fails transiently; the retry delivers
        — the report lands in the server's storage and nothing is
        dropped."""
        import urllib.request as _ur
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter, UIServer
        server = UIServer(port=0)
        try:
            real = _ur.urlopen
            fails = {"n": 1}

            def flaky(req, timeout=None):
                if fails["n"] > 0:
                    fails["n"] -= 1
                    raise OSError("injected transient network failure")
                return real(req, timeout=timeout)

            monkeypatch.setattr(_ur, "urlopen", flaky)
            router = RemoteStatsStorageRouter(server.url, timeout=5,
                                              retries=2, retry_delay=0.01)
            router.putUpdate("sess", "ServingMetrics", "w0", {"ok": 1})
            assert router.dropped == 0
            assert fails["n"] == 0               # the failure was consumed
            store = server._remote_target()
            ups = store.getUpdates("sess", "ServingMetrics", "w0")
            assert ups and ups[-1] == {"ok": 1}
        finally:
            server.stop()

    def test_async_queue_delivers_in_order(self):
        """Async mode against a LIVE server: queued reports deliver in
        submission order; flush() waits for the drain."""
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter, UIServer
        server = UIServer(port=0)
        try:
            router = RemoteStatsStorageRouter(server.url,
                                              queue_capacity=32)
            for i in range(5):
                router.putUpdate("sess", "T", "w0", {"i": i})
            assert router.flush(timeout=10)
            assert router.delivered == 5 and router.dropped == 0
            store = server._remote_target()
            ups = store.getUpdates("sess", "T", "w0")
            assert [u["i"] for u in ups] == list(range(5))
            router.close()
        finally:
            server.stop()

    def test_post_close_submissions_counted_as_dropped(self):
        """Review regression: a report posted after close() is dropped
        but COUNTED — every report is delivered or accounted for."""
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter
        router = RemoteStatsStorageRouter("http://127.0.0.1:1",
                                          timeout=0.2, retries=0,
                                          queue_capacity=4)
        router.close(timeout=1.0)
        router.putUpdate("s", "T", "w", {"late": True})
        assert router.dropped == 1

    def test_sync_mode_unchanged_default(self):
        from deeplearning4j_tpu.ui import RemoteStatsStorageRouter
        router = RemoteStatsStorageRouter("http://127.0.0.1:1")
        assert router.queue_capacity == 0 and router._q is None
        assert router.flush() is True            # no-op synchronously
        router.close()                           # no-op synchronously
        with pytest.raises(ValueError):
            RemoteStatsStorageRouter("http://127.0.0.1:1",
                                     queue_capacity=-1)


class TestTsne:
    def test_render_clusters(self, tmp_path):
        """Two well-separated gaussian clusters must stay separated in the
        projection (ref: TSNEStandardExample's sanity criterion)."""
        from deeplearning4j_tpu.ui import render_tsne, tsne_coords
        rng = np.random.RandomState(0)
        a = rng.normal(0, 0.3, (20, 16))
        b = rng.normal(5, 0.3, (20, 16))
        vecs = np.vstack([a, b])
        labels = [f"a{i}" for i in range(20)] + [f"b{i}" for i in range(20)]
        xy = tsne_coords(vecs, perplexity=8, seed=0)
        da = xy[:20].mean(0)
        db = xy[20:]. mean(0)
        within = max(np.linalg.norm(xy[:20] - da, axis=1).mean(),
                     np.linalg.norm(xy[20:] - db, axis=1).mean())
        between = np.linalg.norm(da - db)
        assert between > 2 * within
        path = render_tsne(labels, vecs, str(tmp_path / "tsne.html"),
                           classes=[0] * 20 + [1] * 20)
        page = open(path).read()
        assert page.count("<circle") == 40 and "a0" in page and "b19" in page

    def test_word_vectors_page(self, tmp_path):
        from deeplearning4j_tpu.text import (
            CollectionSentenceIterator, DefaultTokenizerFactory, Word2Vec)
        from deeplearning4j_tpu.ui import render_word_vectors
        sents = [f"alpha beta gamma delta word{i % 5}" for i in range(60)]
        vec = Word2Vec(minWordFrequency=1, layerSize=16, epochs=1,
                       iterate=CollectionSentenceIterator(sents),
                       tokenizerFactory=DefaultTokenizerFactory())
        vec.fit()
        path = render_word_vectors(vec, str(tmp_path / "words.html"),
                                   perplexity=5)
        page = open(path).read()
        assert "alpha" in page and "<svg" in page

    def test_label_vector_mismatch_raises(self):
        from deeplearning4j_tpu.ui import render_tsne
        with pytest.raises(ValueError, match="labels vs"):
            render_tsne(["a"], np.zeros((2, 4)), "/tmp/x.html")
