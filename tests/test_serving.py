"""Serving runtime tests on the virtual 8-device CPU mesh: dynamic
micro-batch coalescing, bucket-bounded compiled signatures, admission
control / deadline shedding, registry lifecycle, metric monotonicity, and
the N-concurrent-clients bitwise-parity stress test from the subsystem's
acceptance criteria."""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import ParallelInference, make_mesh
from deeplearning4j_tpu.serving import (
    DeadlineExceededError, InferenceEngine, ModelAdapter, ModelRegistry,
    QueueFullError, RejectedError, ServingMetrics, bucket_ladder,
)
from deeplearning4j_tpu.train import Sgd


def mlp_conf(seed=7, n_in=6, n_out=3):
    return (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(nIn=n_in, nOut=16, activation="TANH"))
            .layer(OutputLayer(nIn=16, nOut=n_out, lossFunction="MCXENT"))
            .build())


def fresh_model(seed=7):
    return MultiLayerNetwork(mlp_conf(seed)).init()


def assert_equal_across_batch_shapes(got, want):
    """Equal within 4 float32 ulp of the outputs' scale. The engine pads a
    request to its bucket and the direct call does not: XLA may tile and
    vectorise two batch shapes differently, so bit equality across shapes is
    not a property it gives (the CPU backend here differs by 1 ulp)."""
    atol = 4 * float(np.spacing(np.float32(np.abs(want).max())))
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


class TestBucketLadder:
    def test_geometric_cover(self):
        assert bucket_ladder(32) == (1, 2, 4, 8, 16, 32)
        assert bucket_ladder(33) == (1, 2, 4, 8, 16, 32, 64)
        assert bucket_ladder(1) == (1,)

    def test_mesh_multiple(self):
        assert bucket_ladder(32, multiple_of=8) == (8, 16, 32)
        assert bucket_ladder(20, multiple_of=8) == (8, 16, 32)

    def test_invalid(self):
        with pytest.raises(ValueError):
            bucket_ladder(0)

    def test_min_bucket_exceeds_max_batch_size(self):
        """A floor above the batch cap still yields a valid single-rung
        ladder (the rung covers max_batch_size by construction)."""
        assert bucket_ladder(4, min_bucket=16) == (16,)
        assert bucket_ladder(4, min_bucket=9) == (9,)
        ladder = bucket_ladder(4, min_bucket=16, multiple_of=8)
        assert ladder == (16,) and ladder[-1] >= 4

    def test_non_power_of_two_multiple_of(self):
        """Every rung is a multiple_of-multiple even when multiple_of is
        not a power of two (a 3- or 6-way mesh data axis)."""
        for mult in (3, 6, 12):
            ladder = bucket_ladder(32, multiple_of=mult)
            assert all(b % mult == 0 for b in ladder), (mult, ladder)
            assert ladder[-1] >= 32
            assert all(b2 == 2 * b1 for b1, b2 in zip(ladder, ladder[1:]))
        assert bucket_ladder(32, multiple_of=3) == (3, 6, 12, 24, 48)
        # min_bucket rounds UP to the next multiple, never down
        assert bucket_ladder(32, multiple_of=6, min_bucket=8)[0] == 12

    def test_single_bucket_ladders(self):
        assert bucket_ladder(1) == (1,)
        assert bucket_ladder(8, min_bucket=8) == (8,)
        assert bucket_ladder(7, multiple_of=7) == (7,)
        assert bucket_ladder(64, min_bucket=64, multiple_of=64) == (64,)


class TestEngineCoalescing:
    def test_concurrent_submitters_coalesce_into_one_batch(self):
        """8 submits filling max_batch_size exactly => the dispatcher seals
        ONE batch; every future resolves bitwise-equal to the direct call."""
        model = fresh_model()
        rng = np.random.default_rng(0)
        xs = [rng.normal(size=(4, 6)).astype(np.float32) for _ in range(8)]
        with InferenceEngine(model, max_batch_size=32, max_wait_ms=500) as eng:
            futs = [eng.submit(x) for x in xs]
            outs = [f.result(timeout=60) for f in futs]
        assert eng.metrics.batches_total.value == 1
        assert eng.metrics.requests_per_batch.count == 1
        assert eng.metrics.mean_requests_per_batch() == 8.0
        assert eng.metrics.rows_total.value == 32
        assert eng.metrics.padded_rows_total.value == 0
        for x, o in zip(xs, outs):
            assert np.array_equal(o.toNumpy(), model.output(x).toNumpy())

    def test_single_request_pads_to_bucket(self):
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=16, max_wait_ms=0) as eng:
            out = eng.output(np.zeros((3, 6), np.float32))
        assert out.shape == (3, 3)
        assert eng.metrics.padded_rows_total.value == 1  # 3 -> bucket 4
        assert eng.metrics.fill_ratio.count == 1

    def test_oversize_and_empty_submit_rejected_client_side(self):
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=4, max_wait_ms=0) as eng:
            with pytest.raises(ValueError):
                eng.submit(np.zeros((5, 6), np.float32))
            with pytest.raises(ValueError):
                eng.submit(np.zeros((0, 6), np.float32))


class TestBoundedCompilation:
    def test_50_distinct_batch_sizes_bounded_by_ladder(self):
        """50 novel request sizes may compile at most len(buckets) inference
        signatures — asserted via the engine's cache-hit metrics AND the
        model's live jit cache."""
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=64, max_wait_ms=0) as eng:
            ladder = eng.buckets
            for b in range(1, 51):
                out = eng.output(np.ones((b, 6), np.float32))
                assert out.shape == (b, 3)
            m = eng.metrics
            assert m.bucket_compiles.value <= len(ladder)
            assert m.bucket_hits.value == 50 - m.bucket_compiles.value
            assert m.bucket_cache_hit_rate() > 0.8
            # the model's actual compiled-signature count obeys the bound too
            assert eng.compiled_signatures() <= len(ladder)

    def test_parallel_inference_bucket_padding_bounds_signatures(self):
        """The non-engine ParallelInference path now pads to the n*2^k
        ladder: many odd batch sizes, few compiled shapes."""
        model = fresh_model()
        pi = ParallelInference(model, mesh=make_mesh({"data": 8}))
        assert pi._bucket(13) == 16 and pi._bucket(8) == 8 and pi._bucket(17) == 32
        for b in range(9, 33):
            out = pi.output(np.ones((b, 6), np.float32))
            assert out.shape == (b, 3)
        infer = model._jit_cache.get("infer")
        assert infer is not None and infer._cache_size() <= 2  # 16 and 32


class _SlowAdapter(ModelAdapter):
    """Deterministic stand-in whose dispatch blocks long enough to build a
    backlog (drives the queue-full and shedding paths)."""

    kind = "slow"

    def __init__(self, delay_s=0.25):
        super().__init__(model=None)
        self.delay_s = delay_s

    def infer(self, x):
        time.sleep(self.delay_s)
        return np.asarray(x) * 2.0


class TestAdmissionControl:
    def test_deadline_shedding_returns_rejected_error(self):
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=8, max_wait_ms=0) as eng:
            fut = eng.submit(np.zeros((2, 6), np.float32), timeout_ms=1e-4)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(timeout=30)
            assert isinstance(ei.value, RejectedError)
            assert ei.value.reason == "deadline"
            assert eng.metrics.rejected_deadline.value >= 1
            # engine still serves fresh traffic afterwards
            out = eng.output(np.zeros((2, 6), np.float32))
            assert out.shape == (2, 3)

    def test_queue_full_backpressure(self):
        with InferenceEngine(_SlowAdapter(), max_batch_size=2, max_wait_ms=0,
                             queue_capacity_rows=4) as eng:
            first = eng.submit(np.ones((2, 4)))  # occupies the dispatcher
            time.sleep(0.05)
            held = [eng.submit(np.ones((2, 4)) * i) for i in (2, 3)]  # fills queue
            with pytest.raises(QueueFullError) as ei:
                eng.submit(np.ones((2, 4)) * 9)
            assert ei.value.reason == "queue_full"
            assert eng.metrics.rejected_queue_full.value == 1
            assert np.array_equal(first.result(timeout=30).toNumpy(),
                                  np.ones((2, 4)) * 2.0)
            for f in held:  # backlog drains in FIFO order once unblocked
                f.result(timeout=30)

    def test_shutdown_rejects_queued_and_new(self):
        eng = InferenceEngine(_SlowAdapter(delay_s=0.5), max_batch_size=2,
                              max_wait_ms=0, queue_capacity_rows=64)
        running = eng.submit(np.ones((2, 4)))
        time.sleep(0.05)
        queued = eng.submit(np.ones((2, 4)))
        eng.shutdown(wait=False)
        with pytest.raises(RejectedError) as ei:
            queued.result(timeout=30)
        assert ei.value.reason == "shutdown"
        with pytest.raises(RejectedError):
            eng.submit(np.ones((2, 4)))
        running.result(timeout=30)  # in-flight batch still completes
        eng.shutdown()

    def test_cancelled_future_does_not_kill_dispatcher(self):
        """A client cancelling its queued future must not crash the
        dispatcher thread (set_exception/set_result on a cancelled future
        raises InvalidStateError): later traffic still serves."""
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=8, max_wait_ms=0) as eng:
            # cancel one with a deadline (shed path) and one without (dispatch
            # path); either used to raise out of the dispatcher loop
            f1 = eng.submit(np.zeros((2, 6), np.float32), timeout_ms=1e-4)
            f1.cancel()
            f2 = eng.submit(np.zeros((2, 6), np.float32))
            f2.cancel()
            time.sleep(0.2)
            out = eng.output(np.zeros((2, 6), np.float32))
            assert out.shape == (2, 3)
            assert eng._thread.is_alive()

    def test_retry_on_shed_done_callback_does_not_deadlock(self):
        """A done-callback that re-enters the engine (retry-on-shed) runs in
        the dispatcher thread; shedding must fail futures OUTSIDE the
        admission lock or the resubmit deadlocks the whole engine."""
        model = fresh_model()
        retried = []
        with InferenceEngine(model, max_batch_size=8, max_wait_ms=0) as eng:
            fut = eng.submit(np.zeros((2, 6), np.float32), timeout_ms=1e-4)

            def retry(f):
                if f.exception() is not None:
                    retried.append(eng.submit(np.zeros((2, 6), np.float32)))

            fut.add_done_callback(retry)
            deadline = time.time() + 10
            while not retried and time.time() < deadline:
                time.sleep(0.01)
            assert retried, "shed callback never ran (dispatcher deadlocked?)"
            out = retried[0].result(timeout=30)
            assert out.shape == (2, 3)

    def test_mismatched_row_signature_rejected_at_submit(self):
        """One engine serves ONE input surface: a dtype or feature-shape
        mismatch raises client-side instead of poisoning a co-batch."""
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=8, max_wait_ms=0) as eng:
            eng.output(np.zeros((2, 6), np.float32))
            with pytest.raises(ValueError, match="row signature"):
                eng.submit(np.zeros((2, 6), np.float64))
            with pytest.raises(ValueError, match="row signature"):
                eng.submit(np.zeros((2, 7), np.float32))
            assert eng.output(np.zeros((1, 6), np.float32)).shape == (1, 3)

    def test_expire_queued_sheds_proactively(self):
        """Slot-bound schedulers (continuous-batching decode) never call
        take() while full — expire_queued must shed expired entries in
        place, anywhere in the queue, and release their rows budget."""
        from deeplearning4j_tpu.serving import AdmissionController
        from deeplearning4j_tpu.serving.admission import Request

        ac = AdmissionController(capacity_rows=4)
        keep1 = ac.admit(Request(x="a", rows=1))
        doomed = ac.admit(Request(x="b", rows=2), timeout_ms=1e-4)
        keep2 = ac.admit(Request(x="c", rows=1))
        time.sleep(0.01)
        assert ac.expire_queued() == 1
        assert ac.expire_queued() == 0       # idempotent once drained
        assert ac.depth_requests == 2 and ac.depth_rows == 2
        with pytest.raises(DeadlineExceededError):
            doomed.future.result(timeout=1)
        # FIFO order of survivors intact; budget freed for new admissions
        assert ac.take(4, timeout=0.0) is keep1
        ac.admit(Request(x="d", rows=3))
        assert ac.take(4, timeout=0.0) is keep2

    def test_model_error_propagates_to_futures(self, tmp_path):
        import os

        from deeplearning4j_tpu.util import crash_reporting

        class _Boom(ModelAdapter):
            def infer(self, x):
                raise RuntimeError("kernel exploded")

        crash_reporting.crashDumpOutputDirectory(str(tmp_path))
        try:
            with InferenceEngine(_Boom(model=None), max_batch_size=4,
                                 max_wait_ms=0) as eng:
                fut = eng.submit(np.ones((1, 4)))
                with pytest.raises(RuntimeError, match="kernel exploded"):
                    fut.result(timeout=30)
                assert eng.metrics.failed_total.value == 1
            # serving crashes get the training path's forensics (PR 3):
            # the first unexpected dispatch failure wrote a crash dump
            dumps = [f for f in os.listdir(tmp_path)
                     if f.startswith("dl4jtpu-crash")]
            assert len(dumps) == 1
        finally:
            crash_reporting.crashDumpOutputDirectory(None)


class TestModelRegistry:
    def test_deploy_versions_alias_undeploy(self):
        reg = ModelRegistry(default_buckets=(1, 2, 4))
        m1, m2 = fresh_model(1), fresh_model(2)
        d1 = reg.deploy("mlp", m1)
        d2 = reg.deploy("mlp", m2)
        assert (d1.version, d2.version) == (1, 2)
        assert reg.versions("mlp") == [1, 2]
        assert reg.get("mlp").version == 2           # bare name -> latest
        assert reg.get("mlp:1").adapter.model is m1  # pinned
        reg.alias("prod", "mlp:1")
        assert reg.get("prod").version == 1
        assert reg.undeploy("mlp", 1) == 1
        with pytest.raises(KeyError):
            reg.get("prod")                          # alias died with target
        assert reg.undeploy("mlp") == 1
        with pytest.raises(KeyError):
            reg.get("mlp")

    def test_warmup_compiles_every_bucket_on_deploy(self):
        reg = ModelRegistry(default_buckets=(1, 2, 4, 8))
        model = fresh_model()
        dep = reg.deploy("mlp", model, warmup_example=np.zeros(6, np.float32))
        assert dep.warmup_ms is not None and dep.warmup_ms > 0
        infer = model._jit_cache.get("infer")
        assert infer is not None and infer._cache_size() == 4
        # post-warmup engine traffic is all cache hits
        with reg.engine("mlp", max_wait_ms=0) as eng:
            for b in (1, 3, 7):
                eng.output(np.zeros((b, 6), np.float32))
            assert eng.compiled_signatures() == 4

    def test_registry_serves_computation_graph_and_samediff(self):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        from deeplearning4j_tpu.nn import ComputationGraph

        g_conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.5))
                  .graphBuilder()
                  .addInputs("in")
                  .addLayer("h", DenseLayer(nIn=4, nOut=8, activation="TANH"), "in")
                  .addLayer("out", OutputLayer(nIn=8, nOut=2, activation="SOFTMAX",
                                               lossFunction="MCXENT"), "h")
                  .setOutputs("out")
                  .build())
        cg = ComputationGraph(g_conf).init()

        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        w = sd.var("w", np.full((4, 2), 0.5, np.float32))
        sd.math.tanh(x.mmul(w)).rename("y")

        reg = ModelRegistry(default_buckets=(1, 2, 4))
        reg.deploy("cg", cg)
        reg.deploy("sd", sd, output_name="y")
        xv = np.random.default_rng(3).normal(size=(3, 4)).astype(np.float32)
        with reg.engine("cg", max_wait_ms=0) as ecg:
            assert_equal_across_batch_shapes(ecg.output(xv).toNumpy(),
                                             cg.outputSingle(xv).toNumpy())
        with reg.engine("sd", max_wait_ms=0) as esd:
            assert_equal_across_batch_shapes(
                esd.output(xv).toNumpy(),
                sd.output({"x": xv}, "y")["y"].toNumpy())

    def test_default_buckets_realign_to_mesh(self):
        """registry.engine(mesh=...) with the (1,2,4,...) default ladder must
        not trip the engine's mesh-multiple validation — it re-ladders."""
        reg = ModelRegistry()  # defaults (1, 2, 4, 8, 16, 32)
        model = fresh_model()
        reg.deploy("m", model)
        with reg.engine("m", mesh=make_mesh({"data": 8}),
                        max_wait_ms=0) as eng:
            assert all(b % 8 == 0 for b in eng.buckets)
            assert eng.buckets[-1] >= 32
            out = eng.output(np.ones((3, 6), np.float32))
            assert out.shape == (3, 3)

    def test_concurrent_deploys_get_distinct_versions(self):
        """Version assignment is reserved under the registry lock: parallel
        deploys of one name may not clobber each other's slot."""
        reg = ModelRegistry(default_buckets=(1, 2))
        models = [fresh_model(s) for s in range(6)]
        deps = [None] * 6
        barrier = threading.Barrier(6)

        def worker(i):
            barrier.wait(timeout=30)
            deps[i] = reg.deploy("m", models[i],
                                 warmup_example=np.zeros(6, np.float32))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert sorted(d.version for d in deps) == [1, 2, 3, 4, 5, 6]
        assert reg.versions("m") == [1, 2, 3, 4, 5, 6]
        # every deployed model is reachable at its pinned ref
        for d in deps:
            assert reg.get(f"m:{d.version}").adapter is d.adapter

    def test_bad_refs_and_duplicate_versions(self):
        reg = ModelRegistry()
        with pytest.raises(ValueError):
            reg.deploy("a:b", fresh_model())
        reg.deploy("m", fresh_model(), version=3)
        with pytest.raises(ValueError):
            reg.deploy("m", fresh_model(), version=3)
        with pytest.raises(KeyError):
            reg.alias("x", "nope")
        with pytest.raises(TypeError):
            reg.deploy("bad", object())


class TestMetrics:
    def test_counters_monotone_under_traffic(self):
        model = fresh_model()
        snaps = []
        with InferenceEngine(model, max_batch_size=8, max_wait_ms=0) as eng:
            for round_ in range(3):
                for b in (1, 3, 5):
                    eng.output(np.ones((b, 6), np.float32))
                try:
                    eng.submit(np.ones((2, 6), np.float32),
                               timeout_ms=1e-4).result(timeout=30)
                except RejectedError:
                    pass
                snaps.append(eng.metrics.counters())
        for before, after in zip(snaps, snaps[1:]):
            for k, v in before.items():
                assert after[k] >= v, f"counter {k} decreased"
        assert snaps[-1]["requests_total"] == 12
        assert snaps[-1]["rejected_deadline"] >= 1

    def test_histogram_and_snapshot_shape(self):
        m = ServingMetrics()
        for v in (0.3, 2.0, 40.0, 3000.0):
            m.latency_ms.observe(v)
        assert m.latency_ms.count == 4
        assert m.latency_ms.quantile(0.5) <= m.latency_ms.quantile(1.0)
        snap = m.snapshot()
        assert {"requests_total", "bucket_cache_hit_rate", "latency_ms",
                "per_bucket", "qps"} <= set(snap)

    def test_publish_rides_stats_storage_spi(self):
        import json

        from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

        model = fresh_model()
        storage = InMemoryStatsStorage()
        with InferenceEngine(model, max_batch_size=4, max_wait_ms=0) as eng:
            eng.output(np.ones((2, 6), np.float32))
            eng.metrics.publish(storage)
        ups = storage.getUpdates("serving", "ServingMetrics", "engine_0")
        assert len(ups) == 1
        assert ups[0]["batches_total"] == 1
        json.dumps(ups[0])  # JSON-safe all the way down

    def test_dispatch_spans_reach_profiler(self):
        from deeplearning4j_tpu.profiler import OpProfiler, ProfilerConfig

        prof = OpProfiler(ProfilerConfig())
        model = fresh_model()
        with InferenceEngine(model, max_batch_size=4, max_wait_ms=0,
                             profiler=prof) as eng:
            eng.output(np.ones((2, 6), np.float32))
        names = [s.name for s in prof.spans]
        assert "serving.dispatch" in names


class TestServingStress:
    def test_concurrent_clients_bitwise_parity_on_cpu_mesh(self):
        """Acceptance stress test: 8 client threads against one engine on
        the 8-device CPU mesh; every output equal to a direct model.output()
        call (which runs another batch shape), measured fill ratio > 1
        request/batch, and compiled signatures bounded by the bucket
        ladder."""
        model = fresh_model()
        mesh = make_mesh({"data": 8})
        n_clients, rounds = 8, 3
        rng = np.random.default_rng(42)
        data = [[rng.normal(size=(1 + (t + r) % 4, 6)).astype(np.float32)
                 for r in range(rounds)] for t in range(n_clients)]
        results = [[None] * rounds for _ in range(n_clients)]
        errors = []
        barrier = threading.Barrier(n_clients)

        with InferenceEngine(model, mesh=mesh, max_batch_size=32,
                             max_wait_ms=25, queue_capacity_rows=256) as eng:
            ladder = eng.buckets

            def client(t):
                try:
                    barrier.wait(timeout=30)
                    for r in range(rounds):
                        results[t][r] = eng.output(data[t][r]).toNumpy()
                except Exception as e:  # pragma: no cover - surfaced below
                    errors.append((t, e))

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(n_clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
            assert not errors, f"client errors: {errors}"

            m = eng.metrics
            assert m.requests_total.value == n_clients * rounds
            assert m.rejected_total.value == 0
            # dynamic batching actually batched: > 1 request per dispatch
            assert m.mean_requests_per_batch() > 1.0
            # compiled-signature bound, via the cache-hit metrics
            assert m.bucket_compiles.value <= len(ladder)
            assert m.bucket_hits.value == \
                m.batches_total.value - m.bucket_compiles.value
            assert eng.compiled_signatures() <= len(ladder)

        # parity vs direct single-caller calls (checked after the
        # engine drained so direct calls don't race the mesh context)
        for t in range(n_clients):
            for r in range(rounds):
                expect = model.output(data[t][r]).toNumpy()
                assert_equal_across_batch_shapes(results[t][r], expect)
