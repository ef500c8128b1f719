"""The open loop times every request from when it was due: a server that
stalls shows up in the latency of the requests that came after."""
import threading
import time
import types
from concurrent.futures import Future

from benchmarks.lib.runners import serve_open
from benchmarks.readers import request_percentile


class _Gauge:
    value = 0.0


class StallingEngine:
    """Serves one request at a time, 1 ms each, and stalls once."""

    name = "fake"
    slots = 1

    def __init__(self, stall_after: int, stall_s: float):
        self.metrics = types.SimpleNamespace(
            kv_blocks_in_use=_Gauge(), kv_blocks_total=_Gauge(),
            queue_depth=_Gauge(), slot_occupancy=_Gauge())
        self._queue, self._cv = [], threading.Condition()
        self._stall_after, self._stall_s, self._served = stall_after, stall_s, 0
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def compiled_signatures(self):
        return 1

    def submit(self, prompt, *, max_new_tokens, on_token):
        fut = Future()
        handle = types.SimpleNamespace(future=fut, finish_reason=None)
        with self._cv:
            self._queue.append((max_new_tokens, on_token, handle))
            self._cv.notify()
        return handle

    def _loop(self):
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait(0.05)
                if self._stop:
                    return
                n, on_token, handle = self._queue.pop(0)
            if self._served == self._stall_after:
                time.sleep(self._stall_s)
            self._served += 1
            time.sleep(0.001)
            for tok in range(n):
                on_token(tok)
            handle.finish_reason = "max_tokens"
            handle.future.set_result(list(range(n)))

    def shutdown(self):
        self._stop = True
        self._thread.join(timeout=5.0)
        assert not self._thread.is_alive()


def _cell(seconds):
    mix = {"arrival": {"kind": "poisson", "rate_rps": 40.0},
           "prompt_len": {"dist": "fixed", "value": 4},
           "output_len": {"dist": "fixed", "value": 2},
           "lead_s": 0.2, "grace_s": 5.0}
    return types.SimpleNamespace(
        traffic=mix, seed=2**31 + 3, seconds=seconds, trace=False,
        sizes={"vocab_size": 50}, t_start=time.perf_counter(),
        compiles=types.SimpleNamespace(count=0))


def test_a_stall_shows_in_later_requests_latency():
    from deeplearning4j_tpu.profiler.profiler import OpProfiler
    from deeplearning4j_tpu.serving.tracing import Tracer

    engine = StallingEngine(stall_after=20, stall_s=0.5)
    try:
        obs, client, owed, _ = serve_open.measure(
            _cell(2.0), engine, OpProfiler(), Tracer())
    finally:
        engine.shutdown()
    obs.requests = owed
    assert len(owed) == 80 and all(r["tokens"] == [0, 1] for r in owed)
    ttft = sorted(1e3 * (r["token_t"][0] - r["due"]) for r in owed)
    # about 0.5 s x 40/s = 20 requests queued behind the stall: the tail
    # holds it although each was served in a millisecond once reached
    assert ttft[len(ttft) // 2] < 100.0
    assert request_percentile.read({"value": "ttft_ms", "q": 95}, obs) > 250.0
    # the generator itself kept its schedule through the stall
    assert request_percentile.read({"value": "late_ms", "q": 95}, obs) < 50.0
    # latency from the time of sending would have hidden nothing here
    # either, but only because sending is never blocked: due == sent
    assert all(r["sent"] >= r["due"] for r in owed)


def test_a_request_that_never_ends_counts_as_a_miss():
    from deeplearning4j_tpu.profiler.profiler import OpProfiler
    from deeplearning4j_tpu.serving.tracing import Tracer

    engine = StallingEngine(stall_after=5, stall_s=30.0)
    cell = _cell(0.5)
    cell.traffic["grace_s"] = 0.3
    try:
        obs, _client, owed, _ = serve_open.measure(
            cell, engine, OpProfiler(), Tracer())
    finally:
        engine._stop = True
    obs.requests = owed
    failed = [r for r in owed if r["tokens"] is None]
    assert failed and len(failed) < len(owed) + 1
    assert request_percentile.read({"value": "ttft_ms", "q": 100}, obs) \
        == obs.facts["miss_ms"]
