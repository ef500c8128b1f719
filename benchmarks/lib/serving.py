"""What the two serving runners share: the engine as ``chip_smoke.py`` starts
it (``ModelRegistry.deploy(CausalLMAdapter)`` -> ``generation_engine`` ->
``warmup``), the client-side record of each request, the window's spans,
events and gauges, and the check of emitted tokens against the plain
reference."""
from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

from benchmarks.lib import device, model, watch
from benchmarks.lib.observe import Observed

DEPLOYMENT = "bench-lm"
GAUGES = ("kv_blocks_in_use", "kv_blocks_total", "queue_depth",
          "slot_occupancy")


class Client:
    """The load generator's own record. ``send`` submits one request and
    notes, on the client's clock, when it was due, when it was sent, when
    each token arrived and when it ended. Token times are taken in the
    engine's ``on_token`` callback, which runs as the token is put on the
    stream; ends arrive on ``self.ended`` for a closed loop to act on."""

    def __init__(self, engine):
        self.engine = engine
        self.records = []
        self.ended: "queue.SimpleQueue" = queue.SimpleQueue()

    def send(self, request: dict, due: float) -> dict:
        rec = {"index": request["index"], "due": due, "sent": None,
               "token_t": [], "end": None, "error": None, "reason": None,
               "prompt": request["prompt"],
               "max_new_tokens": request["max_new_tokens"], "tokens": None}
        self.records.append(rec)
        times = rec["token_t"]
        rec["sent"] = time.perf_counter()
        try:
            handle = self.engine.submit(
                request["prompt"], max_new_tokens=request["max_new_tokens"],
                on_token=lambda _tok: times.append(time.perf_counter()))
        except Exception as e:   # refused at the door: a failed request
            rec["end"], rec["error"] = time.perf_counter(), type(e).__name__
            self.ended.put(rec)
            return rec

        def ended(fut, rec=rec, handle=handle):
            rec["end"] = time.perf_counter()
            exc = fut.exception() if not fut.cancelled() else None
            if fut.cancelled() or exc is not None:
                rec["error"] = type(exc).__name__ if exc else "cancelled"
            else:
                rec["tokens"] = fut.result()
                rec["reason"] = handle.finish_reason
            self.ended.put(rec)

        handle.future.add_done_callback(ended)
        return rec


def start_engine(cell):
    """Weights from the seed on the device, the registry, the engine with the
    configuration's settings and this traffic's prefill buckets, warmed up.
    Returns (registry, engine, params, profiler, tracer)."""
    from deeplearning4j_tpu.profiler.profiler import OpProfiler
    from deeplearning4j_tpu.serving import CausalLMAdapter, ModelRegistry
    from deeplearning4j_tpu.serving.tracing import Tracer

    cfg = model.transformer_config(cell.sizes)
    params = model.make_weights(cfg, cell.seed)
    settings = dict(cell.config["deployment"]["engine"])
    settings.update(cell.traffic.get("engine", {}))
    if cell.tiny:
        settings.update(cell.config["tiny_engine"])
        settings.update(cell.traffic.get("tiny_engine", {}))
    profiler = OpProfiler()
    # per-request events cost the scheduler a little for every token, so
    # the request tracer is on only in the traced run
    tracer = Tracer(sample_rate=1.0, capacity=1 << 16) if cell.trace else None
    registry = ModelRegistry()
    registry.deploy(DEPLOYMENT, CausalLMAdapter(params, cfg))
    engine = registry.generation_engine(
        DEPLOYMENT, eos_id=None, profiler=profiler, tracer=tracer, **settings)
    engine.warmup()
    return registry, engine, params, profiler, tracer


class GaugeSampler(threading.Thread):
    """Reads the engine's gauges once a second of the window."""

    def __init__(self, engine, obs: Observed, until: float):
        super().__init__(name="bench-gauges", daemon=True)
        self.engine, self.obs, self.until = engine, obs, until
        self.stop = threading.Event()

    def run(self):
        m = self.engine.metrics
        while not self.stop.is_set() and time.perf_counter() < self.until:
            self.obs.gauges.append(
                {g: float(getattr(m, g).value) for g in GAUGES})
            self.stop.wait(1.0)


_GAP_LABEL = {"serving.decode_step": "in_decode_step",
              "serving.prefill": "in_prefill"}


def clock_base(profiler) -> float:
    """The ``perf_counter`` reading the profiler's span offsets count from,
    found with one span of our own."""
    before = time.perf_counter()
    with profiler.span("bench.clock"):
        pass
    mine = [s for s in profiler.spans if s.name == "bench.clock"][-1]
    return before - mine.start_us / 1e6


def spans_between(profiler, base: float, t0: float, t1: float):
    """The program's spans that lie inside ``[t0, t1]``, on the host clock."""
    out = []
    for s in profiler.spans:
        start = base + s.start_us / 1e6
        end = start + s.dur_us / 1e6
        if start >= t0 and end <= t1:
            out.append({"name": s.name, "start": start, "end": end,
                        "args": s.args or {}})
    return out


def collect(obs: Observed, profiler, base: float, tracer, engine):
    """The program's spans and request events that fall inside the window."""
    t0, t1 = obs.window
    obs.spans = spans_between(profiler, base, t0, t1)
    for tr in (tracer.traces(engine.name) if tracer is not None else ()):
        for name, t, attrs in tr.events:
            if t0 <= t <= t1:
                obs.events.append({"name": name, "t": t,
                                   "attrs": attrs or {}})


def traced_tail(cell, obs: Observed, profiler, base: float, keep_alive):
    """With ``--trace 1``: trace a few seconds more of the same load after
    the window (``keep_alive(until)`` keeps it coming) and reduce it."""
    from benchmarks.lib import xplane

    logdir = os.path.join(cell.scratch, "trace")
    t0 = time.perf_counter()
    with watch.device_trace(logdir) as out:
        keep_alive(t0 + float(cell.traffic["trace_seconds"]))
    t1 = time.perf_counter()
    if out["planes"] is None:
        return
    spans = [(_GAP_LABEL[s["name"]], s["start"], s["end"])
             for s in spans_between(profiler, base, t0 - 1.0, t1 + 1.0)
             if s["name"] in _GAP_LABEL]
    obs.trace = xplane.reduce(out["planes"], spans)
    cell.keep_trace(out)


def agrees_with_reference(cell, params, records, notes) -> bool:
    """For a seeded sample of completed requests: each emitted token's logit
    under the plain teacher-forced reference is within the stated tolerance
    of that position's best logit (``chip_smoke`` ``gap_to_plain_best``)."""
    import jax.numpy as jnp

    tol = cell.config["tolerances"]
    done = [r for r in records if r["tokens"]]
    if not done:
        notes["reference"] = "no completed request to check"
        return False
    rng = np.random.default_rng([cell.seed, 77])
    pick = rng.choice(len(done), size=min(len(done),
                                          int(tol["sample_requests"])),
                      replace=False)
    sample = [done[i] for i in sorted(pick)]
    K = max(len(r["tokens"]) for r in sample)
    T = cell.sizes["max_seq"]
    seqs = np.zeros((len(sample), T), np.int32)
    at = np.zeros((len(sample), K), np.int32)
    emitted = np.zeros((len(sample), K), np.int32)
    valid = np.zeros((len(sample), K), bool)
    for i, r in enumerate(sample):
        p, s = r["prompt"], np.asarray(r["tokens"], np.int32)
        seqs[i, :len(p)] = p
        seqs[i, len(p):len(p) + len(s)] = s[:T - len(p)]
        at[i, :len(s)] = len(p) - 1 + np.arange(len(s))
        emitted[i, :len(s)] = s
        valid[i, :len(s)] = True
    logits = model.reference(cell.config).logits_at(
        params, jnp.asarray(seqs), jnp.asarray(at), cell.sizes)
    took = jnp.take_along_axis(logits, jnp.asarray(emitted)[..., None],
                               axis=2)[..., 0]
    gap = np.asarray(logits.max(-1) - took)
    worst = float(np.max(np.where(valid, gap, 0.0)))
    notes["reference"] = {"requests": len(sample), "worst_gap": worst,
                          "first_token_gap": float(np.max(gap[:, 0]))}
    return worst <= tol["logit_gap"]


def finish(cell, obs, client, owed, params, registry, engine, compiles_before,
           signatures_before):
    """Shut the engine down, then decide ``correct``, ``attempted`` and
    ``failed`` over the requests the window was owed."""
    compiled_inside = cell.compiles.count - compiles_before
    signatures = engine.compiled_signatures()
    memory_peak = device.memory_peak_bytes(cell.devices)
    engine.shutdown()
    registry.shutdown()
    obs.requests = owed
    failed = [r for r in owed if r["tokens"] is None]
    reasons = {r["reason"] for r in owed if r["tokens"] is not None}
    lengths_ok = all(len(r["tokens"]) == r["max_new_tokens"]
                     for r in owed if r["tokens"] is not None)
    obs.facts["compiled_inside_window"] = compiled_inside
    notes = {"finish_reasons": sorted(map(str, reasons)),
             "signatures": [signatures_before, signatures]}
    correct = agrees_with_reference(cell, params, client.records, notes)
    correct = (correct and compiled_inside == 0
               and signatures == signatures_before
               and reasons <= {"max_tokens"} and lengths_ok)
    return {"correct": correct, "attempted": len(owed), "failed": len(failed),
            "observed": obs, "memory_peak_bytes": memory_peak, "notes": notes}
