"""Profiling + numerical-panic tooling (ref: org.nd4j.linalg.profiler.
OpProfiler with ProfilerConfig's checkForNAN/checkForINF 'panic modes', and
deeplearning4j's PerformanceListener timing hooks — SURVEY.md §5.1).

The reference profiles per-op because each op is a discrete kernel launch.
Under XLA a whole train step is ONE fused executable, so per-Java-op timing is
meaningless here; the profiling unit is the **span** (a step, a data-load, an
eval pass) plus XLA's own kernel-level profiler:

- ``OpProfiler`` — named wall-clock spans, nestable, kept in a bounded ring
  and exported as a Chrome trace JSON (chrome://tracing / Perfetto loadable),
  the TPU analog of the reference's printOutDashboard(). Every span is also
  a ``jax.profiler.TraceAnnotation``: while a ``jax.profiler`` trace runs
  (the real per-kernel data the reference's OpProfiler approximates on CPU),
  the span is an event on that trace's host plane, on that trace's clock,
  beside the device operations it caused.
- panic modes — ``ProfilerConfig(checkForNAN=True)`` makes attached
  ``ProfilingListener``s scan score/params/grads each iteration and raise
  ``PanicException`` on the first non-finite value (ref:
  OpExecutionerUtil.checkForAny + ND4JOpProfilerException). Device-side
  reduction: one jitted ``isfinite`` all-reduce per tree, no host transfer of
  the tensors themselves.
- ``mfu()`` — model-flops-utilization calculator (``tools/bench_tf_import.py``;
  the benchmark keeps its own copy under ``benchmarks/lib/flops.py``).
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.optimize.listeners import TrainingListener


class PanicException(RuntimeError):
    """Non-finite value detected under panic mode (ref:
    ND4JOpProfilerException)."""


@dataclass
class ProfilerConfig:
    """(ref: org.nd4j.linalg.profiler.ProfilerConfig builder)."""

    checkForNAN: bool = False
    checkForINF: bool = False
    collectSpans: bool = True


# spans an ``OpProfiler`` keeps: a ring, the oldest dropped and counted (about
# 200 bytes a span; a serving engine records six to nine for every scheduler
# iteration, so this holds some minutes of a busy one)
SPAN_CAPACITY = 1 << 16


@dataclass
class _Span:
    name: str
    start_us: float          # offset from the profiler's ``base``
    dur_us: float
    tid: int
    args: Optional[dict] = None
    start: float = 0.0       # absolute ``time.perf_counter()`` seconds


@jax.jit
def _finite_report(leaves_stacked):
    """all-finite / any-nan / any-inf flags for a flat f32 vector."""
    return (jnp.all(jnp.isfinite(leaves_stacked)),
            jnp.any(jnp.isnan(leaves_stacked)),
            jnp.any(jnp.isinf(leaves_stacked)))


def check_tree_finite(tree, what: str, check_nan=True, check_inf=True):
    """Raise PanicException if any leaf of ``tree`` holds NaN (or Inf)."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype") and jnp.issubdtype(
                  jnp.asarray(l).dtype, jnp.inexact)]
    if not leaves:
        return
    flat = jnp.concatenate([jnp.ravel(jnp.asarray(l)).astype(jnp.float32)
                            for l in leaves])
    ok, has_nan, has_inf = _finite_report(flat)
    if bool(ok):
        return
    if check_nan and bool(has_nan):
        raise PanicException(f"NaN detected in {what} (panic mode)")
    if check_inf and bool(has_inf):
        raise PanicException(f"Inf detected in {what} (panic mode)")


class OpProfiler:
    """Span collector with Chrome-trace export.

    Use ``with profiler.span("train_step"):`` around anything; nesting is
    expressed via Chrome trace's duration-event stacking per thread. The
    newest ``SPAN_CAPACITY`` spans are kept; ``dropped`` counts the
    older ones that made room.
    """

    _instance: Optional["OpProfiler"] = None

    def __init__(self, config: Optional[ProfilerConfig] = None):
        self.config = config or ProfilerConfig()
        self._spans: deque = deque(maxlen=SPAN_CAPACITY)
        self.dropped = 0
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    @classmethod
    def getInstance(cls) -> "OpProfiler":
        if cls._instance is None:
            cls._instance = OpProfiler()
        return cls._instance

    @property
    def base(self) -> float:
        """The ``time.perf_counter()`` reading ``start_us`` counts from."""
        return self._t0

    def reset(self):
        with self._lock:
            self._spans.clear()
            self.dropped = 0
            self._t0 = time.perf_counter()

    def record(self, name: str, start: float, end: float, tid: int,
               args: Optional[dict] = None):
        """Keep one finished span; ``start`` and ``end`` are
        ``time.perf_counter()`` readings."""
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(_Span(
                name=name, start_us=(start - self._t0) * 1e6,
                dur_us=(end - start) * 1e6, tid=tid, args=args or None,
                start=start))

    @contextmanager
    def span(self, name: str, **args):
        """Time the block as a span named ``name`` carrying ``args``, and
        show it in a running ``jax.profiler`` trace (with no trace running
        the annotation is a flag test). Yields ``args``: what the block adds
        to it is kept with the span; the trace event has only what was known
        on entry."""
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield args
        finally:
            if self.config.collectSpans:
                self.record(name, start, time.perf_counter(),
                            threading.get_ident() % 100000, args)

    @property
    def spans(self) -> List[_Span]:
        with self._lock:
            return list(self._spans)

    def summary(self) -> dict:
        """name -> {count, total_ms, mean_ms} (ref: printOutDashboard)."""
        agg: dict = {}
        for s in self.spans:
            d = agg.setdefault(s.name, {"count": 0, "total_ms": 0.0})
            d["count"] += 1
            d["total_ms"] += s.dur_us / 1000.0
        for d in agg.values():
            d["mean_ms"] = d["total_ms"] / d["count"]
        return agg

    def export_chrome_trace(self, path: str, tracer=None) -> str:
        """Chrome-trace JSON of the collected spans (pid 1). Pass a
        ``serving.tracing.Tracer`` to merge its retained request traces
        into the same file on the same perf_counter clock — serving lanes
        (one pid per engine, one tid per request) render beside the
        training spans in one Perfetto view."""
        events = [{"name": s.name, "ph": "X", "ts": s.start_us,
                   "dur": s.dur_us, "pid": 1, "tid": s.tid,
                   **({"args": s.args} if s.args else {})}
                  for s in self.spans]
        if tracer is not None:
            # name this process's lane only in the merged view (the
            # plain export stays exactly the span events)
            events.append({"ph": "M", "name": "process_name", "pid": 1,
                           "args": {"name": "training"}})
            events.extend(tracer.chrome_events(t0=self._t0))
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path


class ProfilingListener(TrainingListener):
    """Per-iteration spans + panic checks as a listener (ref: the reference
    enables OpProfiler globally via Nd4j environment; here it attaches to the
    fit loop it should watch)."""

    def __init__(self, profiler: Optional[OpProfiler] = None,
                 config: Optional[ProfilerConfig] = None,
                 checkParams: bool = True, checkGradients: bool = True):
        self.profiler = profiler or OpProfiler.getInstance()
        if config is not None:
            self.profiler.config = config
        self.checkParams = checkParams
        self.checkGradients = checkGradients
        self._last_t: Optional[float] = None

    @property
    def requiresGradients(self) -> bool:
        cfg = self.profiler.config
        return self.checkGradients and (cfg.checkForNAN or cfg.checkForINF)

    def iterationDone(self, model, iteration, epoch):
        now = time.perf_counter()
        if self._last_t is not None and self.profiler.config.collectSpans:
            self.profiler.record(
                "iteration", self._last_t, now, 0,
                {"iteration": iteration, "epoch": epoch})
        self._last_t = now

        cfg = self.profiler.config
        if not (cfg.checkForNAN or cfg.checkForINF):
            return
        score = model.score()
        if cfg.checkForNAN and np.isnan(score):
            raise PanicException(f"NaN score at iteration {iteration} (panic mode)")
        if cfg.checkForINF and np.isinf(score):
            raise PanicException(f"Inf score at iteration {iteration} (panic mode)")
        if self.checkParams:
            check_tree_finite(model._params, f"parameters@iter{iteration}",
                              cfg.checkForNAN, cfg.checkForINF)
        grads = getattr(model, "_last_grads", None)
        if self.checkGradients and grads is not None:
            check_tree_finite(grads, f"gradients@iter{iteration}",
                              cfg.checkForNAN, cfg.checkForINF)


def mfu(tokens_per_sec: float, flops_per_token: float,
        peak_flops: float) -> float:
    """Model FLOPs utilization against ``peak_flops``, the device's peak
    from :func:`peak_flops` (no default: the peak is the device's)."""
    return tokens_per_sec * flops_per_token / peak_flops


# ---- THE single flop-counting basis for committed MFU numbers --------
# Round-5 verdict #5: one record quoted analytic-flop MFU (~61%) and
# another XLA-counted MFU (56.6%) for the same workload, neither stating
# its basis. Every MFU uses ``MFU_BASIS`` below (XLA's cost analysis counts
# implementation flops — e.g. attention-softmax rebuilds, remat — so it
# sits a few points off the analytic model number; they answer different
# questions).

MFU_BASIS = "analytic_model_flops: 6*N_nonemb + 12*L*H*T per token"

# bf16 peak FLOP/s of one chip, keyed by the ``device_kind`` string a JAX
# device of that generation reports (read off each generation's topology
# description under the installed libtpu). Source of the peaks: Google
# Cloud TPU documentation, the system-architecture page of each generation
# ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e").
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5": 459e12,           # v5p
    "TPU v6 lite": 918e12,      # v6e
}


def peak_flops(device) -> float:
    """bf16 peak of a jax device by its exact ``device_kind``. A device
    that is not in the table is an error, not a default: a utilization
    against an assumed peak is not a measurement."""
    kind = getattr(device, "device_kind", None)
    if kind not in PEAK_FLOPS:
        raise ValueError(
            f"no published bf16 peak for device_kind {kind!r} (platform "
            f"{getattr(device, 'platform', None)!r}); known kinds: "
            f"{sorted(PEAK_FLOPS)}. Add the kind with its source to "
            "profiler.PEAK_FLOPS before computing an MFU on it.")
    return PEAK_FLOPS[kind]


def device_record() -> dict:
    """What this process runs on, as JAX reports it. Every result a bench
    or tool prints carries this, so no number is read without its device."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def transformer_flops_per_token(n_params_non_embedding: int, layers: int,
                                hidden: int, seq_len: int) -> float:
    """Analytic model flops per trained token for a dense transformer:
    6*N (fwd 2N + bwd 4N matmul flops on non-embedding params) plus the
    attention interior 12*L*H*T (QK^T + PV, fwd+bwd). The standard
    PaLM-appendix accounting; no remat recompute included."""
    return 6 * n_params_non_embedding + 12 * layers * hidden * seq_len

