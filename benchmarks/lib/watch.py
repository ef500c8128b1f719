"""Watching a window: compilations counted, and the device trace taken with
the benchmark's anchor on the host clock."""
from __future__ import annotations

import contextlib
import shutil
import time

from benchmarks.lib import xplane

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts every XLA compilation of the process (cache reads included),
    whoever asked for it. ``count`` read before and after a window says
    whether anything compiled inside it."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, _duration, **_kw):
        if event == _COMPILE_EVENT:
            self.count += 1


@contextlib.contextmanager
def device_trace(logdir: str):
    """Trace what runs inside, without the Python tracer, and anchor the
    host clock: yields a dict that holds ``planes`` after the block."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    out = {"planes": None}
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(
                xplane.ANCHOR, pc_ns=time.perf_counter_ns()):
            pass
        yield out
    finally:
        jax.profiler.stop_trace()
    path = xplane.newest_xplane(logdir)
    if path is not None:
        out["planes"] = xplane.load(path, keep_lines=(xplane.OPS_LINE,))
        out["path"] = path
